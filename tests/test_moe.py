"""Mixture-of-experts + expert parallelism (parallel/moe.py) — the
optional-stretch EP axis beyond the reference's DP (SURVEY.md §2.9).

Contracts:
* the one-hot dispatch/combine formulation equals a per-token reference
  loop (when capacity is ample);
* capacity overflow drops tokens (zero contribution), never corrupts;
* the EP (all_to_all) layout is numerically identical to the dense
  formulation with the full expert set;
* the Switch aux loss is 1 at uniform routing;
* gradients flow to router and experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.moe import (
    MoEParams,
    init_moe_params,
    moe_mlp,
    moe_mlp_ep,
)

# Compiled once a shape: op by op the one-hot formulation dispatches (and
# compiles) a few hundred primitives a call.  The calls inside ``jax.grad``
# and ``shard_map`` below are traced already and keep the plain function.
moe_mlp_jit = jax.jit(moe_mlp, static_argnames=(
    "top_k", "capacity_factor", "group_size"))

EP = 4
AXIS = "ep"
D, FF, E = 16, 32, 8


def _x(seed=0, b=2, s=12):
    return jnp.asarray(
        np.random.RandomState(seed).randn(b, s, D), jnp.float32
    ) * 0.5


def _params(seed=0):
    return init_moe_params(jax.random.PRNGKey(seed), D, FF, E)


def _reference_loop(x, p: MoEParams, top_k: int):
    """Per-token routing loop (no capacity limits): the semantics the
    one-hot formulation must reproduce when capacity is ample."""
    b, s, d = x.shape
    x2 = np.asarray(x.reshape(-1, d), np.float64)
    router = np.asarray(p.router, np.float64)
    out = np.zeros_like(x2)
    for t in range(x2.shape[0]):
        logits = x2[t] @ router
        gates = np.exp(logits - logits.max())
        gates = gates / gates.sum()
        picks = np.argsort(-gates)[:top_k]
        weights = gates[picks] / gates[picks].sum()
        for w, e in zip(weights, picks):
            h = x2[t] @ np.asarray(p.w1[e], np.float64) \
                + np.asarray(p.b1[e], np.float64)
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            y = h @ np.asarray(p.w2[e], np.float64) \
                + np.asarray(p.b2[e], np.float64)
            out[t] += w * y
    return out.reshape(b, s, d)


@pytest.mark.parametrize("top_k", [1, 2])
def test_dense_matches_reference_loop(top_k):
    x, p = _x(), _params()
    y, aux = moe_mlp_jit(x, p, top_k=top_k, capacity_factor=100.0)
    ref = _reference_loop(x, p, top_k)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4, rtol=1e-4)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_capacity_overflow_drops_not_corrupts():
    """capacity_factor tiny -> most tokens dropped; the kept ones still
    match the reference loop's value, dropped ones are exactly zero."""
    x, p = _x(1), _params(1)
    y, _ = moe_mlp_jit(x, p, top_k=1, capacity_factor=0.01)  # capacity=1
    ref = _reference_loop(x, p, 1)
    y2 = np.asarray(y).reshape(-1, D)
    r2 = ref.reshape(-1, D)
    kept = ~np.all(y2 == 0.0, axis=1)
    assert kept.sum() >= 1  # at least one slot per expert exists
    assert (~kept).sum() >= 1  # and the tiny capacity dropped some
    np.testing.assert_allclose(y2[kept], r2[kept], atol=1e-4, rtol=1e-4)


def test_uniform_router_aux_is_one():
    x = _x(2)
    p = _params(2)._replace(router=jnp.zeros((D, E)))  # uniform gates
    _, aux = moe_mlp_jit(x, p, top_k=2)
    # ce is exactly 1/E; me depends on argmax ties -> me sums to 1,
    # aux = E * sum(me * 1/E) = 1 regardless of tie-breaking
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_ep_matches_dense_per_shard():
    """moe_mlp_ep over a 4-way mesh == dense moe_mlp applied to each
    rank's token shard with the full expert set."""
    mesh = Mesh(np.asarray(jax.devices()[:EP]), (AXIS,))
    x = _x(3, b=EP * 2, s=8)
    p = _params(3)

    def local(x_l, router, w1, b1, w2, b2):
        lp = MoEParams(router, w1, b1, w2, b2)
        y, aux = moe_mlp_ep(x_l, lp, AXIS, top_k=2)
        return y, aux

    fwd = jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P()),
            check_vma=False,
        )
    )
    y_ep, aux_ep = fwd(x, p.router, p.w1, p.b1, p.w2, p.b2)

    ys, auxs = [], []
    per = x.shape[0] // EP
    for r in range(EP):
        y_r, aux_r = moe_mlp_jit(x[r * per:(r + 1) * per], p, top_k=2)
        ys.append(np.asarray(y_r))
        auxs.append(float(aux_r))
    np.testing.assert_allclose(
        np.asarray(y_ep), np.concatenate(ys), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(float(aux_ep), np.mean(auxs), rtol=1e-5)


def test_gradients_flow():
    x, p = _x(4), _params(4)

    def loss(p):
        y, aux = moe_mlp(x, p, top_k=2)
        return (y ** 2).mean() + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(p)
    for name, g in grads._asdict().items():
        arr = np.asarray(g)
        assert np.all(np.isfinite(arr)), name
        assert np.abs(arr).max() > 0, f"no gradient signal in {name}"


def test_gpt_moe_trains_and_sows_aux():
    """TransformerConfig.moe_experts wires MoE MLPs into every block:
    the model trains, and the per-block aux losses are retrievable via
    the 'losses' collection."""
    import optax

    from horovod_tpu.models.transformer import gpt

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, size=(2, 16)), jnp.int32
    )
    # two of nano's three blocks and sixteen positions: every block is
    # the same expert block, and the assertions ask for no size
    model = gpt("nano", num_layers=2, moe_experts=4, dtype=jnp.float32)
    # compiled, here and below: op by op the initialisers, the backward
    # pass and the update dispatch (and compile) a few thousand primitives
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    # every block carries expert weights instead of fc1/fc2
    assert "w1" in params["params"]["block0"]
    assert "fc1" not in params["params"]["block0"]

    def loss_fn(p):
        logits, state = model.apply(p, tokens, mutable=["losses"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens
        ).mean()
        aux = sum(jax.tree_util.tree_leaves(state["losses"]))
        return nll + 0.01 * aux, (nll, aux)

    tx = optax.adam(1e-3)

    @jax.jit
    def step(params, opt_state):
        (loss, (nll, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, nll, aux

    opt_state = tx.init(params)
    losses = []
    for _ in range(3):
        params, opt_state, nll, aux = step(params, opt_state)
        losses.append(float(nll))
        assert np.isfinite(float(aux))
    assert losses[-1] < losses[0], f"MoE model did not train: {losses}"


def test_ep_gradient_recipe_matches_dense():
    """The documented EP training recipe (pmean router grad, expert grads
    scaled 1/P) yields exactly the gradients of the global objective
    'mean of per-rank losses' — no mesh-size-dependent scale on experts
    (docs/moe.md training contract)."""
    mesh = Mesh(np.asarray(jax.devices()[:EP]), (AXIS,))
    x = _x(5, b=EP * 2, s=8)
    p = _params(5)
    per = x.shape[0] // EP

    def loss_shard(p, xr):
        y, aux = moe_mlp(xr, p, top_k=2)
        return (y ** 2).mean() + 0.01 * aux

    def loss_dense(p):
        return sum(
            loss_shard(p, x[r * per:(r + 1) * per]) for r in range(EP)
        ) / EP

    g_dense = jax.jit(jax.grad(loss_dense))(p)

    def local_grads(router, w1, b1, w2, b2, x_l):
        lp = MoEParams(router, w1, b1, w2, b2)

        def loss_fn(lp):
            y, aux = moe_mlp_ep(x_l, lp, AXIS, top_k=2)
            return (y ** 2).mean() + 0.01 * aux

        g = jax.grad(loss_fn)(lp)
        return MoEParams(
            router=jax.lax.pmean(g.router, AXIS),
            w1=g.w1 / EP, b1=g.b1 / EP, w2=g.w2 / EP, b2=g.b2 / EP,
        )

    g_ep = jax.jit(
        shard_map(
            local_grads, mesh=mesh,
            in_specs=(P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=MoEParams(P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            check_vma=False,
        )
    )(p.router, p.w1, p.b1, p.w2, p.b2, x)

    np.testing.assert_allclose(np.asarray(g_ep.router),
                               np.asarray(g_dense.router),
                               atol=2e-6, rtol=2e-5)
    for name in ("w1", "b1", "w2", "b2"):
        np.testing.assert_allclose(
            np.asarray(getattr(g_ep, name)),
            np.asarray(getattr(g_dense, name)),
            atol=2e-6, rtol=2e-5, err_msg=name,
        )


def test_grouped_routing_matches_reference_loop():
    """Routing within groups (the linear-memory GShard grouping) still
    matches the per-token loop when capacity is ample, across group
    boundaries (n=24, group_size=8 -> 3 groups)."""
    x, p = _x(6), _params(6)  # n = 24 tokens
    y, aux = moe_mlp_jit(x, p, top_k=2, capacity_factor=100.0, group_size=8)
    ref = _reference_loop(x, p, 2)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4, rtol=1e-4)
    assert np.isfinite(float(aux))


def test_ep_grouped_matches_dense_grouped():
    """EP with multi-group routing == dense per shard with the same
    group size."""
    mesh = Mesh(np.asarray(jax.devices()[:EP]), (AXIS,))
    x = _x(7, b=EP * 2, s=8)  # 16 local tokens per rank
    p = _params(7)

    def local(x_l, router, w1, b1, w2, b2):
        lp = MoEParams(router, w1, b1, w2, b2)
        return moe_mlp_ep(x_l, lp, AXIS, top_k=2, group_size=8)[0]

    y_ep = jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=P(AXIS), check_vma=False,
        )
    )(x, p.router, p.w1, p.b1, p.w2, p.b2)
    per = x.shape[0] // EP
    ys = [np.asarray(moe_mlp_jit(x[r * per:(r + 1) * per], p, top_k=2,
                                 group_size=8)[0]) for r in range(EP)]
    np.testing.assert_allclose(
        np.asarray(y_ep), np.concatenate(ys), atol=2e-5, rtol=2e-5
    )


def test_padded_group_routing_matches_reference_loop():
    """Token counts that don't divide the group pad with invalid rows
    (never shrink to a tiny-divisor group): n=22, group_size=8 -> groups
    of 8 with 2 padding rows, which claim no capacity; output still
    matches the per-token loop and padding contributes nothing."""
    x = jnp.asarray(
        np.random.RandomState(8).randn(2, 11, D), jnp.float32
    ) * 0.5  # n = 22
    p = _params(8)
    y, aux = moe_mlp_jit(x, p, top_k=2, capacity_factor=100.0, group_size=8)
    ref = _reference_loop(x, p, 2)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-4, rtol=1e-4)
    assert np.isfinite(float(aux))
