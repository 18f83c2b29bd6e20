"""Megatron-style tensor parallelism (parallel/tensor_parallel.py) — the
optional-stretch axis beyond the reference's DP (SURVEY.md §2.9).

Contract: tp_gpt_apply over a tp-axis mesh reproduces the unsharded
GPT.apply exactly (fp32, up to associativity), forward AND gradients,
with each rank holding only whole-head / width shards of the block
weights and exactly two psums per block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models.transformer import gpt
from horovod_tpu.parallel.tensor_parallel import (
    stack_tp_params,
    tp_gpt_apply,
)

TP = 4
AXIS = "tp"


def _mesh():
    return Mesh(np.asarray(jax.devices()[:TP]), (AXIS,))


def _model(**overrides):
    common = dict(num_layers=2, num_heads=4, emb_dim=64, max_len=64,
                  vocab_size=512, dtype=jnp.float32,
                  attention_impl="reference")
    common.update(overrides)
    return gpt("nano", **common)


def _tokens(seed=0, s=32):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 512, (2, s)), jnp.int32
    )


def _tp_fwd(model, params, tokens):
    sharded, replicated = stack_tp_params(params, model.cfg, TP)

    def local(sharded, replicated, tok):
        return tp_gpt_apply(sharded, replicated, model.cfg, tok, AXIS)

    fwd = jax.jit(
        shard_map(
            local, mesh=_mesh(),
            in_specs=(P(AXIS), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )
    return fwd(sharded, replicated, tokens)


@pytest.mark.parametrize("pos_embedding", ["learned", "rope"])
def test_tp_matches_single_device(pos_embedding):
    model = _model(pos_embedding=pos_embedding)
    tokens = _tokens()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    ref = jax.jit(model.apply)(params, tokens)
    out = _tp_fwd(model, params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_tp_gqa_matches_single_device():
    # TRUE GQA: kv_heads (4) < num_heads (8), both divisible by tp
    model = _model(num_heads=8, num_kv_heads=4)
    tokens = _tokens(1)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)
    ref = jax.jit(model.apply)(params, tokens)
    out = _tp_fwd(model, params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_tp_gradients_match():
    """Grads w.r.t. the SHARDED weights equal the matching slices of the
    unsharded model's grads (column/row splits commute with autodiff).
    check_vma=True (replication tracking) is what makes the psum
    transpose correct — see the tp-scaling pin below."""
    model = _model()
    tokens = _tokens(2)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), tokens)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_ref(p):
        logits = model.apply(p, tokens)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1
        ).mean()

    g_ref = jax.jit(jax.grad(loss_ref))(params)["params"]
    sharded, replicated = stack_tp_params(params, model.cfg, TP)

    def local_loss(sharded, replicated, tok, tgt):
        logits = tp_gpt_apply(sharded, replicated, model.cfg, tok, AXIS)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgt[..., None], -1
        ).mean()

    grad_fn = jax.jit(
        shard_map(
            jax.grad(local_loss), mesh=_mesh(),
            in_specs=(P(AXIS), P(), P(), P()), out_specs=P(AXIS),
            check_vma=True,
        )
    )
    g_tp = grad_fn(sharded, replicated, tokens, targets)
    # qkv kernel shard 0 of the stacked grads == the reference grad's
    # matching column block (rank 0 holds q head 0 + k/v head 0)
    cfg = model.cfg
    hd = cfg.head_dim
    blk_ref = g_ref["block0"]["qkv"]["kernel"]
    emb = cfg.emb_dim
    want = np.concatenate([
        np.asarray(blk_ref[:, :hd]),                   # q head 0
        np.asarray(blk_ref[:, emb:emb + hd]),          # k head 0
        np.asarray(blk_ref[:, 2 * emb:2 * emb + hd]),  # v head 0
    ], axis=1)
    got = np.asarray(g_tp["block0"]["qkv"]["kernel"][0])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # fc2 row shard: rank 0 holds the first width/TP rows
    rows = (cfg.mlp_ratio * cfg.emb_dim) // TP
    np.testing.assert_allclose(
        np.asarray(g_tp["block0"]["fc2"]["kernel"][0]),
        np.asarray(g_ref["block0"]["fc2"]["kernel"][:rows]),
        atol=2e-4, rtol=2e-4,
    )


def test_tp_replicated_stacking_scales_grads():
    """Pin the failure mode stack_tp_params' split exists to prevent:
    pass the replicated weights STACKED-AND-SHARDED instead of truly
    replicated and the sharded-weight grads come out scaled by tp."""
    from jax import lax

    mesh = _mesh()
    W = jnp.asarray(np.random.RandomState(0).randn(TP, 2, 3), jnp.float32)
    H = jnp.asarray(np.random.RandomState(2).randn(3, 5), jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 2), jnp.float32)

    def loss_full(W):
        y = sum(x @ W[r] for r in range(TP))
        return ((y @ H) ** 2).sum()

    g_full = jax.grad(loss_full)(W)

    Hs = jnp.broadcast_to(H[None], (TP,) + H.shape)

    def ll_stacked(Wr, Hs, x):
        y = lax.psum(x @ Wr[0], AXIS)
        return ((y @ Hs[0]) ** 2).sum()

    g_bad = jax.jit(shard_map(
        jax.grad(ll_stacked), mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P()), out_specs=P(AXIS),
        check_vma=True,
    ))(W, Hs, x)
    ratio = float(np.median(np.asarray(g_bad) / np.asarray(g_full)))
    assert abs(ratio - TP) < 1e-3, f"expected the {TP}x artifact, {ratio}"

    def ll_rep(Wr, H, x):
        y = lax.psum(x @ Wr[0], AXIS)
        return ((y @ H) ** 2).sum()

    g_good = jax.jit(shard_map(
        jax.grad(ll_rep), mesh=mesh,
        in_specs=(P(AXIS), P(), P()), out_specs=P(AXIS),
        check_vma=True,
    ))(W, H, x)
    np.testing.assert_allclose(np.asarray(g_good), np.asarray(g_full),
                               rtol=1e-5)


def test_tp_divisibility_errors():
    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), _tokens())
    with pytest.raises(ValueError, match="must divide num_heads"):
        stack_tp_params(params, model.cfg, 3)


def test_unstack_tp_round_trips():
    """stack_tp_params -> unstack_tp_params is the identity (the
    docs/inference.md column/row-split inversion as code); a wrong tp
    raises instead of reassembling a correct-shaped scrambled kernel."""
    import pytest
    from conftest import assert_trees_equal
    from horovod_tpu.parallel.tensor_parallel import unstack_tp_params

    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(8), _tokens())["params"]
    sharded, replicated = stack_tp_params({"params": params},
                                          model.cfg, 2)
    assert_trees_equal(
        unstack_tp_params(sharded, replicated, model.cfg, 2), params
    )
    with pytest.raises(ValueError, match="leading dim"):
        unstack_tp_params(sharded, replicated, model.cfg, 4)
