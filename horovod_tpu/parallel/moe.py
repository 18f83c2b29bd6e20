"""Mixture-of-experts with expert parallelism (EP).

Beyond reference parity (Horovod 0.19.1 is data-parallel only,
SURVEY.md §2.9): a GShard-style MoE MLP for the transformer family,
TPU-first —

* **static shapes everywhere**: top-k routing becomes one-hot
  dispatch/combine tensors with a fixed per-expert capacity, so the
  whole layer is einsums the MXU eats (no gather/scatter, no dynamic
  sizes);
* capacity overflow DROPS tokens (they ride the residual), the standard
  Switch/GShard behavior;
* an auxiliary load-balancing loss (Switch formulation: E * sum over
  experts of fraction-of-tokens x mean-gate) keeps routing spread;
* **expert parallelism**: experts shard over a mesh axis; tokens reach
  their expert's owner through one ``lax.all_to_all`` each way — the
  EP result is EXACTLY the dense formulation's (same math, different
  layout), pinned by tests/test_moe.py.

Layout contract for :func:`moe_mlp_ep` — call inside ``shard_map`` with
tokens sharded over the axis and the expert weights sharded on their
leading (expert) dim; every rank must carry the same token count.

A second, **dropless** core stands beside that path (the end of this
file: :func:`route`, :func:`grouped_ffn`, :func:`routed_experts`): scores
over all experts, top-k, the chosen (token, expert) rows sorted by
expert, a grouped matmul over the experts THIS chip holds, the weighted
sum back.  No capacity, so nothing is dropped; the layer is told which
experts it holds (``first_held``, and as many as its weights have),
routes over all of them and computes its own experts' part of the
result.  On one chip it runs without its exchange.  The GShard path
above is as it was; ROADMAP C6 has the folding of the two.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes

__all__ = ["init_moe_params", "moe_mlp", "moe_mlp_ep", "MoEParams",
           "Routing", "route", "grouped_ffn", "routed_experts",
           "rebalanced", "publish_stats"]

# Initialization scheme, shared by the raw-NamedTuple and flax paths so
# the two can never drift: small-normal router, fan-in-scaled FFN.
ROUTER_STD = 0.02


def _ffn_scales(d: int, ff: int):
    return (2.0 / d) ** 0.5, (2.0 / ff) ** 0.5


# Routing group size: tokens route within fixed-size groups (GShard
# grouping), so dispatch/combine stay O(n * group) instead of O(n^2) —
# at group 4096 and cf=2, a layer's routing tensors are bounded at
# ~n * 16k floats regardless of sequence length.
DEFAULT_GROUP_SIZE = 4096


class MoEParams(NamedTuple):
    """Weights of one MoE MLP: router + E experts' FFNs."""

    router: jax.Array  # [d, E]
    w1: jax.Array      # [E, d, ff]
    b1: jax.Array      # [E, ff]
    w2: jax.Array      # [E, ff, d]
    b2: jax.Array      # [E, d]


def init_moe_params(key, d: int, ff: int, num_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    kr, k1, k2 = jax.random.split(key, 3)
    s1, s2 = _ffn_scales(d, ff)
    return MoEParams(
        router=(jax.random.normal(kr, (d, num_experts)) * ROUTER_STD
                ).astype(dtype),
        w1=(jax.random.normal(k1, (num_experts, d, ff)) * s1).astype(dtype),
        b1=jnp.zeros((num_experts, ff), dtype),
        w2=(jax.random.normal(k2, (num_experts, ff, d)) * s2).astype(dtype),
        b2=jnp.zeros((num_experts, d), dtype),
    )


def _routing(x2, router, num_experts: int, top_k: int, capacity: int,
             valid=None):
    """Shared routing math on flat tokens ``x2 [n, d]``.

    Returns ``(dispatch [n, E, C], combine [n, E, C], aux_loss)`` —
    the GShard one-hot formulation: ``dispatch`` says which (expert,
    capacity-slot) each token occupies; ``combine`` carries the gate
    weight on the same slot.  ``valid [n]`` (optional bool) marks real
    tokens: padding rows claim no capacity slots and are excluded from
    the aux statistics.
    """
    n = x2.shape[0]
    if valid is None:
        valid = jnp.ones((n,), jnp.float32)
    else:
        valid = valid.astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    logits = (x2.astype(jnp.float32) @ router.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)  # [n, E]

    # Switch/GShard aux loss on the FULL distribution (before top-k):
    # E * sum_e mean_tokens_to_e * mean_gate_e ; == 1 when uniform.
    # importance = fraction of (valid) tokens whose top-1 is e
    top1 = jnp.argmax(gates, axis=-1)
    me = (jax.nn.one_hot(top1, num_experts) * valid[:, None]
          ).sum(0) / n_valid
    ce = (gates * valid[:, None]).sum(0) / n_valid
    aux_loss = num_experts * jnp.sum(me * ce)

    dispatch = jnp.zeros((n, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
    remaining = gates
    # fill[e] = next free capacity slot of expert e, advanced per k-round
    fill = jnp.zeros((num_experts,), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)            # [n]
        gate_k = jnp.take_along_axis(
            remaining, idx[:, None], axis=-1
        )[:, 0]
        onehot = jax.nn.one_hot(idx, num_experts) * valid[:, None]
        # position of each token within its expert's queue this round
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1.0)   # [n, E]
        slot = (pos_in_e * onehot).sum(-1).astype(jnp.int32) \
            + jnp.take(fill, idx)                       # [n]
        keep = slot < capacity                          # overflow drops
        slot_oh = jax.nn.one_hot(
            jnp.where(keep, slot, capacity), capacity + 1
        )[:, :capacity]                                 # [n, C]
        d_k = onehot[:, :, None] * slot_oh[:, None, :]  # [n, E, C]
        dispatch = dispatch + d_k
        combine = combine + d_k * gate_k[:, None, None]
        fill = fill + jnp.sum(
            onehot * keep[:, None], axis=0
        ).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)          # mask chosen expert
    # normalize combine weights over the selected experts per token
    denom = combine.sum(axis=(1, 2), keepdims=True)
    combine = jnp.where(denom > 0, combine / jnp.maximum(denom, 1e-9), 0.0)
    return dispatch, combine, aux_loss


def _expert_ffn(buf, w1, b1, w2, b2, dtype, act_store_dtype=None):
    """Batched expert FFN on ``buf [E_local, C, d]``.  When
    ``act_store_dtype`` is set, the gelu intermediate (the 4x-wide
    saved activation) materializes at that dtype — the MoE leg of the
    transformer's opt-in fp8 activation storage
    (models/transformer.py act_store)."""
    h = jnp.einsum("ecd,edf->ecf", buf.astype(dtype), w1.astype(dtype))
    h = jax.nn.gelu(h + b1[:, None, :].astype(dtype))
    if act_store_dtype is not None:
        h = jnp.asarray(jnp.asarray(h, act_store_dtype), dtype)
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(dtype))
    return out + b2[:, None, :].astype(dtype)


def _grouped_routing(x2, router, num_experts, top_k, capacity_factor,
                     group_size):
    """Route within fixed-size token groups (vmapped _routing): returns
    ``(xg [G,g,d], dispatch [G,g,E,C], combine [G,g,E,C], capacity,
    aux, n)`` with per-group capacity, keeping routing memory linear in
    n.  Token counts that don't divide by the group PAD with invalid
    rows (they claim no capacity and skew no statistics) rather than
    shrinking the group — a tiny divisor would make per-group capacity
    ~1 and silently drop most tokens."""
    n, d = x2.shape
    g = min(group_size, n)
    pad = (-n) % g
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    valid = (jnp.arange(n + pad) < n)
    xg = x2.reshape((n + pad) // g, g, d)
    vg = valid.reshape((n + pad) // g, g)
    capacity = max(1, int(-(-capacity_factor * g * top_k // num_experts)))
    dispatch, combine, aux = jax.vmap(
        lambda xx, vv: _routing(xx, router, num_experts, top_k, capacity,
                                valid=vv)
    )(xg, vg)
    return xg, dispatch, combine, capacity, aux.mean(), n


def moe_mlp(x, params: MoEParams, *, top_k: int = 2,
            capacity_factor: float = 2.0,
            group_size: int = DEFAULT_GROUP_SIZE,
            dtype=jnp.float32, act_store_dtype=None):
    """Dense (single-device / data-parallel) MoE MLP.

    ``x [b, s, d]`` -> ``(y [b, s, d], aux_loss)``.  Tokens route within
    groups of <= ``group_size``; capacity =
    ``ceil(capacity_factor * group * top_k / E)`` slots per expert per
    group; overflow tokens pass through with zero MLP contribution
    (residual-only).
    """
    b, s, d = x.shape
    num_experts = params.router.shape[1]
    n = b * s
    x2 = x.reshape(n, d)
    xg, dispatch, combine, capacity, aux, n = _grouped_routing(
        x2, params.router, num_experts, top_k, capacity_factor, group_size
    )
    G = xg.shape[0]
    buf = jnp.einsum("gnec,gnd->gecd", dispatch, xg.astype(jnp.float32))
    buf = buf.transpose(1, 0, 2, 3).reshape(num_experts, G * capacity, d)
    out = _expert_ffn(buf, params.w1, params.b1, params.w2, params.b2,
                      dtype, act_store_dtype)
    out = out.reshape(num_experts, G, capacity, d).transpose(1, 0, 2, 3)
    y = jnp.einsum("gnec,gecd->gnd", combine, out.astype(jnp.float32))
    y = y.reshape(-1, d)[:n]  # drop padding rows
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_mlp_ep(x, params: MoEParams, ep_axis: str, *, top_k: int = 2,
               capacity_factor: float = 2.0,
               group_size: int = DEFAULT_GROUP_SIZE, dtype=jnp.float32,
               act_store_dtype=None):
    """Expert-parallel MoE MLP: call inside ``shard_map``.

    Sharding: ``x [b_local, s, d]`` tokens sharded over ``ep_axis``;
    ``params.w1/b1/w2/b2`` sharded on the leading expert dim
    (``E_local = E / P`` per rank); ``params.router`` replicated.
    Per-expert capacity counts LOCAL tokens, so global capacity per
    expert is identical to the dense formulation run per shard.

    Two ``lax.all_to_all`` (tokens to expert owners and back); result is
    numerically identical to :func:`moe_mlp` applied shard-wise with the
    full expert set.
    """
    p = lax.axis_size(ep_axis)
    b, s, d = x.shape
    e_local = params.w1.shape[0]
    num_experts = e_local * p
    if params.router.shape[1] != num_experts:
        # without this, out-of-range expert indices one-hot to zero and
        # tokens silently ride the residual
        raise ValueError(
            f"router has {params.router.shape[1]} experts but the sharded "
            f"weights imply {e_local} x {p} ranks = {num_experts}"
        )
    n = b * s
    x2 = x.reshape(n, d)
    xg, dispatch, combine, capacity, aux, n = _grouped_routing(
        x2, params.router, num_experts, top_k, capacity_factor, group_size
    )
    G = xg.shape[0]
    cap_total = G * capacity
    # local per-expert buffers for ALL experts, then ship each expert
    # group to its owner: [E, G*C, d] -> a2a over the expert dim ->
    # [P * E_local tiles] == this rank's experts' tokens from every rank
    buf = jnp.einsum("gnec,gnd->gecd", dispatch, xg.astype(jnp.float32))
    buf = buf.transpose(1, 0, 2, 3).reshape(num_experts, cap_total, d)
    buf = buf.reshape(p, e_local, cap_total, d)
    buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                         tiled=False)          # [P, e_local, G*C, d]
    buf = buf.transpose(1, 0, 2, 3).reshape(e_local, p * cap_total, d)
    out = _expert_ffn(buf, params.w1, params.b1, params.w2, params.b2,
                      dtype, act_store_dtype)
    out = out.reshape(e_local, p, cap_total, d).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                         tiled=False)          # [P, e_local, G*C, d] home
    out = out.reshape(num_experts, G, capacity, d).transpose(1, 0, 2, 3)
    y = jnp.einsum("gnec,gecd->gnd", combine, out.astype(jnp.float32))
    y = y.reshape(-1, d)[:n]  # drop padding rows
    # aux is a per-shard statistic; average it so every rank agrees
    aux = lax.pmean(aux, ep_axis)
    return y.reshape(b, s, d).astype(x.dtype), aux


# --------------------------------------------------------------------- flax

def moe_flax_params(module, d: int, ff: int, num_experts: int) -> MoEParams:
    """Declare the MoE weights on a flax module (fp32 params, like the
    rest of the model family; compute casts per call)."""
    import flax.linen as nn  # noqa: PLC0415

    s1, s2 = _ffn_scales(d, ff)
    return MoEParams(
        router=module.param(
            "router", nn.initializers.normal(ROUTER_STD), (d, num_experts),
            jnp.float32,
        ),
        w1=module.param(
            "w1", nn.initializers.normal(s1), (num_experts, d, ff),
            jnp.float32,
        ),
        b1=module.param(
            "b1", nn.initializers.zeros, (num_experts, ff), jnp.float32
        ),
        w2=module.param(
            "w2", nn.initializers.normal(s2), (num_experts, ff, d),
            jnp.float32,
        ),
        b2=module.param(
            "b2", nn.initializers.zeros, (num_experts, d), jnp.float32
        ),
    )


# ----------------------------------------------------------------- dropless

class Routing(NamedTuple):
    """What :func:`route` decides for ``n`` tokens and ``k`` experts a
    token.  A *slot* is one (token, choice) pair, ``n * k`` of them,
    numbered ``token * k + choice``."""

    weights: jax.Array      # [n, k] float32: normalised over all k chosen
    experts: jax.Array      # [n, k] int32: the chosen experts, of all E
    order: jax.Array        # [n*k] int32: sorted row -> slot, held experts
                            # first and by expert, the rest after them
    group_sizes: jax.Array  # [held + 1] int32: rows of each held expert,
                            # then the rows whose expert lives elsewhere
    dropped: jax.Array      # int32 scalar: slots of a held expert that
                            # the sort left no row for (there is no
                            # capacity, so 0: the counter is the proof)
    load: jax.Array         # [E] int32: the slots that chose each of ALL
                            # experts, what the balancing update reads


def route(x2, router, bias, *, top_k: int, scaling: float,
          first_held: int, held: int) -> Routing:
    """Sigmoid scores over ALL experts, in float32 whatever the stream's
    dtype (a choice is discrete: a score rounded to bfloat16 picks
    another expert); the ``top_k`` largest of ``score + bias``;
    weights ``score / (sum of the chosen scores + 1e-20) * scaling``.
    ``bias`` (the aux-free balancing correction) moves the choice and
    never a weight, and takes no gradient.  Experts ``first_held`` to
    ``first_held + held - 1`` are this chip's."""
    n = x2.shape[0]
    scores = jax.nn.sigmoid(jnp.dot(
        x2.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scaling
    local = experts.reshape(n * top_k) - first_held
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    dropped = is_held.sum(dtype=jnp.int32) - group_sizes[:held].sum()
    load = jnp.zeros((router.shape[1],), jnp.int32).at[
        experts.reshape(n * top_k)].add(1)
    return Routing(weights, experts.astype(jnp.int32), order, group_sizes,
                   dropped, load)


def rebalanced(moe_state, moe_stats, rate: float, axis_name=None):
    """The aux-free balancing update (``noaux_tc``; DeepSeek-V3's report,
    section 2.1.2) of every expert layer's selection bias after a step:
    ``bias_e += rate * sign(mean load - load_e)``, an expert chosen more
    often than the mean a little less likely next step.  ``moe_state`` and
    ``moe_stats`` are the model's two collections (per layer ``bias`` and
    ``load``, the slots that chose each of all the experts in the step
    just run); under ``axis_name`` the load is summed over the chips that
    bring tokens, so that every copy of the bias moves alike.  A training
    recipe like the optimizer's, so the step calls it, not the model."""
    from flax.traverse_util import flatten_dict, unflatten_dict  # noqa: PLC0415

    stats = flatten_dict(moe_stats)
    out = {}
    for path, bias in flatten_dict(moe_state).items():
        load = stats[path[:-1] + ("load",)].astype(jnp.float32)
        if axis_name is not None:
            load = lax.psum(load, axis_name)
        out[path] = bias + rate * jnp.sign(load.mean() - load)
    return unflatten_dict(out)


@jax.custom_vjp
def _to_experts(x2, order, inverse):
    """Every token's row once for each of its ``k`` choices, in expert
    order: ``x2[order // k]``, ``[n, d] -> [n k, d]``.  The backward pass
    gathers the rows' gradients back into slot order (``g[inverse]``) and
    sums a token's ``k``: no scatter-add, and no ``[n k, d]`` copy of the
    tokens before the sort."""
    return _slot_rows(x2, order)


def _slot_rows(x2, order):
    return jnp.take(x2, order // (order.shape[0] // x2.shape[0]), axis=0)


def _to_experts_fwd(x2, order, inverse):
    return _slot_rows(x2, order), (inverse, x2.shape[0])


def _to_experts_bwd(res, g):
    inverse, n = res
    back = jnp.take(g, inverse, axis=0).reshape(n, -1, g.shape[-1])
    return back.sum(axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_to_experts.defvjp(_to_experts_fwd, _to_experts_bwd)


@jax.custom_vjp
def _from_experts(ys, order, inverse):
    """The experts' rows back in slot order, ``ys[inverse]``; the
    backward pass is the gather ``g[order]``."""
    return jnp.take(ys, inverse, axis=0)


def _from_experts_fwd(ys, order, inverse):
    return jnp.take(ys, inverse, axis=0), order


def _from_experts_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_from_experts.defvjp(_from_experts_fwd, _from_experts_bwd)

# (rows, contraction, columns) of a grouped-matmul tile on the chip
GMM_TILING = (512, 1024, 1024)


def _gmm(lhs, rhs, group_sizes, interpret: bool):
    """``lhs [rows, k]`` times ``rhs [held, k, n]``, the rows of group
    ``g`` (consecutive, ``group_sizes[g]`` of them) with ``rhs[g]``; the
    rows after the last held group come out zero.  On the chip jax's
    Pallas grouped matmul (``pallas.ops.tpu.megablox``): its grid is the
    row tiles that hold a held expert's rows, so its time follows the
    rows routed here and not ``rows x held``.  Off the chip
    (``interpret``) ``lax.ragged_dot`` computes the same, the last
    group against a zero matrix."""
    if interpret:
        rhs = jnp.concatenate([rhs, jnp.zeros_like(rhs[:1])])
        return lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: PLC0415

    tiling = tuple(min(t, d) for t, d in zip(
        GMM_TILING, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling)


def grouped_ffn(xs, gate_up, down, group_sizes, *, dtype=jnp.bfloat16,
                interpret: bool = False):
    """The gated feed-forward ``W_down(silu(x W_gate) * (x W_up))`` of
    every held expert on its own rows: ``xs [rows, d]`` in expert order,
    ``gate_up [held, d, 2 ff]`` (gate then up), ``down [held, ff, d]``,
    ``group_sizes [held + 1]``.  Rows past the held groups give zero."""
    ff = down.shape[1]
    with jax.named_scope(scopes.MOE_EXPERTS):
        h = _gmm(xs.astype(dtype), gate_up.astype(dtype), group_sizes,
                 interpret)
        act = (jax.nn.silu(h[:, :ff]) * h[:, ff:]).astype(dtype)
        return _gmm(act, down.astype(dtype), group_sizes, interpret)


def routed_experts(x2, router, bias, gate_up, down, *, top_k: int,
                   scaling: float, first_held: int = 0,
                   dtype=jnp.bfloat16, interpret: Optional[bool] = None):
    """The routed part of a dropless expert layer on flat tokens
    ``x2 [n, d]``: ``sum over the chosen AND held experts e of
    w_e FFN_e(x)``.  What the experts held elsewhere would have added is
    left out (their share of the weights is not renormalised away).
    ``interpret=None`` takes the kernel on backend ``tpu`` and its
    stand-in on ``cpu``: one rule for every kernel of the package,
    ``flash_attention._interpret_for_backend``, looked up through that
    module as ``ops/ssd.py`` does (a compile for the chip from a machine
    without one replaces it there).  Returns ``(y [n, d], routing)``."""
    if interpret is None:
        from ..ops import flash_attention  # noqa: PLC0415

        interpret = flash_attention._interpret_for_backend(
            jax.default_backend())
    n, d = x2.shape
    held = gate_up.shape[0]
    with jax.named_scope(scopes.MOE_ROUTE):
        routing = route(x2, router, bias, top_k=top_k, scaling=scaling,
                        first_held=first_held, held=held)
        order = routing.order
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * top_k, dtype=jnp.int32))
    with jax.named_scope(scopes.MOE_DISPATCH):
        xs = _to_experts(x2.astype(dtype), order, inverse)
    ys = grouped_ffn(xs, gate_up, down, routing.group_sizes, dtype=dtype,
                     interpret=interpret)
    with jax.named_scope(scopes.MOE_DISPATCH):
        back = _from_experts(ys, order, inverse).reshape(n, top_k, d)
        y = jnp.einsum("nkd,nk->nd", back.astype(jnp.float32),
                       routing.weights)
    return y.astype(dtype), routing


def publish_stats(stats, registry=None) -> dict:
    """An expert layer's counters, from the device state the model keeps
    them in (collection ``moe_stats``: per layer ``rows`` [held] and
    ``dropped``, of the last step), as gauges of the metrics registry
    (``obs/registry.py``) and as the dict returned: per layer the rows
    routed to held experts, the largest held expert's rows over the
    mean, and the rows dropped (``load`` is the balancing update's).
    Read after a step, on the host: never from a callback inside it."""
    import numpy as np  # noqa: PLC0415
    from flax.traverse_util import flatten_dict  # noqa: PLC0415

    from ..obs.registry import get_registry  # noqa: PLC0415

    registry = registry or get_registry()
    flat = flatten_dict(jax.device_get(stats), sep="/")
    out = {}
    for path, rows in sorted(flat.items()):
        if not path.endswith("/rows"):
            continue
        layer = path[:-len("/rows")]
        rows = np.asarray(rows)
        mean = float(rows.mean())
        entry = {"rows_held": int(rows.sum()),
                 "max_over_mean": float(rows.max()) / mean if mean else 0.0,
                 "rows_dropped": int(flat[layer + "/dropped"])}
        for name, value in entry.items():
            registry.gauge(f"moe.{name}", layer=layer).set(value)
        out[layer] = entry
    return out
