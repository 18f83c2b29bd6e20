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
sum back (on the chip one kernel that follows the routed rows,
``ops/moe_combine.py``).  No capacity, so nothing is dropped; the layer
is told which experts it holds (``first_held``, and as many as its
weights have), routes over all of them and computes its own experts'
part of the result.  The sort puts the held experts' rows first, so the
layer works on a static number of the sort's first rows
(:func:`row_bound`, from the shapes) and not on every slot, with the
whole-buffer computation behind a ``lax.cond`` for the step whose held
rows pass that.  On one chip it runs without its exchange.  The layer is
two halves, a decision
(:func:`routing_decision`: which experts, with which weights, and the
sort) and its application (:func:`apply_routing`), because a model may
decide from one tensor and dispatch another (a router that reads the
layer's input, ahead of attention); :func:`routed_experts` is the two on
one tensor.  In a device trace three scopes cover it (``moe_route``,
``moe_dispatch``, ``moe_experts``) and each names its own operations one
level down (``horovod_tpu/scopes.py``: ``moe_logits``, ``moe_topk``,
``moe_sort``, ``moe_unsort``; ``moe_rows_in``, ``moe_rows_out``;
``moe_cast``, ``moe_gate``).  Nothing of the decision follows the slots
one after another: the counts are comparisons summed over the slots
(:func:`_counts`), the sigmoid rule's chosen scores a select over the
experts (:func:`_chosen`), the sort's inverse a second sort, so that
``moe_route`` scatters and gathers nothing, forward or backward, but in
the derivative of ``top_k``'s own values (the ``softmax_chosen`` rule's,
which the chip's compiler fuses).  The GShard
path above is as it was and carries no scope; ROADMAP C6 has the folding
of the two.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes

__all__ = ["init_moe_params", "moe_mlp", "moe_mlp_ep", "MoEParams",
           "Routing", "route", "grouped_ffn", "routed_experts", "row_bound",
           "routing_decision", "apply_routing", "rebalanced",
           "publish_stats", "SCORE_RULES", "ACTIVATIONS"]

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
                            # (the slots' keys compared with every group
                            # and summed: ``_counts``, as ``load`` is)
    dropped: jax.Array      # int32 scalar: slots of a held expert that
                            # the sort left no row for (there is no
                            # capacity, so 0: the counter is the proof)
    load: jax.Array         # [E] int32: the slots that chose each of ALL
                            # experts, what the balancing update reads
    overflowed: jax.Array = False  # bool scalar: the held experts' rows
                            # passed the layer's row bound, so it ran on
                            # the whole slot buffer (``apply_routing``
                            # sets it; ``route`` knows no bound)
    inverse: Optional[jax.Array] = None  # [n*k] int32: slot -> sorted row
                            # (``routing_decision`` sets it: ``order``
                            # sorted once more)
    balance: Optional[jax.Array] = None  # float32 scalar, where asked for:
                            # E * sum_e f_e P_e, 1.0 at an even load


# How a router's outputs become a choice and its weights (``route``).
SCORE_RULES = ("sigmoid", "softmax_chosen")


def route(x2, router, bias, *, top_k: int, scaling: float,
          first_held: int, held: int, score_rule: str = "sigmoid",
          balance: bool = False) -> Routing:
    """Scores over ALL experts, in float32 whatever the stream's dtype (a
    choice is discrete: a score rounded to bfloat16 picks another
    expert), by ``score_rule``.  ``"sigmoid"``: sigmoid scores; the
    ``top_k`` largest of ``score + bias``; weights ``score / (sum of the
    chosen scores + 1e-20) * scaling``; ``bias`` (the aux-free balancing
    correction) moves the choice and never a weight, and takes no
    gradient.  ``"softmax_chosen"``: the ``top_k`` largest raw logits and
    a softmax over those alone (the full softmax renormalised over the
    chosen), times ``scaling``; no bias (``None``), so nothing here holds
    the load even.  Experts ``first_held`` to ``first_held + held - 1``
    are this chip's.

    No operation here scatters to or gathers by a slot, which the chip
    does one key after another (8.7 ns a key: PERF.md section 6, PR 58).
    Under the sigmoid rule ``top_k`` gives the choice alone and the
    chosen scores are selected from ``scores`` over the expert axis
    (:func:`_chosen`: the gathered values to the bit, and a dense masked
    sum on the way back); ``softmax_chosen`` reads ``top_k``'s own values,
    whose derivative the chip's compiler fuses (0.03 ms a layer where the
    select costs 0.5: same section); ``group_sizes`` and ``load`` are
    comparisons summed over the slots (:func:`_counts`: the scatter-adds'
    integers); the sort is one stable ``argsort`` of the keys.

    ``balance`` asks for the load-balance loss beside the decision (scope
    ``moe_balance``): ``E * sum_e f_e P_e``, ``f_e`` the share of the
    ``n * top_k`` slots that chose expert ``e`` (a count: no gradient),
    ``P_e`` the mean over tokens of the full softmax of the logits; 1.0
    at an even load, and its gradient reaches the router through ``P``
    alone."""
    if score_rule not in SCORE_RULES:
        raise ValueError(f"score_rule must be one of {SCORE_RULES}, got "
                         f"{score_rule!r}")
    if score_rule == "softmax_chosen" and bias is not None:
        raise ValueError(
            "score_rule='softmax_chosen' chooses by the raw logits: "
            "it takes no selection bias")
    n = x2.shape[0]
    with jax.named_scope(scopes.MOE_LOGITS):
        logits = jnp.dot(
            x2.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if score_rule == "sigmoid" else logits
    with jax.named_scope(scopes.MOE_TOPK):
        if score_rule == "sigmoid":
            # the choice alone: its values are the biased ones and are
            # not read, so nothing of it is differentiated
            _, experts = lax.top_k(lax.stop_gradient(scores + bias), top_k)
            chosen = _chosen(scores, experts)
            weights = (chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
                       * scaling)
        else:
            chosen, experts = lax.top_k(scores, top_k)
            weights = jax.nn.softmax(chosen, axis=-1) * scaling
    with jax.named_scope(scopes.MOE_SORT):
        local = experts.reshape(n * top_k) - first_held
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = _counts(key, held + 1)
        dropped = is_held.sum(dtype=jnp.int32) - group_sizes[:held].sum()
        load = _counts(experts.reshape(n * top_k), router.shape[1])
    routing = Routing(weights, experts.astype(jnp.int32), order, group_sizes,
                      dropped, load)
    if balance:
        with jax.named_scope(scopes.MOE_BALANCE):
            share = load.astype(jnp.float32) / (n * top_k)
            mean = jax.nn.softmax(logits, axis=-1).mean(axis=0)
            routing = routing._replace(
                balance=router.shape[1] * jnp.sum(share * mean))
    return routing


def _chosen(scores, experts):
    """``scores[t, experts[t, j]]`` as ``[n, k]``, by a select over the
    expert axis and not a gather by the slots: ``sum over e of where(
    experts[t, j] == e, scores[t, e], 0)``, one fused pass over ``[n, k,
    E]``.  A token's experts are distinct and ``x + 0 = x``, so the value
    is the gathered one to the bit, and so is the cotangent, which
    ``jax`` transposes to a dense masked sum over ``k`` where a gather's
    is a scatter-add."""
    mask = experts[..., None] == jnp.arange(scores.shape[-1],
                                            dtype=experts.dtype)
    return jnp.where(mask, scores[:, None, :], 0).sum(-1)


def _counts(values, bins: int):
    """How many of ``values`` (integers, one a slot) are each of ``0`` to
    ``bins - 1``, as int32: every slot compared with every bin and the
    matches summed over the slots, one fused compare-and-reduce that
    writes ``bins`` integers, where a scatter-add follows the slots one
    after another."""
    return (values[:, None] == jnp.arange(bins, dtype=values.dtype)).sum(
        0, dtype=jnp.int32)


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


def _rows(values, index):
    """``values[index]`` for an index that cannot leave ``values`` (a
    permutation's part, or it over ``k``): no select after the gather."""
    return jnp.take(values, index, axis=0, mode="clip")


# The rows of a grouped-matmul tile on the chip: ``row_bound`` rounds to
# it, so every ``[row_bound, .]`` buffer follows it.
GMM_ROW_TILE = 512
# What a grouped-matmul call's blocks (each twice: Pallas fetches one
# while the kernel works on the other) and its float32 accumulator may
# take of VMEM: three quarters of the 16 MiB the compiler gives a kernel
# that states no limit (megablox states none), the rest for the kernel's
# own temporaries (the float32 select where it stores a tile).
GMM_VMEM_BYTES = 12 * 2 ** 20
# The expert layer works on this many even shares of the slots
# (``row_bound``).  2: once the balancing has settled, a layer's held
# experts get 0.98 to 1.03 shares; on the way there a layer peaked at 1.3
# to 1.9 shares over eighteen seeds where 16 of 128 experts are held
# (never past two), and at 1.1 to 4.0 over twenty where 8 of 64 are: past
# two in one to eight layer-steps of the first twenty steps on fourteen of
# those seeds, never later (PERF.md section 6).  A row of the bound costs
# every step (gather, cast, gate, the kernel's select: 5.6 and 10.7 ms a
# step for two shares more); a step over the bound costs its layer the
# whole buffer once (``routed_experts``: +8.5 ms there), exact and
# counted.  Two shares, a quarter of the slots where an eighth is held,
# lose those few early layer-steps and win every other step; four would
# keep most of them and pay twice the dead rows ever after.
ROW_BOUND_SHARES = 2


def row_bound(n: int, top_k: int, held: int, experts: int) -> int:
    """The rows of the sort that an expert layer computes on, from its
    shapes alone: ``ROW_BOUND_SHARES`` even shares of the ``n * top_k``
    slots for ``held`` of ``experts``, a whole number of the grouped
    matmul's row tiles, and never more than the slots; all of them where
    every expert is held."""
    slots, tile = n * top_k, GMM_ROW_TILE
    if held >= experts:
        return slots
    share = -(-slots * held * ROW_BOUND_SHARES // experts)
    return min(slots, -(-share // tile) * tile)


def _gmm(lhs, rhs, group_sizes, interpret: bool, transpose_rhs=False):
    """``lhs [rows, k]`` times ``rhs [held, k, n]``, the rows of group
    ``g`` (consecutive, ``group_sizes[g]`` of them) with ``rhs[g]`` (with
    its transpose under ``transpose_rhs``: ``lhs [rows, n]`` then); the
    rows of the last group, which has no matrix, come out zero.  On the
    chip jax's Pallas grouped matmul (``pallas.ops.tpu.megablox``): its
    grid is the row tiles that hold a held expert's rows, so its time
    follows the rows routed here and not ``rows x held``, and it zeroes
    the rows it did not visit by a select over ``rows``.  Where there is
    no chip (``interpret``: the tests' CPU) ``lax.ragged_dot`` stands in
    for it, the last group against a zero matrix."""
    if interpret:
        rhs = rhs.swapaxes(1, 2) if transpose_rhs else rhs
        rhs = jnp.concatenate([rhs, jnp.zeros_like(rhs[:1])])
        return lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm  # noqa: PLC0415

    tiles = gmm_tiles(*lhs.shape, rhs.shape[1 if transpose_rhs else 2],
                      lhs.dtype.itemsize)
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiles,
               transpose_rhs=transpose_rhs)


def _divisors(size: int):
    """The multiples of 128 (a lane tile) that divide ``size``; where
    there is none, ``size`` itself up to 1024 (a block as wide as the
    array, or tiles that the kernel pads and masks)."""
    return [t for t in range(128, size + 1, 128) if size % t == 0] or [
        min(size, 1024)]


def gmm_tiles(rows: int, k: int, n: int, itemsize: int,
              weights_out: bool = False):
    """``(tm, tk, tn)`` of one grouped-matmul call, from its own rows,
    contraction ``k`` and output width ``n`` and the operands' item size:
    ``gmm``'s ``[rows, k] x [k, n]`` a group, or under ``weights_out``
    ``tgmm``'s ``[k, rows] x [rows, n]`` a group (``k`` and ``n`` the
    matrix's two sides, the rows contracted).  ``tk`` and ``tn`` divide
    ``k`` and ``n`` (``_divisors``): the kernel rounds a dimension up to
    whole tiles, and the MXU multiplies the padding (and the VPU masks the
    last ``k`` tile of both operands).  Of the pairs whose blocks fit
    ``GMM_VMEM_BYTES`` (``[tm, tk]``, ``[tk, tn]`` and ``[tm, tn]`` twice
    each, and the output's block once more in float32: ``[tm, tn]``, or
    ``[tk, tn]`` under ``weights_out``) the one with the largest matrix
    block ``tk tn``, which has the fewest grid steps and fetches the rows
    the fewest times; between equals the squarer, so ``(1024, 1024)``
    wherever 1024 divides both.  ``tm`` is ``GMM_ROW_TILE`` (all the rows
    where they are fewer)."""
    tm = min(GMM_ROW_TILE, rows)
    pairs = [(tk, tn) for tk in _divisors(k) for tn in _divisors(n)
             if gmm_vmem_bytes(tm, tk, tn, itemsize, weights_out)
             <= GMM_VMEM_BYTES]
    return (tm, *max(pairs, key=lambda p: (p[0] * p[1], -abs(p[0] - p[1]))))


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int,
                   weights_out: bool = False) -> int:
    """What ``gmm_tiles`` counts against ``GMM_VMEM_BYTES``: the three
    blocks twice each and the float32 accumulator of the output's."""
    return (2 * itemsize * (tm * tk + tk * tn + tm * tn)
            + 4 * (tk if weights_out else tm) * tn)


def ffn_calls(d: int, ff: int, gated: bool = True):
    """The six grouped matmuls of one expert feed-forward and its
    gradients as ``(k, n, weights_out)``: ``hidden -> [gate | up]`` (an
    ungated expert's: ``hidden -> up``, ``ff`` wide) and ``ff -> hidden``
    forward, the same two transposed for the rows' gradients, and
    ``tgmm`` for each matrix's."""
    first = 2 * ff if gated else ff
    return ((d, first, False), (ff, d, False), (d, ff, False),
            (first, d, False), (ff, d, True), (d, first, True))


def tile_fill(calls, tiles) -> float:
    """What share of the multiply-adds that the kernels execute for
    ``calls`` (``ffn_calls``) under ``tiles`` (a ``(tm, tk, tn)`` each) is
    needed: ``k n`` over ``ceil(k / tk) tk ceil(n / tn) tn``, summed over
    the calls (so weighted by their operations).  1.0 where every tile
    divides its call."""
    up = lambda size, tile: -(-size // tile) * tile
    return sum(k * n for k, n, _ in calls) / sum(
        up(k, tk) * up(n, tn) for (k, n, _), (_, tk, tn) in zip(calls, tiles))


def ffn_tile_fill(d: int, ff: int, dtype, gated: bool = True) -> float:
    """``tile_fill`` of an expert layer of hidden ``d`` and width ``ff``
    under the tiles its calls get (gauge ``moe.gmm_tile_fill``)."""
    calls = ffn_calls(d, ff, gated)
    size = jnp.dtype(dtype).itemsize
    return tile_fill(calls, [gmm_tiles(GMM_ROW_TILE, k, n, size, out)
                             for k, n, out in calls])


def _gmm_bwd(lhs, rhs, group_sizes, interpret: bool, grad):
    """``_gmm``'s gradients by ``lhs`` and ``rhs``: the same kernel with
    each matrix transposed for the rows, and ``tgmm`` (group ``g``'s
    ``lhs_g^T grad_g``, float32 sums over the group's own row tiles) for
    the matrices, each with the tiles of its own ``k`` and ``n``
    (``gmm_tiles``): the pair of kernels that ``megablox.ops.gmm``'s own
    rule runs (taking the gradients through that rule traces each forward
    kernel once more, for nothing: a second of a cell's set-up); the
    stand-in's are ``jax``'s."""
    if interpret:
        return jax.vjp(lambda lhs, rhs: _gmm(lhs, rhs, group_sizes, True),
                       lhs, rhs)[1](grad)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm  # noqa: PLC0415

    tiles = gmm_tiles(*lhs.shape, grad.shape[1], lhs.dtype.itemsize,
                      weights_out=True)
    return (_gmm(grad, rhs, group_sizes, False, transpose_rhs=True),
            tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype, tiles,
                 num_actual_groups=rhs.shape[0]))


# The activation of an expert: act(x W_gate) * (x W_up) in a gated one,
# act(x W_up) in an ungated one (``_gate``).
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda h: jnp.square(jax.nn.relu(h))}


def grouped_ffn(xs, gate_up, down, group_sizes, *, dtype=jnp.bfloat16,
                interpret: bool = False, activation: str = "silu"):
    """The gated feed-forward ``W_down(act(x W_gate) * (x W_up))`` of
    every held expert on its own rows (``activation``, a name in
    ``ACTIVATIONS``: silu, relu, or relu2, its square): ``xs [rows, d]``
    in expert order, ``gate_up [held, d, 2 ff]`` (gate then up), or
    ``[held, d, ff]`` for experts without a gate matrix, ``W_down(act(x
    W_up))``: the first matrix's width says which; ``down [held, ff, d]``,
    ``group_sizes [held + 1]`` that add up to ``rows``: the held experts'
    rows, then what is left of ``rows``, which no expert here computes
    and which comes out zero (``_gmm``).  ``rows`` is what the caller
    gathered: ``apply_routing`` runs this feed-forward (``_ffn``, with
    the backward rule below) on the sort's first ``row_bound`` rows, not
    on the slot buffer."""
    return _grouped_ffn(interpret, activation, xs.astype(dtype),
                        gate_up.astype(dtype), down.astype(dtype),
                        group_sizes)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_ffn(interpret, activation, xs, gate_up, down, sizes):
    return _ffn(xs, gate_up, down, sizes, interpret, activation)[0]


def _grouped_ffn_fwd(interpret, activation, xs, gate_up, down, sizes):
    ys, h = _ffn(xs, gate_up, down, sizes, interpret, activation)
    return ys, (xs, h, gate_up, down, sizes)


def _grouped_ffn_bwd(interpret, activation, kept, d_ys):
    return (*_ffn_bwd(*kept, interpret, activation, d_ys), None)


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def _gate(activation: str, ff: int, h):
    """``act(gate) * up`` of ``h = [gate | up]``, each ``ff`` wide; of an
    ``h`` that is ``ff`` wide (an ungated expert's ``x W_up``) ``act(h)``
    alone, in float32."""
    if h.shape[1] == ff:
        return ACTIVATIONS[activation](h.astype(jnp.float32)).astype(h.dtype)
    return (ACTIVATIONS[activation](h[:, :ff]) * h[:, ff:]).astype(h.dtype)


def _ffn(xs, gate_up, down, sizes, interpret: bool, activation: str):
    """``grouped_ffn`` on operands of one dtype: the rows' outputs and
    ``h = xs W_[gate | up]``, what its backward pass reads again."""
    with jax.named_scope(scopes.MOE_EXPERTS):
        h = _gmm(xs, gate_up, sizes, interpret)
        with jax.named_scope(scopes.MOE_GATE):
            act = _gate(activation, down.shape[1], h)
        return _gmm(act, down, sizes, interpret), h


def _ffn_bwd(xs, h, gate_up, down, sizes, interpret: bool, activation: str,
             d_ys):
    """``_ffn``'s gradients by ``xs`` and both matrices, from ``xs`` and
    ``h``: no grouped matmul of the forward pass runs again."""
    with jax.named_scope(scopes.MOE_EXPERTS):
        with jax.named_scope(scopes.MOE_GATE):
            act, gate_bwd = jax.vjp(
                partial(_gate, activation, down.shape[1]), h)
        d_act, d_down = _gmm_bwd(act, down, sizes, interpret, d_ys)
        with jax.named_scope(scopes.MOE_GATE):
            d_h, = gate_bwd(d_act)
        d_xs, d_gate_up = _gmm_bwd(xs, gate_up, sizes, interpret, d_h)
        return d_xs, d_gate_up, d_down


def _head(rows: int, order, held_sizes):
    """The sort's first ``rows`` rows (slots), and the groups they fall
    into: the held experts' rows, then what is left of ``rows``."""
    return order[:rows], jnp.append(held_sizes, rows - held_sizes.sum())


# ``_forward`` and ``_backward`` run under ``jax.jit`` so that the step's
# module holds each of them once a shape and not once a layer, rule and
# side: without it a cell's warm ``compile_s`` stands 6 s over the
# parent's (the bound on ``setup_s`` is a tenth: 4 s), with it 1.7 to 2.7.
@partial(jax.jit, static_argnums=(0, 1, 2))
def _forward(rows: int, interpret: bool, activation: str, x2, weights, order,
             inverse, held_sizes, gate_up, down):
    """The held experts' weighted outputs in token order ``[n, d]``
    (float32), computed on the first ``rows`` rows of the sort (static;
    the held experts' rows are among them when ``held_sizes.sum() <=
    rows``), and what the backward pass reads again, all ``[rows, .]``:
    the gathered rows, ``h`` and the experts' outputs.  The way back
    follows the routed rows (``ops/moe_combine.py``): on the chip one
    kernel that reads the held experts' rows once and sums a token's
    choices in float32 in the order of the held experts; elsewhere one
    gathered row a slot, summed in the order of the choices, a slot whose
    row lies past ``rows`` adding exactly zero."""
    from ..ops import moe_combine  # noqa: PLC0415

    k = weights.shape[1]
    head, sizes = _head(rows, order, held_sizes)
    with jax.named_scope(scopes.MOE_DISPATCH), \
            jax.named_scope(scopes.MOE_ROWS_IN):
        xs = _rows(x2, head // k)
    ys, h = _ffn(xs, gate_up, down, sizes, interpret, activation)
    with jax.named_scope(scopes.MOE_DISPATCH), \
            jax.named_scope(scopes.MOE_ROWS_OUT):
        y = moe_combine.combine(ys, weights, inverse, held_sizes, k=k,
                                dtype=jnp.float32, interpret=interpret)
    return y, (xs, h, ys)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _backward(rows: int, interpret: bool, activation: str, kept, weights,
              order, inverse, held_sizes, gate_up, down, g):
    """``_forward``'s gradients by the tokens, the weights and both
    matrices, on ``[rows, .]`` buffers alone: the tokens' gradients
    gathered to the rows (``g[head // k]``), weighted for the experts'
    outputs, multiplied with them and summed for the weights, a row's sum
    put at the slot the row came from; and the rows' gradients brought
    back to the tokens as the outputs were, unweighted
    (``moe_combine.combine``): nothing here has ``n k`` rows of ``d``."""
    from ..ops import moe_combine  # noqa: PLC0415

    xs, h, ys = kept
    k = weights.shape[1]
    head, sizes = _head(rows, order, held_sizes)
    with jax.named_scope(scopes.MOE_DISPATCH):
        with jax.named_scope(scopes.MOE_ROWS_IN):
            g_rows = _rows(g, head // k)
            d_ys = g_rows * _rows(weights.reshape(-1), head)[:, None]
        with jax.named_scope(scopes.MOE_ROWS_OUT):
            # a row past the held groups carries zeros, and a slot whose
            # row lies past ``rows`` keeps the zero it starts with
            d_weights = jnp.zeros((weights.size,), jnp.float32).at[head].set(
                (ys.astype(jnp.float32) * g_rows).sum(-1),
                unique_indices=True).reshape(weights.shape)
    d_xs, d_gate_up, d_down = _ffn_bwd(xs, h, gate_up, down, sizes,
                                       interpret, activation,
                                       d_ys.astype(ys.dtype))
    with jax.named_scope(scopes.MOE_DISPATCH), \
            jax.named_scope(scopes.MOE_ROWS_OUT):
        d_x2 = moe_combine.combine(d_xs, None, inverse, held_sizes, k=k,
                                   dtype=d_xs.dtype, interpret=interpret)
    return d_x2, d_weights, d_gate_up, d_down


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _experts(bound: int, interpret: bool, activation: str, overflowed,
             operands):
    """``_forward`` on the sort's first ``bound`` rows, or on every slot
    in a step whose held rows pass ``bound`` (``overflowed``, a device
    value; ``None`` where the bound is every slot, and then there is no
    branch).  The two ``lax.cond`` sit inside the rules of one
    ``custom_vjp``, so ``jax`` differentiates neither: a branch is traced
    once forward and once backward, and the side not taken hands nothing
    over but zeros of the bounded side's ``[bound, .]`` shapes (it
    computes its own forward again, in the step that takes it)."""
    return _experts_fwd(bound, interpret, activation, overflowed,
                        operands)[0]


def _experts_fwd(bound, interpret, activation, overflowed, operands):
    bounded = partial(_forward, bound, interpret, activation)
    if overflowed is None:
        y, kept = bounded(*operands)
    else:
        def whole(*operands):
            y, kept = _forward(operands[2].shape[0], interpret, activation,
                               *operands)
            return y, jax.tree.map(lambda a: jnp.zeros_like(a[:bound]), kept)

        y, kept = lax.cond(overflowed, whole, bounded, *operands)
    return y, (overflowed, operands, kept)


def _experts_bwd(bound, interpret, activation, res, g):
    overflowed, operands, kept = res
    bounded = partial(_backward, bound, interpret, activation)
    if overflowed is None:
        grads = bounded(kept, *operands[1:], g)
    else:
        def whole(_, *rest):
            slots = operands[2].shape[0]
            _, kept = _forward(slots, interpret, activation, operands[0],
                               *rest[:-1])
            return _backward(slots, interpret, activation, kept, *rest)

        grads = lax.cond(overflowed, whole, bounded, kept, *operands[1:], g)
    d_x2, d_weights, d_gate_up, d_down = grads
    return None, (d_x2, d_weights, None, None, None, d_gate_up, d_down)


_experts.defvjp(_experts_fwd, _experts_bwd)


def routing_decision(x2, router, bias, *, top_k: int, scaling: float,
                     first_held: int, held: int,
                     score_rule: str = "sigmoid",
                     balance: bool = False) -> Routing:
    """The first half of the layer, from the tensor the router reads
    (``x2 [n, d]``): :func:`route` and the sort's inverse, under the
    scope ``moe_route``.  What it returns says everything
    :func:`apply_routing` needs about ``n`` tokens, so the tensor that is
    dispatched may be another one of as many rows (the stream after
    attention, for a router that reads the layer's input)."""
    with jax.named_scope(scopes.MOE_ROUTE):
        routing = route(x2, router, bias, top_k=top_k, scaling=scaling,
                        first_held=first_held, held=held,
                        score_rule=score_rule, balance=balance)
        with jax.named_scope(scopes.MOE_UNSORT):
            # ``order`` is a permutation: its inverse is its own sort
            inverse = jnp.argsort(routing.order).astype(jnp.int32)
    return routing._replace(inverse=inverse)


def apply_routing(routing: Routing, x2, gate_up, down, *,
                  dtype=jnp.bfloat16, interpret: Optional[bool] = None,
                  activation: str = "silu"):
    """The second half: ``sum over the chosen AND held experts e of
    w_e FFN_e(x)`` for the rows of ``x2 [n, d]``, by a decision
    (:func:`routing_decision`) over ``n`` tokens.

    The sort puts the held experts' rows first, so everything between it
    and the result runs on the first ``row_bound(...)`` rows, a static
    number from the shapes; a slot whose row lies past them takes zero on
    the way back.  A step in which the held experts get more rows than
    that runs the same computation, with the same kernels, on all
    ``n * top_k`` rows instead (``lax.cond`` on the device: nothing is
    fetched, nothing is dropped or capped) and says so in
    ``routing.overflowed``.  Where every expert is held the bound is
    ``n * top_k`` and there is no branch.

    ``interpret=None`` takes the kernel on backend ``tpu`` and its
    stand-in on ``cpu``: one rule for every kernel of the package,
    ``flash_attention._interpret_for_backend``, looked up through that
    module as ``ops/ssd.py`` does (a compile for the chip from a machine
    without one replaces it there).  Returns ``(y [n, d], routing)``."""
    if interpret is None:
        from ..ops import flash_attention  # noqa: PLC0415

        interpret = flash_attention._interpret_for_backend(
            jax.default_backend())
    n, top_k = routing.weights.shape
    held = gate_up.shape[0]
    bound = row_bound(n, top_k, held, routing.load.shape[0])
    held_sizes = routing.group_sizes[:held]
    overflowed = held_sizes.sum() > bound if bound < n * top_k else None
    with jax.named_scope(scopes.MOE_EXPERTS), \
            jax.named_scope(scopes.MOE_CAST):
        # cast once, outside the branch: both sides read the same copy
        matrices = gate_up.astype(dtype), down.astype(dtype)
    y = _experts(bound, interpret, activation, overflowed, (
        x2.astype(dtype), routing.weights, routing.order, routing.inverse,
        held_sizes, *matrices))
    if overflowed is not None:
        routing = routing._replace(overflowed=overflowed)
    return y.astype(dtype), routing


def routed_experts(x2, router, bias, gate_up, down, *, top_k: int,
                   scaling: float, first_held: int = 0,
                   dtype=jnp.bfloat16, interpret: Optional[bool] = None):
    """The routed part of a dropless expert layer on flat tokens
    ``x2 [n, d]``, decided from the tensor it dispatches: the two halves
    (:func:`routing_decision` with sigmoid scores and a selection bias,
    :func:`apply_routing` with a silu gate) in turn.  What the experts
    held elsewhere would have added is left out (their share of the
    weights is not renormalised away).  Returns ``(y [n, d],
    routing)``."""
    routing = routing_decision(
        x2, router, bias, top_k=top_k, scaling=scaling,
        first_held=first_held, held=gate_up.shape[0])
    return apply_routing(routing, x2, gate_up, down, dtype=dtype,
                         interpret=interpret)


def publish_stats(stats, registry=None) -> dict:
    """An expert layer's counters, from the device state the model keeps
    them in (collection ``moe_stats``: per layer ``rows`` [held] and
    ``dropped``, of the last step, and ``overflow_steps``, counted up
    since the state was made), as gauges of the metrics registry
    (``obs/registry.py``) and as the dict returned: per layer the rows
    routed to held experts, the largest held expert's rows over the
    mean, the rows dropped, and the steps in which the layer passed its
    row bound and ran on the whole slot buffer (``load`` is the
    balancing update's), and, where the layer computes it, the last
    step's load-balance loss (``balance_loss``: 1.0 is an even load).
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
                 "rows_dropped": int(flat[layer + "/dropped"]),
                 "overflow_steps": int(flat[layer + "/overflow_steps"])}
        if layer + "/balance_loss" in flat:
            entry["balance_loss"] = float(flat[layer + "/balance_loss"])
        for name, value in entry.items():
            registry.gauge(f"moe.{name}", layer=layer).set(value)
        out[layer] = entry
    return out
