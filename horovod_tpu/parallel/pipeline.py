"""GPipe-style pipeline parallelism for the transformer family.

Beyond reference parity (Horovod 0.19.1 is data-parallel only,
SURVEY.md §2.9): the GPT block stack splits into P contiguous stages
over a ``pp`` mesh axis; microbatches stream through the pipeline with
activations handed to the next stage by ``lax.ppermute`` each tick —
the TPU-idiomatic SPMD pipeline (every rank runs the SAME program; stage
identity comes from ``axis_index``), with a ``lax.scan`` over
``M + P - 1`` ticks so the schedule is one compiled loop, no
data-dependent control flow.

Embeddings and the LM head stay replicated and run outside the
pipelined region (they are marginal at these widths); each stage holds
only its ``num_layers / P`` blocks' weights.  Equivalence with the
unsharded model — forward and gradients — is pinned by
tests/test_pipeline.py.

The schedule family (docs/pipeline.md):

* :func:`pp_gpt_apply` — GPipe forward, full logits on every rank
  (inference/eval, equivalence tests).
* :func:`pp_gpt_loss` — training: stage-local head + token loss inside
  the tick, ONE scalar psum rejoin, per-tick remat.
* :func:`pp_gpt_loss_circular` — circular/interleaved groups: each
  device holds ``circles`` non-contiguous layer groups and the stream
  wraps the ring, shrinking the bubble ~``circles``x with no
  masked-branch waste (the SPMD answer to 1F1B — see docs/pipeline.md).
* :func:`pp_tp_gpt_loss` — TP-sharded blocks inside stages: the 3-axis
  ``dp x pp x tp`` deployment shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["stack_pp_params", "stack_pp_params_circular",
           "stack_tp_pp_params", "unstack_pp_params",
           "unstack_pp_params_circular", "unstack_tp_pp_params",
           "pp_gpt_apply", "pp_gpt_loss", "pp_gpt_loss_circular",
           "pp_tp_gpt_loss"]


def stack_pp_params(params, cfg, pp: int):
    """Split a GPT parameter pytree into ``(staged, replicated)``.

    ``staged``: the block weights restacked as a pytree whose leaves have
    leading dims ``[pp, layers_per_stage, ...]`` — shard over the mesh
    with ``in_specs=P(pp_axis)``.  ``replicated``: embeddings, final LN,
    head — ``in_specs=P()`` (truly replicated; see
    tensor_parallel.stack_tp_params for why that distinction is
    load-bearing under autodiff).
    """
    from ..models.transformer import require_gpt2_block  # noqa: PLC0415

    require_gpt2_block(cfg, "parallel.pipeline.stack_pp_params")
    if cfg.num_layers % pp:
        raise ValueError(
            f"pp={pp} must divide num_layers={cfg.num_layers}"
        )
    if set(params.keys()) == {"params"}:
        params = params["params"]
    p = jax.tree_util.tree_map(np.asarray, params)
    per = cfg.num_layers // pp
    blocks = [p[f"block{i}"] for i in range(cfg.num_layers)]
    if any("fc1" not in b for b in blocks):
        raise ValueError(
            "stack_pp_params supports dense blocks only (MoE blocks "
            "shard over the ep axis; see docs/moe.md)"
        )
    # stack homogenous block trees: leaf -> [pp, per, ...]
    staged = jax.tree_util.tree_map(
        lambda *leaves: jnp.asarray(np.stack(leaves).reshape(
            (pp, per) + np.asarray(leaves[0]).shape
        )),
        *blocks,
    )
    replicated = {
        k: jax.tree_util.tree_map(jnp.asarray, v)
        for k, v in p.items() if not k.startswith("block")
    }
    return staged, replicated


def stack_pp_params_circular(params, cfg, pp: int, circles: int):
    """Restack for the circular schedule: device ``s`` holds the
    ``circles`` non-contiguous layer groups ``{s, s+pp, ..}`` —
    ``staged`` leaves get leading dims ``[pp, circles, layers_per_group,
    ...]`` (group ``v*pp + s`` at ``staged[s, v]``), so the microbatch
    stream can wrap through every device ``circles`` times
    (:func:`pp_gpt_loss_circular`).  ``replicated`` as in
    :func:`stack_pp_params`."""
    from ..models.transformer import require_gpt2_block  # noqa: PLC0415

    require_gpt2_block(cfg, "parallel.pipeline.stack_pp_params_circular")
    if circles < 1:
        raise ValueError(f"circles={circles} must be >= 1")
    if cfg.num_layers % (pp * circles):
        raise ValueError(
            f"pp*circles={pp}*{circles} must divide "
            f"num_layers={cfg.num_layers}"
        )
    staged, replicated = stack_pp_params(params, cfg, pp)
    per_group = cfg.num_layers // (pp * circles)
    # stack_pp_params laid blocks contiguously: [pp, per_stage, ...] with
    # per_stage = circles*per_group and stage s holding layers
    # [s*per_stage, (s+1)*per_stage).  The circular layout instead puts
    # layer (v*pp + s)*per_group + j at [s, v, j]; restack from the flat
    # block order via [circles, pp, per_group] -> transpose.
    def _restack(leaf):
        flat = jnp.reshape(leaf, (cfg.num_layers,) + leaf.shape[2:])
        grouped = jnp.reshape(
            flat, (circles, pp, per_group) + leaf.shape[2:]
        )
        return jnp.transpose(
            grouped, (1, 0, 2) + tuple(range(3, grouped.ndim))
        )

    return jax.tree_util.tree_map(_restack, staged), replicated


def _check_staged_lead(staged, want: tuple, what: str):
    """Loud mismatch guard for the unstack inverses: JAX index clamping
    would otherwise turn a wrong pp/circles/tp into a silently
    corrupted (correct-shaped!) checkpoint."""
    got = jax.tree_util.tree_leaves(staged)[0].shape[:len(want)]
    if tuple(got) != want:
        raise ValueError(
            f"staged leaves have leading dims {tuple(got)}, expected "
            f"{want} ({what}) — unstacking with different factors than "
            "the tree was stacked with"
        )


def unstack_pp_params(staged, replicated, cfg, pp: int):
    """Inverse of :func:`stack_pp_params`: reassemble the canonical GPT
    parameter pytree (``block{i}`` entries + embeddings/head) from the
    staged tree — docs/inference.md's "unstack the leading dims"
    instruction as code (round-trip pinned by tests/test_pipeline.py).
    """
    per = cfg.num_layers // pp
    _check_staged_lead(staged, (pp, per), "pp, layers_per_stage")
    out = dict(replicated)
    for i in range(cfg.num_layers):
        s, j = divmod(i, per)
        out[f"block{i}"] = jax.tree_util.tree_map(
            lambda a: a[s, j], staged
        )
    return out


def unstack_pp_params_circular(staged, replicated, cfg, pp: int,
                               circles: int):
    """Inverse of :func:`stack_pp_params_circular` (layer
    ``(v*pp + s)*per_group + j`` lives at ``staged[s, v, j]``)."""
    per_group = cfg.num_layers // (pp * circles)
    _check_staged_lead(staged, (pp, circles, per_group),
                       "pp, circles, layers_per_group")
    out = dict(replicated)
    for i in range(cfg.num_layers):
        g, j = divmod(i, per_group)
        v, s = divmod(g, pp)
        out[f"block{i}"] = jax.tree_util.tree_map(
            lambda a: a[s, v, j], staged
        )
    return out


def unstack_tp_pp_params(staged_sharded, staged_replicated, replicated,
                         cfg, pp: int, tp: int):
    """Inverse of :func:`stack_tp_pp_params`: per-block per-rank shards
    are re-formed and handed to ``unstack_tp_params`` — a TP-in-PP
    training state round-trips to the canonical checkpoint format."""
    from .tensor_parallel import unstack_tp_params  # noqa: PLC0415

    per = cfg.num_layers // pp
    _check_staged_lead(staged_sharded, (pp, tp, per),
                       "pp, tp, layers_per_stage")
    _check_staged_lead(staged_replicated, (pp, per),
                       "pp, layers_per_stage")
    sharded, rep = {}, dict(replicated)
    for i in range(cfg.num_layers):
        s, j = divmod(i, per)
        sharded[f"block{i}"] = jax.tree_util.tree_map(
            lambda a: a[s, :, j], staged_sharded
        )
        rep[f"block{i}"] = jax.tree_util.tree_map(
            lambda a: a[s, j], staged_replicated
        )
    return unstack_tp_params(sharded, rep, cfg, tp)


def _dense_block(cfg, p, x, positions, rope_tabs):
    """One transformer block from raw weights — the shared
    ``models.transformer.block_math`` wiring via its raw-weights
    entry point (single source of truth for the block forward)."""
    from ..models.transformer import raw_block_forward  # noqa: PLC0415

    return raw_block_forward(cfg, p, x, positions, rope_tabs)


def _head_loss(replicated_params, cfg, y, tgt):
    """Per-microbatch token loss from a stage's final activation — the
    one definition both the contiguous and circular training schedules
    mask into their ticks."""
    from .tensor_parallel import _gpt_head  # noqa: PLC0415

    logits = _gpt_head(replicated_params, cfg, y)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()


def _vma_axes(refs, base):
    """The varying-axes set a scan carry must declare: ``base`` plus
    every axis any of ``refs`` (activations, stage weights) varies
    over — e.g. a dp axis in a composed dp x pp mesh."""
    axes = set(base)
    for r in refs:
        try:
            axes |= set(jax.typeof(r).vma)
        except (AttributeError, TypeError):
            pass
    return tuple(sorted(axes))


def _mark_varying(v, axes):
    """Mark a replicated value device-varying over ``axes`` so a scan
    carry's type matches the tick outputs under replication tracking
    (check_vma=True)."""
    return lax.pcast(v, axes, to="varying")


class _Schedule:
    """Everything the GPipe tick loop shares between the logits and the
    stage-local-loss entry points: the embedded microbatch stream, the
    (optionally remat'd) stage body, the permutation, and the vma
    plumbing for the scan carry."""

    def __init__(self, staged_params, replicated_params, cfg, tokens,
                 pp_axis, microbatches, pos_offset, positions, remat,
                 contiguous=True, local=None, layer_fn=None,
                 extra_axes=()):
        from ..models.transformer import (  # noqa: PLC0415
            require_gpt2_block,
        )
        from .tensor_parallel import _gpt_embed  # noqa: PLC0415

        require_gpt2_block(cfg, "parallel.pipeline")
        self.pp_axis = pp_axis
        self.pp = lax.axis_size(pp_axis)
        self.stage = lax.axis_index(pp_axis)
        self.cfg = cfg
        b, s = tokens.shape
        if b % microbatches:
            raise ValueError(
                f"batch {b} must divide into microbatches={microbatches}"
            )
        # embed (replicated, outside the pipeline) — shared GPT scaffold
        x, positions, rope_tabs = _gpt_embed(
            replicated_params, cfg, tokens, pos_offset, positions
        )
        self.b, self.s = b, s
        self.mb = b // microbatches
        self.microbatches = microbatches
        self.mbs = x.reshape(microbatches, self.mb, s, cfg.emb_dim)
        self.positions, self.rope_tabs = positions, rope_tabs
        default_local = local is None
        if default_local:
            local = jax.tree_util.tree_map(lambda a: a[0], staged_params)
        self.local = local
        layers_per_stage = jax.tree_util.tree_leaves(local)[0].shape[0]
        per_stage = cfg.num_layers // self.pp
        if contiguous:
            # Guard against mis-stacked params reaching a contiguous
            # entry point — circular-stacked trees (extra [circles] dim
            # broadcasting through the matmuls) or a stack built for a
            # different pp (stages silently dropped): finite-looking
            # but wrong loss, no error.  (The converse mistake is
            # caught in pp_gpt_loss_circular.)
            if default_local:
                qkv = local["qkv"]["kernel"]
                if qkv.ndim != 3 or layers_per_stage != per_stage:
                    raise ValueError(
                        f"staged qkv kernel has shape {qkv.shape}, "
                        f"expected [{per_stage}, emb, qkv_dim] "
                        "(num_layers/pp contiguous layers per device) — "
                        "params stacked with stack_pp_params_circular "
                        "must go through pp_gpt_loss_circular"
                    )
            elif layers_per_stage != per_stage:
                raise ValueError(
                    f"staged params carry {layers_per_stage} "
                    f"layers/stage but num_layers/pp = {per_stage} — "
                    "stacked for a different pp than this mesh axis?"
                )

        if layer_fn is None:
            def layer_fn(p_j, x, positions, rope_tabs):
                return _dense_block(cfg, p_j, x, positions, rope_tabs)

        def run_stage(x):
            for j in range(layers_per_stage):
                p_j = jax.tree_util.tree_map(lambda a: a[j], local)
                x = layer_fn(p_j, x, positions, rope_tabs)
            return x

        if remat:
            # Backward then stores one (mb, s, emb) input per tick and
            # recomputes the blocks' internals, instead of saving every
            # attention/MLP intermediate of every tick — the per-stage
            # activation-memory fix for pipelined training.
            run_stage = jax.checkpoint(run_stage)
        self.run_stage = run_stage

        self.fwd_perm = [(i, (i + 1) % self.pp) for i in range(self.pp)]
        self.n_ticks = microbatches + self.pp - 1

        # The scan carry must have the same varying-axes set as the tick
        # outputs: pp_axis (the ppermute), every declared extra axis
        # (e.g. tp in TP-in-PP), every axis the activations vary over
        # (e.g. a dp axis in a composed dp x pp mesh — tokens sharded
        # over dp make every stage output dp-varying), and every axis
        # the stage weights vary over.
        self._carry_axes = _vma_axes(
            (self.mbs, *jax.tree_util.tree_leaves(local)[:1]),
            {pp_axis, *extra_axes},
        )

    def varying(self, v):
        """:func:`_mark_varying` over this schedule's carry axes."""
        return _mark_varying(v, self._carry_axes)

    def stage_io(self, incoming, t):
        """The per-tick stage input/output shared by every schedule:
        stage 0 ingests microbatch t (clipped), other stages take the
        handed-over activation; returns the stage output and its
        ppermuted hand-off."""
        feed_idx = jnp.clip(t, 0, self.microbatches - 1)
        fresh = lax.dynamic_index_in_dim(self.mbs, feed_idx, axis=0,
                                         keepdims=False)
        x_in = jnp.where(self.stage == 0, fresh, incoming)
        y = self.run_stage(x_in)
        return y, lax.ppermute(y, self.pp_axis, self.fwd_perm)


def pp_gpt_apply(staged_params, replicated_params, cfg, tokens,
                 pp_axis: str, *, microbatches: int,
                 pos_offset=0, positions=None, remat: bool = False):
    """``GPT.apply`` with the block stack pipelined over ``pp_axis``.

    ``tokens [batch, seq]`` must be replicated over the axis and have
    ``batch % microbatches == 0``.  The schedule is GPipe forward:
    ``M + P - 1`` ticks, one microbatch entering stage 0 per tick,
    activations ppermuted stage-to-stage.  Returns fp32 logits.

    This entry point materializes every microbatch's final activation
    and broadcasts them over the axis so every rank returns full logits
    — right for inference/eval and the equivalence tests.  For training
    use :func:`pp_gpt_loss`, whose rejoin is one scalar.
    """
    from .tensor_parallel import _gpt_head  # noqa: PLC0415

    sched = _Schedule(staged_params, replicated_params, cfg, tokens,
                      pp_axis, microbatches, pos_offset, positions, remat)
    pp, stage, mb, s = sched.pp, sched.stage, sched.mb, sched.s
    zero = sched.varying(jnp.zeros((mb, s, cfg.emb_dim), cfg.dtype))

    def tick(carry, t):
        incoming, outputs = carry
        y, handoff = sched.stage_io(incoming, t)
        # last stage finished microbatch t - (pp - 1) this tick
        out_idx = jnp.clip(t - (pp - 1), 0, microbatches - 1)
        take = jnp.logical_and(stage == pp - 1, t >= pp - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(take,
                      y,
                      lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)),
            out_idx, axis=0,
        )
        return (handoff, outputs), None

    outputs0 = sched.varying(jnp.zeros(
        (microbatches, mb, s, cfg.emb_dim), cfg.dtype
    ))
    (_, outputs), _ = lax.scan(
        tick, (zero, outputs0), jnp.arange(sched.n_ticks)
    )
    # only the last stage holds real outputs; broadcast them to all
    # ranks so the (replicated) head runs everywhere and the caller gets
    # replicated logits — one psum of a masked contribution
    outputs = lax.psum(
        jnp.where(stage == pp - 1, outputs, jnp.zeros_like(outputs)),
        pp_axis,
    )
    x = outputs.reshape(sched.b, s, cfg.emb_dim)
    return _gpt_head(replicated_params, cfg, x)


def pp_gpt_loss(staged_params, replicated_params, cfg, tokens, targets,
                pp_axis: str, *, microbatches: int,
                pos_offset=0, positions=None, remat: bool = True):
    """Pipelined causal-LM training loss with a stage-local head.

    The GPipe schedule of :func:`pp_gpt_apply`, but built for training
    (VERDICT r4 weak #5): the LM head and the token cross-entropy run
    per-microbatch inside the tick — only the last stage's contribution
    is kept — and the cross-stage rejoin is ONE scalar ``psum`` instead
    of broadcasting an ``(M, mb, seq, emb)`` activation buffer over the
    axis.  With ``remat=True`` (the default: this entry point exists for
    training) backward stores one stage input per tick rather than every
    block intermediate, so per-stage activation memory is
    O(ticks x mb x seq x emb) flat instead of O(M x layer internals).

    ``targets [batch, seq]`` are the next-token labels aligned with
    ``tokens``.  Returns the mean token loss, replicated over the axis.
    """
    sched = _Schedule(staged_params, replicated_params, cfg, tokens,
                      pp_axis, microbatches, pos_offset, positions, remat)
    return _gpipe_loss(sched, replicated_params, cfg, targets, remat)


def _gpipe_loss(sched, replicated_params, cfg, targets, remat):
    """The GPipe loss tick loop shared by the contiguous and TP-in-PP
    entry points: last stage finishes microbatch ``t - (pp-1)`` each
    tick, runs head+loss on it there (SPMD: every stage computes them,
    only the last stage's masked contribution survives — no
    microbatch's final activation ever outlives its tick), and the
    rejoin is one scalar psum."""
    pp, stage, mb, s = sched.pp, sched.stage, sched.mb, sched.s
    microbatches = sched.microbatches
    tgt_mbs = targets.reshape(microbatches, mb, s)
    zero = sched.varying(jnp.zeros((mb, s, cfg.emb_dim), cfg.dtype))

    def head_loss(y, tgt):
        return _head_loss(replicated_params, cfg, y, tgt)

    if remat:
        head_loss = jax.checkpoint(head_loss)

    def tick(carry, t):
        incoming, loss_sum = carry
        y, handoff = sched.stage_io(incoming, t)
        out_idx = jnp.clip(t - (pp - 1), 0, microbatches - 1)
        tgt = lax.dynamic_index_in_dim(tgt_mbs, out_idx, axis=0,
                                       keepdims=False)
        take = jnp.logical_and(stage == pp - 1, t >= pp - 1)
        loss_sum = loss_sum + jnp.where(take, head_loss(y, tgt), 0.0)
        return (handoff, loss_sum), None

    loss0 = sched.varying(jnp.zeros((), jnp.float32))
    (_, loss_sum), _ = lax.scan(
        tick, (zero, loss0), jnp.arange(sched.n_ticks)
    )
    # every microbatch is the same size, so the mean of per-microbatch
    # means is the global token mean; the psum is the whole rejoin
    return lax.psum(loss_sum, sched.pp_axis) / microbatches


def pp_gpt_loss_circular(staged_params, replicated_params, cfg, tokens,
                         targets, pp_axis: str, *, microbatches: int,
                         circles: int, pos_offset=0, positions=None,
                         remat: bool = True):
    """:func:`pp_gpt_loss` on the circular (interleaved-group) schedule.

    Each device holds ``circles`` non-contiguous layer groups
    (:func:`stack_pp_params_circular`) and the microbatch stream wraps
    through the ring ``circles`` times: device ``s`` at tick ``t`` works
    stream position ``k = t - s`` — circle ``v = k // M``, microbatch
    ``m = k % M`` — always exactly ONE group-forward per tick, so unlike
    a 1F1B schedule there is no masked-branch compute waste (see
    docs/pipeline.md).  Bubble shrinks from ``(P-1)/(M+P-1)`` to
    ``(P-1)/(circles*M + P-1)`` — the praxis-style circular pipeline —
    at the price of ``circles``x the ppermute hand-off traffic.

    A circle-boundary activation (device P-1's output for circle
    ``v < circles-1``) re-enters device 0 ``M - P + 1`` ticks after it
    arrives, banked in an M-slot ring buffer: slot ``h % M`` is written
    at tick ``h + P`` and read at tick ``h + M``, collision-free for
    ``microbatches >= pp`` (enforced).  Loss/head/rejoin semantics are
    exactly :func:`pp_gpt_loss` (stage-local head on the final circle,
    one scalar psum).
    """
    sched = _Schedule(staged_params, replicated_params, cfg, tokens,
                      pp_axis, microbatches, pos_offset, positions,
                      remat=False,       # applied to run_group below
                      contiguous=False)  # leaves are [circles, group, ..]
    pp, stage, mb, s = sched.pp, sched.stage, sched.mb, sched.s
    M = microbatches
    if M < pp:
        raise ValueError(
            f"circular schedule needs microbatches >= pp ({M} < {pp}): "
            "the ring buffer re-feeds device 0 M-P+1 ticks after arrival"
        )
    leaves = jax.tree_util.tree_leaves(sched.local)
    if leaves[0].shape[0] != circles:
        raise ValueError(
            f"staged params carry {leaves[0].shape[0]} groups/device, "
            f"expected circles={circles} — restack with "
            "stack_pp_params_circular(params, cfg, pp, circles)"
        )
    per_group = leaves[0].shape[1]
    tgt_mbs = targets.reshape(M, mb, s)

    def run_group(v, x):
        p_v = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
            sched.local,
        )
        for j in range(per_group):
            p_j = jax.tree_util.tree_map(lambda a: a[j], p_v)
            x = _dense_block(cfg, p_j, x, sched.positions,
                             sched.rope_tabs)
        return x

    def head_loss(y, tgt):
        return _head_loss(replicated_params, cfg, y, tgt)

    if remat:
        run_group = jax.checkpoint(run_group)
        head_loss = jax.checkpoint(head_loss)

    n_ticks = circles * M + pp - 1
    zero = sched.varying(jnp.zeros((mb, s, cfg.emb_dim), cfg.dtype))
    queue0 = sched.varying(jnp.zeros((M, mb, s, cfg.emb_dim), cfg.dtype))
    loss0 = sched.varying(jnp.zeros((), jnp.float32))

    def tick(carry, t):
        incoming, queue, loss_sum = carry
        # (1) bank the arrival FIRST: device 0's incoming this tick is
        # stream position h = t - pp (device P-1's output last tick);
        # write-then-read makes the M == pp edge (write and read of the
        # same slot in one tick) correct.
        h = t - pp
        slot = jnp.mod(h, M)  # non-negative for any h
        queue = lax.dynamic_update_index_in_dim(
            queue,
            jnp.where(h >= 0, incoming,
                      lax.dynamic_index_in_dim(queue, slot, 0,
                                               keepdims=False)),
            slot, axis=0,
        )
        # (2) this device's stream position
        k = jnp.clip(t - stage, 0, circles * M - 1)
        k_valid = jnp.logical_and(t - stage >= 0,
                                  t - stage < circles * M)
        v = k // M
        m = jnp.mod(k, M)
        fresh = lax.dynamic_index_in_dim(sched.mbs, m, 0, keepdims=False)
        banked = lax.dynamic_index_in_dim(queue, m, 0, keepdims=False)
        x0 = jnp.where(v == 0, fresh, banked)
        x_in = jnp.where(stage == 0, x0, incoming)
        y = run_group(v, x_in)
        # (3) final-circle outputs of the last device carry the loss
        tgt = lax.dynamic_index_in_dim(tgt_mbs, m, 0, keepdims=False)
        take = jnp.logical_and(
            jnp.logical_and(stage == pp - 1, v == circles - 1), k_valid
        )
        loss_sum = loss_sum + jnp.where(take, head_loss(y, tgt), 0.0)
        handoff = lax.ppermute(y, pp_axis, sched.fwd_perm)
        return (handoff, queue, loss_sum), None

    (_, _, loss_sum), _ = lax.scan(
        tick, (zero, queue0, loss0), jnp.arange(n_ticks)
    )
    return lax.psum(loss_sum, pp_axis) / M


def stack_tp_pp_params(params, cfg, pp: int, tp: int):
    """Restack for TP-inside-PP: pipeline stages whose blocks are
    Megatron-sharded over a second mesh axis — the 3-axis
    (dp x pp x tp) deployment shape.

    Returns ``(staged_sharded, staged_replicated, replicated)``:

    * ``staged_sharded`` — block matmul shards, leaves
      ``[pp, tp, layers_per_stage, ...]``: ``in_specs=P(pp_axis,
      tp_axis)``.
    * ``staged_replicated`` — per-block LNs and post-psum biases
      (tp-replicated but stage-local), leaves ``[pp, layers_per_stage,
      ...]``: ``in_specs=P(pp_axis)``.
    * ``replicated`` — embeddings, final LN, head: ``in_specs=P()``.
    """
    from ..models.transformer import require_gpt2_block  # noqa: PLC0415
    from .tensor_parallel import stack_tp_params  # noqa: PLC0415

    require_gpt2_block(cfg, "parallel.pipeline.stack_tp_pp_params")
    if cfg.num_layers % pp:
        raise ValueError(
            f"pp={pp} must divide num_layers={cfg.num_layers}"
        )
    sharded, replicated = stack_tp_params(params, cfg, tp)
    per = cfg.num_layers // pp

    def _stack_blocks(tree_of_blocks, tp_leading):
        blocks = [tree_of_blocks[f"block{i}"]
                  for i in range(cfg.num_layers)]

        def _leaf(*leaves):
            stacked = jnp.stack([jnp.asarray(x) for x in leaves])
            # [L, (tp,) ...] -> [pp, per, (tp,) ...]
            stacked = jnp.reshape(
                stacked, (pp, per) + stacked.shape[1:]
            )
            if tp_leading:  # -> [pp, tp, per, ...]
                stacked = jnp.moveaxis(stacked, 2, 1)
            return stacked

        return jax.tree_util.tree_map(_leaf, *blocks)

    staged_sharded = _stack_blocks(sharded, tp_leading=True)
    staged_replicated = _stack_blocks(
        {k: v for k, v in replicated.items() if k.startswith("block")},
        tp_leading=False,
    )
    true_replicated = {
        k: jax.tree_util.tree_map(jnp.asarray, v)
        for k, v in replicated.items() if not k.startswith("block")
    }
    return staged_sharded, staged_replicated, true_replicated


def pp_tp_gpt_loss(staged_sharded, staged_replicated, replicated_params,
                   cfg, tokens, targets, pp_axis: str, tp_axis: str, *,
                   microbatches: int, pos_offset=0, positions=None,
                   remat: bool = True):
    """:func:`pp_gpt_loss` with each stage's blocks Megatron-sharded
    over ``tp_axis`` — TP inside PP, the composition a real multi-pod
    deployment runs (dp x pp x tp; the dp axis comes from the caller's
    mesh and gradient pmean as in ``tests/test_composed.py``).

    Per tick each rank runs its stage's layers on its head/width shard
    (two psums per block over ``tp_axis`` — parallel/tensor_parallel.py)
    and hands the full activation to the next stage over ``pp_axis``;
    head/loss/rejoin semantics are exactly :func:`pp_gpt_loss`.  Trees
    from :func:`stack_tp_pp_params`.
    """
    from .tensor_parallel import _tp_block  # noqa: PLC0415

    tp = lax.axis_size(tp_axis)
    # slice off both sharded leading dims ([pp, tp, ...] / [pp, ...]);
    # the tuple is one pytree so _Schedule's per-layer slicing and the
    # layers-per-stage guard see both trees together
    local = (
        jax.tree_util.tree_map(lambda a: a[0][0], staged_sharded),
        jax.tree_util.tree_map(lambda a: a[0], staged_replicated),
    )

    def layer_fn(p_j, x, positions, rope_tabs):
        sh_j, rep_j = p_j
        return _tp_block(cfg, sh_j, rep_j, x, positions, rope_tabs,
                         tp_axis, tp)

    sched = _Schedule(None, replicated_params, cfg, tokens, pp_axis,
                      microbatches, pos_offset, positions, remat,
                      local=local, layer_fn=layer_fn,
                      extra_axes=(tp_axis,))
    loss = _gpipe_loss(sched, replicated_params, cfg, targets, remat)
    # value-identical on every tp rank (post-psum activations): the
    # pmean collapses the tp axis for a replicated scalar return
    return lax.pmean(loss, tp_axis)
