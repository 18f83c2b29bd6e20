"""Slot-based KV-cache incremental decoding for the GPT family.

This is the model half of the serving plane (``horovod_tpu/serve/``):
the cache is a fixed pool of *slots* (batch rows) with **per-slot write
positions**, so a continuous-batching scheduler can admit a new request
into one slot — overwriting it via :func:`assign_slot` — while the
other slots keep decoding, all through ONE compiled ``decode_step``
shape (Orca-style iteration-level scheduling needs exactly this: the
batch never changes shape, only which rows are live).

* :func:`init_cache` — per-layer K/V buffers ``[L, b, max_len, kv_heads,
  head_dim]`` plus per-slot write positions ``pos [b]``.
* :func:`decode_step` — one token for every slot: append its K/V at
  that slot's own position, attend the single query against the slot's
  prefix, return next-token logits.  ``write_mask [b]`` freezes rows
  (no K/V write, no position advance) — finished or free slots ride
  along for free.
* :func:`prefill` — single-forward prefill: ONE full causal forward
  writes every position's K/V into the cache in one shot (the scanned
  token-by-token path survives as :func:`prefill_scan`, and the two are
  pinned bitwise against each other by tests/test_decode.py).
* :func:`generate` — greedy/sampled continuation; ``eos_id=`` freezes
  finished rows (masked writes, repeated pad) and exits the loop early
  once every row is done, so short completions in a batch don't pay for
  the longest.
* :func:`reset_slot` / :func:`assign_slot` — the serving primitives:
  clear one slot; prefill one request into one slot while the other
  slots' caches stay bitwise untouched.

The block wiring is NOT re-implemented here: each step runs
``raw_block_forward`` (the single-source :func:`block_math`) with an
``attend`` override that appends to the cache and attends against the
prefix — so GQA head routing, fp8 activation storage, and any future
block change flow into decoding automatically.  RoPE is applied inside
the override (per-slot positions need per-row angle tables, which the
shared ``[s, half]`` broadcast in ``attention_mixer`` cannot express), with
the same fp32 rotation math as ``ops/rope.py``.

Dense blocks only (MoE is training-path-only, parallel/moe.py).
Decoding past a slot's cache end drops the write and poisons that
slot's logits with NaN (the same loud-failure contract as the
out-of-range wpe gather in ``GPT.__call__``) instead of silently
overwriting the last position.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes
from .transformer import (TransformerConfig, raw_block_forward,
                          require_gpt2_block)

__all__ = [
    "init_cache",
    "decode_step",
    "prefill",
    "prefill_scan",
    "generate",
    "reset_slot",
    "assign_slot",
    "init_paged_pool",
    "decode_step_paged",
    "assign_slot_paged",
]


def _params(params):
    if set(params.keys()) == {"params"}:
        params = params["params"]
    return params


def init_cache(cfg: TransformerConfig, batch: int, max_len=None):
    """Empty slot pool: per-layer K/V at the cache dtype + per-slot
    write positions ``pos [batch]``."""
    require_gpt2_block(cfg, "models.decode.init_cache")
    if cfg.moe_experts > 0:
        raise ValueError("decode cache supports dense blocks only")
    s = max_len or cfg.max_len
    kv = (cfg.num_layers, batch, s, cfg.kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _slot_pos(cache, batch: int):
    """Per-slot positions ``[b]``; legacy scalar-``pos`` caches (pre-slot
    refactor pytrees restored from disk) broadcast to the batch."""
    pos = cache["pos"]
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    return pos


def _rope_rows(x, cos, sin):
    """Rotate ``x [b, 1, heads, hd]`` by PER-ROW tables ``[b, hd//2]``
    — the same fp32 math as ``ops.rope.apply_rope_tables``, with the
    broadcast moved from the sequence axis to the batch axis (each slot
    sits at its own position)."""
    half = x.shape[-1] // 2
    c = cos[:, None, None, :]
    s = sin[:, None, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    )
    return out.astype(x.dtype)


def _attend_cached(cfg, q, k_cache, v_cache, pos):
    """One query per slot against that slot's cache prefix: ``q [b, h,
    hd]``, ``k/v_cache [b, S, hkv, hd]``, ``pos [b]`` -> ``[b, h, hd]``.
    Positions beyond each slot's own ``pos`` are masked; with
    ``cfg.attention_window`` the band's lower edge is masked too (parity
    with the flash kernel's sliding window); GQA queries fold onto their
    kv group via reshape, no K/V broadcast.  The kv-head count is read
    off the CACHE shape, not the config, so a width-sharded caller
    (heads split over a mesh axis) reuses this math bitwise on its
    shard."""
    b, h, hd = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    group = h // hkv
    qg = q.reshape(b, hkv, group, hd).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    st = jnp.einsum("bkgd,bskd->bkgs", qg, kf) * (hd ** -0.5)
    idx = jnp.arange(s)[None, None, None, :]
    pb = pos[:, None, None, None]
    mask = idx > pb
    if cfg.attention_window is not None:
        mask = mask | (idx < pb - (cfg.attention_window - 1))
    st = jnp.where(mask, jnp.finfo(jnp.float32).min / 2, st)
    p = jax.nn.softmax(st, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf)
    return out.reshape(b, h, hd)


def _attend_prefix(cfg, q, k_cache, v_cache):
    """All prompt queries at once against the (just-written) cache:
    ``q [b, s, h, hd]``, ``k/v_cache [b, S, hkv, hd]`` -> ``[b, s, h,
    hd]``.  Query position ``t`` sees exactly the mask the scanned path
    applies at ``pos == t`` (future positions min-filled, window lower
    edge too), so the two prefills softmax over identical score rows.
    kv-head count comes from the cache shape (width-shard-reusable,
    like :func:`_attend_cached`)."""
    b, s, h, hd = q.shape
    big = k_cache.shape[1]
    hkv = k_cache.shape[2]
    group = h // hkv
    qg = q.reshape(b, s, hkv, group, hd).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    st = jnp.einsum("btkgd,bskd->btkgs", qg, kf) * (hd ** -0.5)
    idx = jnp.arange(big)[None, None, None, None, :]
    t = jnp.arange(s)[None, :, None, None, None]
    mask = idx > t
    if cfg.attention_window is not None:
        mask = mask | (idx < t - (cfg.attention_window - 1))
    st = jnp.where(mask, jnp.finfo(jnp.float32).min / 2, st)
    p = jax.nn.softmax(st, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", p, vf)
    return out.reshape(b, s, h, hd)


def decode_step(cfg: TransformerConfig, params, cache, tokens_t,
                write_mask=None):
    """Decode one token per slot: ``tokens_t [b]`` -> ``(logits
    [b, vocab], cache)`` with each slot's K/V appended at its OWN
    ``cache["pos"][slot]``.

    ``write_mask [b]`` (bool, default all-true): rows where it is False
    are frozen — their K/V write is dropped and their position does not
    advance — so evicted/finished slots ride the compiled step without
    touching their cache.  Frozen rows still produce (meaningless)
    logits; callers ignore them.
    """
    require_gpt2_block(cfg, "models.decode.decode_step")
    p = _params(params)
    b = tokens_t.shape[0]
    pos = _slot_pos(cache, b)
    s_cache = cache["k"].shape[2]

    # Per-slot embedding scaffold (the shared _gpt_embed broadcasts one
    # position vector across the batch, which per-slot decode cannot
    # use): same gather/cast/add math per row, including the loud NaN
    # fill past max_len on the learned table.  Keep in lockstep with
    # parallel/tensor_parallel._gpt_embed — it is the contract source,
    # and the bitwise prefill-vs-scan pin in tests/test_decode.py is
    # what catches drift between the two.
    x = jnp.take(
        p["wte"]["embedding"], tokens_t[:, None], axis=0
    ).astype(cfg.dtype)
    if cfg.pos_embedding == "learned":
        pe = jnp.take(p["wpe"], pos, axis=0,
                      mode="fill", fill_value=jnp.nan)
        x = x + pe.astype(cfg.dtype)[:, None]
    rope_tabs = None
    if cfg.pos_embedding == "rope":
        from ..ops.rope import rope_tables  # noqa: PLC0415

        rope_tabs = rope_tables(pos, cfg.head_dim, cfg.rope_theta)

    if write_mask is None:
        write_pos = pos
        advance = jnp.ones((b,), jnp.int32)
    else:
        # Masked rows write at index s_cache — out of bounds, which
        # scatter-with-mode="drop" discards — and stay put.
        write_pos = jnp.where(write_mask, pos, s_cache)
        advance = write_mask.astype(jnp.int32)

    rows = jnp.arange(b)
    k_new, v_new = cache["k"], cache["v"]
    for i in range(cfg.num_layers):

        def attend(q, k_t, v_t, _i=i):
            # q [b, 1, nh, hd]; k_t/v_t [b, 1, nkv, hd].  RoPE applies
            # HERE (per-row tables); attention_mixer skipped it because we
            # passed rope_tabs=None.  Append at each slot's own
            # position, then attend against that slot's prefix.
            nonlocal k_new, v_new
            if rope_tabs is not None:
                q = _rope_rows(q, *rope_tabs)
                k_t = _rope_rows(k_t, *rope_tabs)
            k_new = k_new.at[_i, rows, write_pos].set(
                k_t[:, 0].astype(cfg.dtype), mode="drop"
            )
            v_new = v_new.at[_i, rows, write_pos].set(
                v_t[:, 0].astype(cfg.dtype), mode="drop"
            )
            att = _attend_cached(cfg, q[:, 0], k_new[_i], v_new[_i], pos)
            return att[:, None]

        x = raw_block_forward(cfg, p[f"block{i}"], x, pos[:, None],
                              None, attend=attend)

    from ..parallel.tensor_parallel import _gpt_head  # noqa: PLC0415

    logits = _gpt_head(p, cfg, x)[:, 0]
    # A slot writing past its cache end would CLAMP in the old
    # dynamic-update spelling (silently overwriting the last position);
    # here the write is dropped AND that slot's logits are poisoned —
    # per slot, so one full request never corrupts its batch peers.
    overrun = pos >= s_cache
    if write_mask is not None:
        overrun = overrun & write_mask
    logits = jnp.where(overrun[:, None], jnp.nan, logits)
    return logits, {"k": k_new, "v": v_new, "pos": pos + advance}


def prefill(cfg: TransformerConfig, params, tokens, max_len=None,
            lengths=None):
    """Single-forward prefill: feed prompts ``[b, s]`` through ONE full
    causal forward, writing every position's K/V into a fresh cache in
    one shot — O(1) dispatches where :func:`prefill_scan` pays O(s)
    sequential ``decode_step`` launches.  Returns per-position logits
    ``[b, s, vocab]`` and the filled cache.

    ``lengths [b]`` (optional): true per-row prompt lengths for
    right-padded batches — each slot's ``pos`` is set to its own length
    so pad positions stay masked and the next decode overwrites them.
    Pinned bitwise against the scanned path by tests/test_decode.py.

    One divergence from :func:`prefill_scan`: prompts longer than
    ``cfg.max_len`` fed into an enlarged cache (rope models only — no
    table to run off) trip the full forward's max_len guard here; use
    the scanned path for that corner.
    """
    require_gpt2_block(cfg, "models.decode.prefill")
    from ..parallel.tensor_parallel import (  # noqa: PLC0415
        _gpt_embed, _gpt_head,
    )

    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len)
    s_cache = cache["k"].shape[2]
    if s > s_cache:
        raise ValueError(
            f"prompt length {s} exceeds the {s_cache}-token cache; "
            f"raise max_len"
        )
    p = _params(params)
    # Explicit contiguous positions: prompts entering a decode cache are
    # always contiguous, and passing them explicitly keeps zigzag-layout
    # models decodable (their forward demands explicit positions; the
    # attend override below replaces the zigzag schedule anyway) — the
    # scanned path always drove decode_step with explicit positions too.
    x, positions, rope_tabs = _gpt_embed(p, cfg, tokens, 0,
                                         jnp.arange(s))

    k_new, v_new = cache["k"], cache["v"]
    for i in range(cfg.num_layers):

        def attend(q, k_t, v_t, _i=i):
            # k_t/v_t [b, s, nkv, hd], rope-applied by attention_mixer (the
            # shared [s, half] tables are exactly right here: every row
            # sits at positions 0..s-1) — write the whole prompt's K/V
            # in one shot, then attend every query against the prefix.
            nonlocal k_new, v_new
            k_new = lax.dynamic_update_slice(
                k_new, k_t.astype(cfg.dtype)[None], (_i, 0, 0, 0, 0)
            )
            v_new = lax.dynamic_update_slice(
                v_new, v_t.astype(cfg.dtype)[None], (_i, 0, 0, 0, 0)
            )
            return _attend_prefix(cfg, q, k_new[_i], v_new[_i])

        x = raw_block_forward(cfg, p[f"block{i}"], x, positions,
                              rope_tabs, attend=attend)

    logits = _gpt_head(p, cfg, x)
    if lengths is None:
        pos = jnp.full((b,), s, jnp.int32)
    else:
        pos = jnp.asarray(lengths, jnp.int32)
    return logits, {"k": k_new, "v": v_new, "pos": pos}


def prefill_scan(cfg: TransformerConfig, params, tokens, max_len=None):
    """Token-by-token prefill: the prompt scanned through
    ``decode_step`` (one compiled loop, O(s) sequential dispatches).
    Kept as the bitwise oracle for :func:`prefill` — the incremental
    dataflow this module exists to get right."""
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len)

    def step(cache, tok_t):
        logits, cache = decode_step(cfg, params, cache, tok_t)
        return cache, logits

    cache, logits = lax.scan(step, cache, tokens.T)
    return jnp.transpose(logits, (1, 0, 2)), cache


def reset_slot(cache, slot):
    """Clear slot ``slot``: zero its K/V rows, rewind its position.
    The other slots' buffers are bitwise untouched."""
    return {
        "k": cache["k"].at[:, slot].set(0),
        "v": cache["v"].at[:, slot].set(0),
        "pos": cache["pos"].at[slot].set(0),
    }


def assign_slot(cfg: TransformerConfig, params, cache, slot, tokens,
                length=None):
    """Prefill ONE request into slot ``slot`` of a multi-slot cache
    while every other slot's K/V stays bitwise untouched — the
    admission primitive of the continuous-batching scheduler.

    ``tokens [s]`` may be right-padded to a bucket length; ``length``
    (dynamic scalar, default ``s``) is the true prompt length.  Returns
    ``(cache, last_logits [vocab])`` where ``last_logits`` is the
    prediction at the prompt's final real position (the request's first
    generated token is its argmax/sample).  ``slot`` and ``length`` are
    trace-time dynamic, so one compiled assign per prompt-length bucket
    serves every admission.
    """
    require_gpt2_block(cfg, "models.decode.assign_slot")
    s = tokens.shape[0]
    s_cache = cache["k"].shape[2]
    if s > s_cache:
        raise ValueError(
            f"assign_slot: {s} prompt tokens exceed the {s_cache}-token "
            f"slot cache"
        )
    if length is None:
        length = s
    length = jnp.asarray(length, jnp.int32)
    # Prefill into a BUCKET-length cache, not the slot length: the
    # admission then pays O(s^2) attention and writes only [0:s) of the
    # slot.  Positions >= s keep the evicted predecessor's K/V — masked
    # by pos until the advancing decode overwrites them, so they never
    # attend; zeroing them would cost a full-slot write per admit.
    logits, one = prefill(cfg, params, tokens[None], max_len=s,
                          lengths=length[None])
    k = lax.dynamic_update_slice(cache["k"], one["k"], (0, slot, 0, 0, 0))
    v = lax.dynamic_update_slice(cache["v"], one["v"], (0, slot, 0, 0, 0))
    pos = cache["pos"].at[slot].set(length)
    last = jnp.take(logits[0], length - 1, axis=0)
    return {"k": k, "v": v, "pos": pos}, last


def init_paged_pool(cfg: TransformerConfig, num_pages: int,
                    page_size: int, num_slots: int,
                    kv_heads: Optional[int] = None):
    """Paged KV pool: per-layer K/V in fixed-size PAGES (``page_size``
    token rows each) shared by every slot, plus per-slot write
    positions.  A slot's cache is whatever pages its block table
    (serve/paged.py) names, so resident KV bytes scale with tokens
    actually written instead of ``slots x max_len`` — the vLLM block-
    table idea on top of :func:`decode_step`'s masked-write machinery.

    ``kv_heads`` overrides the per-pool head count for width-sharded
    pools (each device of the width axis holds only ITS heads' pages).
    """
    require_gpt2_block(cfg, "models.decode.init_paged_pool")
    if cfg.moe_experts > 0:
        raise ValueError("decode cache supports dense blocks only")
    hkv = kv_heads if kv_heads is not None else cfg.kv_heads
    kv = (cfg.num_layers, num_pages, page_size, hkv, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "pos": jnp.zeros((num_slots,), jnp.int32),
    }


def decode_step_paged(cfg: TransformerConfig, params, pool, tables,
                      tokens_t, write_mask=None, *, tp_axis=None,
                      rep=None):
    """One decode step through the BLOCK TABLE: ``tokens_t [b]`` ->
    ``(logits [b, vocab], pool)`` where each slot's K/V append lands in
    page ``tables[slot, pos // page_size]`` at row ``pos % page_size``,
    and attention gathers the slot's pages back into its virtually
    contiguous prefix — logical position ``t`` maps to gathered index
    ``t`` exactly, so the math (and the tokens) are BITWISE the
    contiguous :func:`decode_step`'s whenever the virtual length
    matches (pinned by tests/test_paged.py).

    ``tables [b, max_pages]`` int32: page ids into the pool; entries
    past a slot's allocated prefix carry ``num_pages`` (the null page)
    — out of bounds, so scatter-``drop`` discards writes there and the
    gather zero-fills (masked by ``pos`` regardless).  Decoding past
    the virtual capacity drops the write and NaN-poisons that slot's
    logits, the same loud-failure contract as the contiguous path.

    ``tp_axis``/``rep``: width sharding (Megatron TP inside the
    serving fleet).  When set, ``params`` is this shard's block tree
    and ``rep`` the replicated tree (both from
    ``tensor_parallel.stack_tp_params``), the pool holds only this
    shard's ``kv_heads // width`` heads' pages, and each block rejoins
    through the two row-parallel psums over ``tp_axis`` — call inside
    ``shard_map`` (serve/engine.py does).
    """
    require_gpt2_block(cfg, "models.decode.decode_step_paged")
    if tp_axis is None:
        p = _params(params)
        rep = p
        tp = 1
    else:
        from ..ops.collectives import axis_size  # noqa: PLC0415

        p = params
        tp = axis_size(tp_axis)
    b = tokens_t.shape[0]
    pos = _slot_pos(pool, b)
    num_pages, ps = pool["k"].shape[1], pool["k"].shape[2]
    mp = tables.shape[1]
    virt = mp * ps

    # Per-slot embedding scaffold — same math as decode_step (the
    # bitwise pin between the two paths is what catches drift).
    x = jnp.take(
        rep["wte"]["embedding"], tokens_t[:, None], axis=0
    ).astype(cfg.dtype)
    if cfg.pos_embedding == "learned":
        pe = jnp.take(rep["wpe"], pos, axis=0,
                      mode="fill", fill_value=jnp.nan)
        x = x + pe.astype(cfg.dtype)[:, None]
    rope_tabs = None
    if cfg.pos_embedding == "rope":
        from ..ops.rope import rope_tables  # noqa: PLC0415

        rope_tabs = rope_tables(pos, cfg.head_dim, cfg.rope_theta)

    # Write coordinates: page id of each slot's next position (null
    # page for frozen rows and overruns -> scatter drops them).
    page_of = jnp.take_along_axis(
        tables, jnp.minimum(pos // ps, mp - 1)[:, None], axis=1
    )[:, 0]
    in_range = pos < virt
    if write_mask is None:
        advance = jnp.ones((b,), jnp.int32)
        w_page = jnp.where(in_range, page_of, num_pages)
    else:
        advance = write_mask.astype(jnp.int32)
        w_page = jnp.where(write_mask & in_range, page_of, num_pages)
    w_off = pos % ps

    k_new, v_new = pool["k"], pool["v"]
    for i in range(cfg.num_layers):

        def attend(q, k_t, v_t, _i=i):
            # q [b, 1, nh, hd]; k_t/v_t [b, 1, nkv, hd].  RoPE per-row
            # here (attention_mixer got rope_tabs=None), append into the
            # slot's current page, then gather the block table back
            # into the virtually contiguous [b, virt, nkv, hd] prefix.
            nonlocal k_new, v_new
            if rope_tabs is not None:
                q = _rope_rows(q, *rope_tabs)
                k_t = _rope_rows(k_t, *rope_tabs)
            k_new = k_new.at[_i, w_page, w_off].set(
                k_t[:, 0].astype(cfg.dtype), mode="drop"
            )
            v_new = v_new.at[_i, w_page, w_off].set(
                v_t[:, 0].astype(cfg.dtype), mode="drop"
            )
            with jax.named_scope(scopes.KV_GATHER):
                kc = jnp.take(k_new[_i], tables, axis=0,
                              mode="fill", fill_value=0)
                vc = jnp.take(v_new[_i], tables, axis=0,
                              mode="fill", fill_value=0)
                kc = kc.reshape(b, virt, kc.shape[-2], kc.shape[-1])
                vc = vc.reshape(b, virt, vc.shape[-2], vc.shape[-1])
            att = _attend_cached(cfg, q[:, 0], kc, vc, pos)
            return att[:, None]

        if tp_axis is None:
            x = raw_block_forward(cfg, p[f"block{i}"], x, pos[:, None],
                                  None, attend=attend)
        else:
            from ..parallel.tensor_parallel import _tp_block  # noqa: PLC0415

            x = _tp_block(cfg, p[f"block{i}"], rep[f"block{i}"], x,
                          pos[:, None], None, tp_axis, tp,
                          attend=attend)

    from ..parallel.tensor_parallel import _gpt_head  # noqa: PLC0415

    logits = _gpt_head(rep, cfg, x)[:, 0]
    overrun = pos >= virt
    if write_mask is not None:
        overrun = overrun & write_mask
    logits = jnp.where(overrun[:, None], jnp.nan, logits)
    return logits, {"k": k_new, "v": v_new, "pos": pos + advance}


def _prefill_shard(cfg, p, rep, tokens, lengths, tp_axis):
    """Width-sharded single-forward prefill: :func:`prefill`'s math on
    this shard's heads — the mini-cache holds ``kv_heads // width``
    heads, blocks rejoin through the row-parallel psums.  Returns
    ``(logits [b, s, vocab], {"k", "v", "pos"})`` like prefill."""
    from ..parallel.tensor_parallel import (  # noqa: PLC0415
        _gpt_embed, _gpt_head, _tp_block,
    )

    from ..ops.collectives import axis_size  # noqa: PLC0415

    tp = axis_size(tp_axis)
    nkv = cfg.kv_heads // tp
    b, s = tokens.shape
    x, positions, rope_tabs = _gpt_embed(rep, cfg, tokens, 0,
                                         jnp.arange(s))
    k_new = jnp.zeros((cfg.num_layers, b, s, nkv, cfg.head_dim),
                      cfg.dtype)
    v_new = jnp.zeros_like(k_new)
    for i in range(cfg.num_layers):

        def attend(q, k_t, v_t, _i=i):
            nonlocal k_new, v_new
            k_new = lax.dynamic_update_slice(
                k_new, k_t.astype(cfg.dtype)[None], (_i, 0, 0, 0, 0)
            )
            v_new = lax.dynamic_update_slice(
                v_new, v_t.astype(cfg.dtype)[None], (_i, 0, 0, 0, 0)
            )
            return _attend_prefix(cfg, q, k_new[_i], v_new[_i])

        x = _tp_block(cfg, p[f"block{i}"], rep[f"block{i}"], x,
                      positions, rope_tabs, tp_axis, tp, attend=attend)

    logits = _gpt_head(rep, cfg, x)
    pos = jnp.asarray(lengths, jnp.int32)
    return logits, {"k": k_new, "v": v_new, "pos": pos}


def assign_slot_paged(cfg: TransformerConfig, params, pool, tables,
                      slot, tokens, length=None, *, tp_axis=None,
                      rep=None):
    """Admit ONE request into the paged pool: prefill the prompt into a
    contiguous mini-cache (the exact :func:`prefill` math, so the
    contiguous bitwise pins carry over), then scatter its rows into the
    slot's pages.  Positions past the slot's allocated prefix hit the
    null page and are dropped; every other slot's pages are bitwise
    untouched.  Returns ``(pool, last_logits [vocab])``.

    ``tp_axis``/``rep``: width-sharded admission — the mini-cache and
    the pool both hold only this shard's heads (see
    :func:`decode_step_paged`).
    """
    require_gpt2_block(cfg, "models.decode.assign_slot_paged")
    s = tokens.shape[0]
    ps = pool["k"].shape[2]
    mp = tables.shape[1]
    if s > mp * ps:
        raise ValueError(
            f"assign_slot_paged: {s} prompt tokens exceed the "
            f"{mp * ps}-row virtual slot capacity"
        )
    if length is None:
        length = s
    length = jnp.asarray(length, jnp.int32)
    if tp_axis is None:
        logits, one = prefill(cfg, params, tokens[None], max_len=s,
                              lengths=length[None])
    else:
        logits, one = _prefill_shard(cfg, params, rep, tokens[None],
                                     length[None], tp_axis)
    with jax.named_scope(scopes.KV_SCATTER):
        pidx = jnp.arange(s)
        row = jnp.take(tables, slot, axis=0)
        pages = jnp.take(row, pidx // ps)
        offs = pidx % ps
        k = pool["k"].at[:, pages, offs].set(one["k"][:, 0], mode="drop")
        v = pool["v"].at[:, pages, offs].set(one["v"][:, 0], mode="drop")
        pos = pool["pos"].at[slot].set(length)
    last = jnp.take(logits[0], length - 1, axis=0)
    return {"k": k, "v": v, "pos": pos}, last


def generate(cfg: TransformerConfig, params, prompt, steps: int,
             max_len=None, temperature: float = 0.0, top_k: int = 0,
             key=None, eos_id: Optional[int] = None):
    """Continuation: ``prompt [b, s]`` -> ``[b, steps]`` tokens.

    ``temperature == 0`` (default) is greedy argmax.  ``temperature > 0``
    samples ``softmax(logits / temperature)`` (requires ``key``);
    ``top_k > 0`` additionally truncates to the k most likely tokens
    before sampling.

    ``eos_id``: rows that emit it are FROZEN — their cache writes are
    masked, their position stops advancing, and they repeat ``eos_id``
    as pad — and the decode loop exits as soon as every row is done, so
    a batch of short completions stops paying for its longest member.
    """
    require_gpt2_block(cfg, "models.decode.generate")
    if temperature > 0 and key is None:
        raise ValueError("temperature > 0 requires a PRNG key")

    def pick(logits, k):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        lt = logits / temperature
        if top_k > 0:
            kth = lax.top_k(lt, top_k)[0][..., -1:]
            lt = jnp.where(lt < kth, -jnp.inf, lt)
        return jax.random.categorical(k, lt, axis=-1)

    b = prompt.shape[0]
    if steps <= 0:
        return jnp.zeros((b, 0), jnp.int32)
    keys = (
        jax.random.split(key, steps) if key is not None
        else jnp.zeros((steps, 2), jnp.uint32)
    )
    logits, cache = prefill(cfg, params, prompt, max_len)
    first = pick(logits[:, -1], keys[0]).astype(jnp.int32)

    if eos_id is None:
        # Emit the NEWLY picked token from the scan (seeded with
        # ``first``): token i+1 costs exactly one decode_step on token
        # i, so ``steps`` tokens take ``steps - 1`` scan iterations.
        def step(carry, k):
            cache, tok = carry
            logits, cache = decode_step(cfg, params, cache, tok)
            new = pick(logits, k).astype(jnp.int32)
            return (cache, new), new

        (_, _), toks = lax.scan(step, (cache, first), keys[1:])
        return jnp.concatenate([first[:, None], toks.T], axis=1)

    # eos-aware path: same per-row math as the scan above (frozen rows
    # only freeze THEMSELVES — rows are independent), with a while_loop
    # so the batch stops as soon as its last row finishes.
    done0 = first == eos_id
    out0 = jnp.full((b, steps), eos_id, jnp.int32).at[:, 0].set(first)

    def cond(carry):
        step_i, _, _, done, _ = carry
        return (step_i < steps) & ~jnp.all(done)

    def body(carry):
        step_i, cache, tok, done, out = carry
        logits, cache = decode_step(cfg, params, cache, tok,
                                    write_mask=~done)
        new = pick(logits, keys[step_i]).astype(jnp.int32)
        new = jnp.where(done, eos_id, new)
        out = out.at[:, step_i].set(new)
        done = done | (new == eos_id)
        return step_i + 1, cache, new, done, out

    _, _, _, _, out = lax.while_loop(
        cond, body, (jnp.asarray(1, jnp.int32), cache, first, done0, out0)
    )
    return out
