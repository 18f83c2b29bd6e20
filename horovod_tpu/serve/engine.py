"""Slot engine: the compiled-model half of the serving plane.

Wraps the slot-based decode primitives (models/decode.py) for the
continuous-batching loop: ONE jitted ``decode_step`` over the whole
slot pool (shape never changes, so it compiles once), plus one jitted
``assign_slot`` per prompt-length *bucket* (prompts are right-padded to
the next power of two, so admission compiles O(log max_len) variants,
not one per prompt length).

Three orthogonal modes (ISSUE 15):

* ``kv_mode="paged"`` — KV rows live in fixed-size pages handed out by
  the pure allocator (serve/paged.py); the compiled step gathers each
  slot's prefix through its block table, so resident KV bytes track
  tokens actually written and admission capacity is judged in free
  pages (``can_admit``), not free slots.  ``"contiguous"`` keeps the
  PR-10 worst-case-row pool (the PR-14 waste baseline).
* ``width > 1`` — Megatron tensor parallelism inside the serving
  fleet: params split by ``tensor_parallel.stack_tp_params`` and the
  paged decode step shard_mapped over the ``width`` axis of a
  ``(replica, width)`` device-mesh view (PR-8 conventions: replicas
  ride DCN across processes, width rides ICI).  Each width shard holds
  only ITS heads' KV pages; every block rejoins through two psums.
  Requires ``kv_mode="paged"``.
* per-request sampling — temperature/top-k picks keyed purely on
  ``(request id, emission index, serve seed)`` (serve/sampling.py), so
  every rank derives the identical token and elastic replay reproduces
  the stream.  ``temperature == 0`` (default) is the old greedy path.

Determinism contract (the serving HVD001 invariant): given the same
config, params, seed, and the same sequence of admit/step/release
calls, every rank's engine produces bit-identical tokens — the
scheduler feeds every rank the same calls, the page allocator is a
pure state machine, the sampler's keys are pure functions of request
identity, and XLA's decode math is deterministic per backend.
"""

from __future__ import annotations

import weakref
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.decode import (
    assign_slot, assign_slot_paged, decode_step, decode_step_paged,
    init_cache, init_paged_pool,
)
from ..obs import memplane
from . import sampling
from .paged import PagedKV, pages_for

__all__ = ["SlotEngine", "prompt_bucket", "WIDTH_AXIS", "REPLICA_AXIS"]

_MIN_BUCKET = 8

# Mesh axis names of the serving width shard — the (replica, width)
# view of the PR-8 mesh conventions (DCN outer, ICI inner).
REPLICA_AXIS = "replica"
WIDTH_AXIS = "width"


def prompt_bucket(n: int, cache_len: int) -> int:
    """Pad target for an ``n``-token prefill: the next power of two
    (floor ``_MIN_BUCKET``), clamped to the cache length."""
    if n > cache_len:
        raise ValueError(
            f"prompt of {n} tokens exceeds the {cache_len}-token cache"
        )
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, cache_len)


def _pick_tokens(logits, temps, topks, keys, sidx):
    """Vectorized per-slot token pick: each row samples with ITS
    request's key at ITS emission index (sampling.sample_token — the
    same math the oracle tests run)."""

    def one(lg, t, k, base, i):
        return sampling.sample_token(lg, t, k,
                                     sampling.token_key(base, i))

    return jax.vmap(one)(logits, temps, topks, keys, sidx)


class SlotEngine:
    """A fixed pool of decode slots over one model.

    ``admit`` prefills a request into one slot (other slots' caches are
    bitwise untouched — pinned by tests/test_decode.py); ``step`` runs
    one decode iteration for the ACTIVE slots only (frozen rows ride
    along masked).  In paged mode eviction MUST be reported via
    :meth:`release_slot` so the slot's pages return to the free list;
    in contiguous mode an evicted slot is simply excluded from the next
    step's mask and overwritten by the next admission.
    """

    def __init__(self, cfg, params, num_slots: int,
                 max_len: Optional[int] = None, *,
                 kv_mode: str = "contiguous",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 width: int = 1,
                 sample_seed: int = 0):
        if kv_mode not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        from ..models.transformer import require_gpt2_block  # noqa: PLC0415

        require_gpt2_block(cfg, "serve.engine.SlotEngine")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.kv_mode = kv_mode
        self.width = int(width or 1)
        self.sample_seed = int(sample_seed)
        if self.width > 1 and kv_mode != "paged":
            raise ValueError(
                "width sharding requires kv_mode='paged' (the width-"
                "sharded decode program is the paged one)"
            )
        # Serving context cap: never beyond the model's trained context
        # (a learned-positions model NaN-poisons past max_len, and the
        # prefill forward rejects prompts beyond it) — admission
        # buckets and request validation both bound against THIS.
        self.cache_len = int(max_len or cfg.max_len)
        self.serve_len = min(self.cache_len, int(cfg.max_len))

        self.paged: Optional[PagedKV] = None
        self._mesh = None
        self._sh = self._rep = None
        if kv_mode == "paged":
            self.page_size = int(page_size)
            mp = pages_for(self.cache_len, self.page_size)
            # Default pool: worst case (every slot full) — safe, no
            # memory win; callers size it down to get one (bench/CI
            # prove the waste target with a bounded pool).
            self.num_pages = int(num_pages or num_slots * mp)
            self.paged = PagedKV(num_slots, self.num_pages,
                                 self.page_size, self.cache_len)
            # The virtual slot length the compiled step sees (whole
            # pages); >= cache_len, masked by pos beyond it.
            self.cache_len = self.paged.max_pages_per_slot * self.page_size
            kv_heads = cfg.kv_heads
            self.cache = init_paged_pool(cfg, self.num_pages,
                                         self.page_size, num_slots,
                                         kv_heads=kv_heads)
        else:
            self.cache = init_cache(cfg, num_slots, max_len)
            self.cache_len = int(self.cache["k"].shape[2])
            self.serve_len = min(self.cache_len, int(cfg.max_len))

        if self.width > 1:
            from jax.sharding import Mesh  # noqa: PLC0415

            from ..parallel.tensor_parallel import (  # noqa: PLC0415
                stack_tp_params,
            )

            devs = jax.devices()
            if len(devs) < self.width:
                raise ValueError(
                    f"width={self.width} needs at least that many "
                    f"devices; this process sees {len(devs)}"
                )
            self._mesh = Mesh(
                np.array(devs[:self.width]).reshape(1, self.width),
                (REPLICA_AXIS, WIDTH_AXIS),
            )
            self._sh, self._rep = stack_tp_params(params, cfg, self.width)

        # Host-side per-slot state, identical on every rank by the
        # schedule invariant: current input token, sampling params,
        # request stream root, emission index.
        self._cur = np.zeros(num_slots, np.int32)
        self._temp = np.zeros(num_slots, np.float32)
        self._topk = np.zeros(num_slots, np.int32)
        self._bkey = np.zeros((num_slots,) + sampling.KEY_SHAPE,
                              np.uint32)
        self._sidx = np.zeros(num_slots, np.int32)

        self._tables_dev = None
        self._build_compiled()
        self._assign_exec: Dict[int, object] = {}
        self._step_exec = None
        self._step_flops: Optional[float] = None
        self._step_flops_known = False
        # Memory-plane owner tags: weakref so a dropped engine (tests
        # build many) is pruned, not pinned alive by its observability.
        ref = weakref.ref(self)
        memplane.register_owner(
            "kv_cache", lambda: (lambda e: e.cache if e else None)(ref())
        )
        memplane.register_owner(
            "params", lambda: (lambda e: e.params if e else None)(ref())
        )

    # ---------------------------------------------------------- compiled

    def _build_compiled(self):
        cfg = self.cfg

        if self.kv_mode == "contiguous":

            def _assign(params, cache, slot, tokens, length, temp,
                        topk, bkey):
                cache, last = assign_slot(cfg, params, cache, slot,
                                          tokens, length)
                tok = sampling.sample_token(
                    last, temp, topk, sampling.token_key(bkey, 0)
                )
                return cache, tok

            def _step(params, cache, tokens, mask, temps, topks, keys,
                      sidx):
                logits, cache = decode_step(cfg, params, cache, tokens,
                                            write_mask=mask)
                return _pick_tokens(logits, temps, topks, keys,
                                    sidx), cache

            # The cache is the big state; donate it so each call
            # updates in place instead of keeping input and output
            # pools both live.
            self._assign_compiled = jax.jit(_assign, donate_argnums=(1,))
            self._step_compiled = jax.jit(_step, donate_argnums=(1,))
            return

        if self.width == 1:

            def _assign(params, pool, tables, slot, tokens, length,
                        temp, topk, bkey):
                pool, last = assign_slot_paged(cfg, params, pool,
                                               tables, slot, tokens,
                                               length)
                tok = sampling.sample_token(
                    last, temp, topk, sampling.token_key(bkey, 0)
                )
                return pool, tok

            def _step(params, pool, tables, tokens, mask, temps, topks,
                      keys, sidx):
                logits, pool = decode_step_paged(cfg, params, pool,
                                                 tables, tokens,
                                                 write_mask=mask)
                return _pick_tokens(logits, temps, topks, keys,
                                    sidx), pool

            self._assign_compiled = jax.jit(_assign, donate_argnums=(1,))
            self._step_compiled = jax.jit(_step, donate_argnums=(1,))
            return

        # Width-sharded paged decode: ONE jitted program shard_mapped
        # over the width axis.  The pool's kv-head axis is split across
        # the mesh (each shard holds its heads' pages); params travel
        # as the (sharded, replicated) pair; tables/tokens/sampling
        # state are replicated.  check_rep is off via shard_map_compat
        # (version shim), so the replicated outputs rely on the psum
        # rejoin — deterministic per backend, pinned by tests.
        from jax.sharding import PartitionSpec as P  # noqa: PLC0415

        from ..ops.collectives import shard_map_compat  # noqa: PLC0415

        pool_spec = {
            "k": P(None, None, None, WIDTH_AXIS, None),
            "v": P(None, None, None, WIDTH_AXIS, None),
            "pos": P(),
        }

        def _assign_sm(sh, rep, pool, tables, slot, tokens, length,
                       temp, topk, bkey):
            p = jax.tree_util.tree_map(lambda a: a[0], sh)
            pool, last = assign_slot_paged(
                cfg, p, pool, tables, slot, tokens, length,
                tp_axis=WIDTH_AXIS, rep=rep,
            )
            tok = sampling.sample_token(
                last, temp, topk, sampling.token_key(bkey, 0)
            )
            return pool, tok

        def _step_sm(sh, rep, pool, tables, tokens, mask, temps,
                     topks, keys, sidx):
            p = jax.tree_util.tree_map(lambda a: a[0], sh)
            logits, pool = decode_step_paged(
                cfg, p, pool, tables, tokens, write_mask=mask,
                tp_axis=WIDTH_AXIS, rep=rep,
            )
            return _pick_tokens(logits, temps, topks, keys,
                                sidx), pool

        self._assign_compiled = jax.jit(
            shard_map_compat(
                _assign_sm, mesh=self._mesh,
                in_specs=(P(WIDTH_AXIS), P(), pool_spec, P(), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(pool_spec, P()),
            ),
            donate_argnums=(2,),
        )
        self._step_compiled = jax.jit(
            shard_map_compat(
                _step_sm, mesh=self._mesh,
                in_specs=(P(WIDTH_AXIS), P(), pool_spec, P(), P(), P(),
                          P(), P(), P(), P()),
                out_specs=(P(), pool_spec),
            ),
            donate_argnums=(2,),
        )

    def _tables(self):
        """Device block-table array, cached until an admit/release/
        page-boundary allocation changes it — steady-state decode
        steps (no boundary crossing) reuse the uploaded array instead
        of paying a host rebuild + transfer per step."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(
                [self.paged.table_row(s) for s in range(self.num_slots)],
                jnp.int32,
            )
        return self._tables_dev

    def _params_args(self):
        if self.width > 1:
            return (self._sh, self._rep)
        return (self.params,)

    # --------------------------------------------------------- admission

    def can_admit(self, total_len: int) -> bool:
        """Admission capacity judgement: in paged mode, does the pool
        have free pages for this request's WORST CASE (prompt + full
        token budget) on top of every active commitment?  Contiguous
        mode has no page accounting — a free slot is always enough.
        Point-in-time view; a scheduling round admitting SEVERAL
        requests must use :meth:`admission_gate`."""
        if self.paged is None:
            return True
        return self.paged.can_admit(int(total_len))

    def admission_gate(self):
        """One scheduling round's capacity gate: ``gate(req, resume) ->
        bool``, accumulating the round's accepted worst cases so two
        same-round admissions are never judged against the same free
        pages (serve/paged.py admission_gate)."""
        if self.paged is None:
            return lambda req, resume: True
        page_gate = self.paged.admission_gate()

        def gate(req, resume) -> bool:
            return page_gate(len(req.prompt) + req.max_new_tokens)

        return gate

    def admit(self, slot: int, prompt: Sequence[int],
              resume: Sequence[int] = (), *,
              total_len: Optional[int] = None,
              temperature: float = 0.0, top_k: int = 0,
              rid: str = "") -> Optional[int]:
        """Prefill ``prompt`` (plus already-emitted ``resume`` tokens on
        elastic replay) into ``slot``.

        Fresh request: returns its FIRST generated token (sampled at
        emission index 0 with the request's key — greedy when
        ``temperature == 0``).  Replay: the resume tokens were already
        emitted to the client, so nothing new is generated here — the
        slot is rebuilt to the exact cache state the dead world held
        and returns None; the next ``step`` samples at emission index
        ``len(resume)``, continuing the stream bit-exactly.

        ``total_len`` (paged mode): the request's worst case, ``prompt
        + max_new_tokens`` rows — what the page allocator commits so a
        mid-decode page allocation can never fail.  Defaults to the
        full serving context.
        """
        if resume:
            seq = list(prompt) + list(resume[:-1])
            cur = int(resume[-1])
        else:
            seq = list(prompt)
            cur = None
        bucket = prompt_bucket(len(seq), self.serve_len)
        padded = np.zeros(bucket, np.int32)
        padded[:len(seq)] = seq
        bkey = np.asarray(sampling.request_key(self.sample_seed, rid),
                          np.uint32)
        if self.paged is not None:
            total = int(total_len or self.serve_len)
            self.paged.admit(slot, len(seq), max(total, len(seq)))
            self._tables_dev = None
            args = self._params_args() + (
                self.cache, self._tables(), jnp.asarray(slot, jnp.int32),
                jnp.asarray(padded), jnp.asarray(len(seq), jnp.int32),
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(top_k, jnp.int32), jnp.asarray(bkey),
            )
        else:
            args = (self.params, self.cache,
                    jnp.asarray(slot, jnp.int32), jnp.asarray(padded),
                    jnp.asarray(len(seq), jnp.int32),
                    jnp.asarray(temperature, jnp.float32),
                    jnp.asarray(top_k, jnp.int32), jnp.asarray(bkey))
        assign_fn = self._assign_exec.get(bucket)
        if assign_fn is None:
            # First admission at this bucket: AOT-compile once (the jit
            # dispatch cache never runs — ONE compile per bucket, same
            # handoff as _step_exec) and register the artifact's memory
            # breakdown while we hold it.
            assign_fn = self._assign_compiled.lower(*args).compile()
            memplane.register_program(f"serve.assign_b{bucket}", assign_fn)
            self._assign_exec[bucket] = assign_fn
        self.cache, first = assign_fn(*args)
        self._temp[slot] = temperature
        self._topk[slot] = top_k
        self._bkey[slot] = bkey
        if cur is not None:
            self._cur[slot] = cur
            self._sidx[slot] = len(resume)
            return None
        tok = int(first)
        self._cur[slot] = tok
        self._sidx[slot] = 1
        return tok

    def release_slot(self, slot: int) -> None:
        """Evict: return the slot's pages to the free list (no-op in
        contiguous mode — the next admission overwrites the rows)."""
        if self.paged is not None:
            self.paged.release(slot)
            self._tables_dev = None

    # ------------------------------------------------------------ decode

    def step(self, active: Iterable[int]) -> Dict[int, int]:
        """One decode iteration: every slot in ``active`` consumes its
        current token and emits the next; all other slots are frozen.
        Returns ``{slot: token}`` for the active slots."""
        slots: List[int] = sorted(active)
        if not slots:
            return {}
        mask = np.zeros(self.num_slots, bool)
        mask[slots] = True
        if self.paged is not None:
            # Page-boundary crossings: make sure each active slot's
            # next write position has a page (cannot fail under the
            # commitment invariant); the device table refreshes only
            # when an allocation actually changed it.
            for s in slots:
                if self.paged.ensure_capacity(s):
                    self._tables_dev = None
            extra = (self._tables(),)
        else:
            extra = ()
        step_fn = self._step_exec or self._step_compiled
        toks, self.cache = step_fn(
            *(self._params_args() + (self.cache,) + extra + (
                jnp.asarray(self._cur), jnp.asarray(mask),
                jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._bkey), jnp.asarray(self._sidx),
            ))
        )
        toks = np.asarray(toks)
        out = {}
        for s in slots:
            self._cur[s] = toks[s]
            self._sidx[s] += 1
            if self.paged is not None:
                self.paged.advance(s)
            out[s] = int(toks[s])
        return out

    # --------------------------------------------------------- profiling

    def step_flops(self) -> Optional[float]:
        """Model FLOPs of one ``decode_step`` over the full slot pool,
        from XLA's cost analysis of the compiled artifact (post-fusion,
        per-device; a width-sharded program reports its SHARD's flops,
        which is the point: width divides per-device work).  AOT lowered once and cached;
        None when the backend exposes no cost model."""
        if self._step_flops_known:
            return self._step_flops
        self._step_flops_known = True
        try:
            from ..obs.profile import flops_from_compiled  # noqa: PLC0415

            mask = np.ones(self.num_slots, bool)
            extra = (self._tables(),) if self.paged is not None else ()
            compiled = self._step_compiled.lower(
                *(self._params_args() + (self.cache,) + extra + (
                    jnp.asarray(self._cur), jnp.asarray(mask),
                    jnp.asarray(self._temp), jnp.asarray(self._topk),
                    jnp.asarray(self._bkey), jnp.asarray(self._sidx),
                ))
            ).compile()
            self._step_exec = compiled
            memplane.register_program("serve.decode_step", compiled)
            self._step_flops = flops_from_compiled(compiled)
        except Exception:
            self._step_flops = None
        return self._step_flops

    # ------------------------------------------------------ kv occupancy

    def kv_stats(self, active: Iterable[int] = ()) -> dict:
        """Allocated-vs-live KV bytes.

        Contiguous mode: the fixed-row math (memplane.kv_occupancy) —
        each busy slot charged its full worst-case ``cache_len`` row,
        the PR-14 waste baseline.  Paged mode: recomputed from the
        block table — allocated is pages actually handed out, so the
        only waste left is each slot's partial last page — plus the
        page-pool gauges (``page_size``/``pages_free``/``pages_used``)
        the /metrics surface exports."""
        pool = int(self.cache["k"].nbytes) + int(self.cache["v"].nbytes)
        if self.paged is not None:
            per_pos = pool / float(self.num_pages * self.page_size)
            out = self.paged.stats(per_pos)
            out["pool_bytes"] = pool
            # What the PR-10 contiguous design would have reserved for
            # the same busy slots (slots x worst-case rows): the PR-14
            # baseline recomputed on THIS traffic, so the paged win is
            # an apples-to-apples number in every record.
            out["contiguous_equiv_bytes"] = int(
                out["slots_in_use"] * self.cache_len * per_pos
            )
            return out
        per_pos = pool / float(self.num_slots * self.cache_len)
        positions = np.asarray(self.cache["pos"]).reshape(-1)
        if positions.shape[0] < self.num_slots:  # legacy scalar pos
            positions = np.full(self.num_slots, int(positions[0] if
                                                    positions.size else 0))
        return memplane.kv_occupancy(
            positions.tolist(), list(active), self.cache_len, per_pos,
            pool_bytes=pool,
        )

    # ---------------------------------------------------------- hot swap

    def set_params(self, params) -> None:
        """Swap the served weights in place (weight hot-swap,
        serve/hotswap.py).  The jitted step/assign executables key on
        shapes and dtypes, which a same-model checkpoint preserves — a
        flip costs zero recompiles and the KV cache is untouched (the
        flip happens between decode steps; in-flight requests continue
        over their existing cache).  A width-sharded engine restacks
        the checkpoint into its (sharded, replicated) pair — same
        shapes, so still zero recompiles."""
        old = jax.tree_util.tree_structure(self.params)
        new = jax.tree_util.tree_structure(params)
        if old != new:
            raise ValueError(
                f"hot-swap params tree mismatch: engine serves {old}, "
                f"got {new} — this checkpoint belongs to a different "
                f"model"
            )
        self.params = params
        if self.width > 1:
            from ..parallel.tensor_parallel import (  # noqa: PLC0415
                stack_tp_params,
            )

            self._sh, self._rep = stack_tp_params(params, self.cfg,
                                                  self.width)

    # ------------------------------------------------------------- reset

    def reset(self) -> None:
        """Drop every slot (elastic epoch rebuild): fresh zero cache,
        free page pool, zero cursors.  Compiled functions are retained
        — recovery pays re-prefill, never re-compile."""
        if self.paged is not None:
            self.paged.reset()
            self._tables_dev = None
            self.cache = init_paged_pool(self.cfg, self.num_pages,
                                         self.page_size, self.num_slots,
                                         kv_heads=self.cfg.kv_heads)
        else:
            self.cache = init_cache(self.cfg, self.num_slots,
                                    self.cache_len)
        self._cur[:] = 0
        self._temp[:] = 0.0
        self._topk[:] = 0
        self._bkey[:] = 0
        self._sidx[:] = 0
