"""Serving plane (horovod_tpu/serve/): the continuous-batching
scheduler as a pure decision table, the slot engine against the
single-stream ``generate`` oracle, sequence-sharded long-context
attention against the replicated math, and the end-to-end elastic
story — staggered requests through a live 2-proc fleet with a
mid-stream kill recovered by respawn + replay, zero requests dropped.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models.decode import generate
from horovod_tpu.models.transformer import gpt
from horovod_tpu.serve import (
    Request, ServeJob, SlotEngine, SlotScheduler, validate_request,
)
from horovod_tpu.serve.engine import prompt_bucket

AXIS = "seq"


def _req(rid, n=3, mnt=4, eos=None):
    return Request(rid=rid, prompt=tuple(range(1, n + 1)),
                   max_new_tokens=mnt, eos_id=eos)


def _model(**overrides):
    common = dict(num_layers=1, num_heads=2, emb_dim=32, max_len=64,
                  vocab_size=64, dtype=jnp.float32,
                  attention_impl="reference")
    common.update(overrides)
    return gpt("nano", **common)


# ---------------------------------------------------------------------------
# Scheduler core: the pure decision table
# ---------------------------------------------------------------------------


def test_admit_fcfs_into_lowest_free_slots():
    s = SlotScheduler(3)
    for i in range(2):
        s.enqueue(_req(f"r{i}"))
    admits = s.admit(step=1)
    assert [(a.slot, a.req.rid) for a in admits] == [(0, "r0"), (1, "r1")]
    assert s.free_slots() == [2]
    assert s.queue_depth == 0 and s.active_slots == 2


def test_slot_exhaustion_queues_and_recycles():
    s = SlotScheduler(2)
    for i in range(5):
        s.enqueue(_req(f"r{i}", mnt=1))
    assert [a.req.rid for a in s.admit()] == ["r0", "r1"]
    assert s.queue_depth == 3  # pool exhausted -> queued
    assert s.admit() == []     # no free slot, no admission
    s.record(0, 7)
    s.record(1, 7)
    evs = s.evict_finished()
    assert [(e.slot, e.rid, e.reason) for e in evs] == [
        (0, "r0", "budget"), (1, "r1", "budget")]
    # evicted slots recycle immediately, FCFS order preserved
    assert [(a.slot, a.req.rid) for a in s.admit()] == [
        (0, "r2"), (1, "r3")]
    assert s.queue_depth == 1


def test_eviction_reasons_and_stop_conditions():
    s = SlotScheduler(2)
    s.enqueue(_req("budget", mnt=2))
    s.enqueue(_req("eos", mnt=10, eos=9))
    s.admit()
    s.record(0, 5)
    s.record(1, 5)
    assert s.evict_finished() == []
    s.record(0, 6)
    s.record(1, 9)  # the eos token
    evs = {e.rid: e for e in s.evict_finished()}
    assert evs["budget"].reason == "budget"
    assert evs["budget"].tokens == (5, 6)
    assert evs["eos"].reason == "eos"
    assert evs["eos"].tokens == (5, 9)
    # recording past a stop condition is a contract violation
    s.enqueue(_req("x", mnt=1))
    s.admit()
    s.record(0, 1)
    with pytest.raises(ValueError, match="finished"):
        s.record(0, 2)
    with pytest.raises(KeyError):
        s.record(1, 2)  # freed slot has no active request


def test_resume_replay_counts_toward_budget():
    s = SlotScheduler(1)
    s.enqueue(_req("r", mnt=3), resume=(4, 5))
    (adm,) = s.admit()
    assert adm.resume == (4, 5)
    s.record(0, 6)  # one more token exhausts the budget
    (ev,) = s.evict_finished()
    assert ev.tokens == (4, 5, 6) and ev.reason == "budget"


def test_identical_schedule_across_simulated_ranks():
    """The HVD001 invariant: N scheduler instances fed the same inputs
    in the same order make identical decisions, step for step."""
    rng = np.random.RandomState(0)
    ranks = [SlotScheduler(2) for _ in range(3)]
    logs = [[] for _ in ranks]
    rid = 0
    for step in range(1, 40):
        arrivals = [
            _req(f"r{rid + i}", n=int(rng.randint(1, 4)),
                 mnt=int(rng.randint(1, 5)))
            for i in range(rng.randint(0, 3))
        ]
        rid += len(arrivals)
        token = int(rng.randint(0, 50))
        for sched, log in zip(ranks, logs):
            for req in arrivals:
                sched.enqueue(req)
            admits = sched.admit(step)
            for a in admits:
                sched.record(a.slot, token)
            for slot in sorted(sched.active):
                if not sched.active[slot].done:
                    sched.record(slot, token)
            evs = sched.evict_finished()
            log.append((
                step,
                tuple((a.slot, a.req.rid) for a in admits),
                tuple((e.slot, e.rid, e.reason, e.tokens) for e in evs),
                sched.queue_depth, sched.active_slots,
            ))
    assert logs[0] == logs[1] == logs[2]


def test_snapshot_lists_active_then_queued():
    s = SlotScheduler(1)
    s.enqueue(_req("a", mnt=5))
    s.enqueue(_req("b", mnt=5))
    s.admit()
    s.record(0, 3)
    snap = s.snapshot()
    assert [d["rid"] for d in snap] == ["a", "b"]
    assert snap[0]["emitted"] == [3] and snap[1]["emitted"] == []


def test_request_and_scheduler_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid="x", prompt=())
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(rid="x", prompt=(1,), max_new_tokens=0)
    with pytest.raises(ValueError, match="num_slots"):
        SlotScheduler(0)


def test_validate_request_decision_table():
    ok = {"prompt": [1, 2], "max_new_tokens": 4}
    assert validate_request(ok, serve_len=16) is None
    assert validate_request(ok, serve_len=16, vocab_size=64) is None
    # Every verdict is a str (the human message) AND carries the
    # machine-readable code ServeClient.result surfaces (ISSUE 16);
    # tests/test_frontdoor.py has the full code table.
    v = validate_request({"prompt": [], "max_new_tokens": 4}, 16)
    assert "prompt" in v and v.code == "bad_prompt"
    v = validate_request({"prompt": [1, -2], "max_new_tokens": 4}, 16)
    assert "ints" in v and v.code == "bad_token"
    v = validate_request(
        {"prompt": [1, 64], "max_new_tokens": 4}, 16, vocab_size=64)
    assert "vocab" in v and v.code == "oob_token"
    v = validate_request({"prompt": [1], "max_new_tokens": 0}, 16)
    assert "max_new_tokens" in v and v.code == "bad_budget"
    v = validate_request(
        {"prompt": [1] * 10, "max_new_tokens": 8}, 16)
    assert "exceeds" in v and v.code == "ctx_exceeded"


def test_engine_serve_len_caps_oversized_cache():
    """An oversized slot cache must not let a valid-looking request's
    power-of-two prefill bucket exceed the model's max_len (review
    finding: that ValueError would crash-loop the fleet on replay)."""
    model = _model()  # cfg.max_len = 64
    params = model.init(jax.random.PRNGKey(20),
                        jnp.zeros((1, 8), jnp.int32))
    eng = SlotEngine(model.cfg, params, num_slots=1, max_len=128)
    assert eng.cache_len == 128 and eng.serve_len == 64
    # a 40-token prompt would bucket to 64 (<= max_len): admissible
    reason = validate_request(
        {"prompt": [1] * 40, "max_new_tokens": 8}, eng.serve_len)
    assert reason is None
    assert eng.admit(0, [1] * 40) is not None
    # 70 tokens fits the raw cache but not the serving context
    assert "exceeds" in validate_request(
        {"prompt": [1] * 70, "max_new_tokens": 8}, eng.serve_len)


def test_prompt_bucket():
    assert prompt_bucket(3, 64) == 8
    assert prompt_bucket(8, 64) == 8
    assert prompt_bucket(9, 64) == 16
    assert prompt_bucket(40, 48) == 48  # clamped to the cache
    with pytest.raises(ValueError, match="exceeds"):
        prompt_bucket(65, 64)


# ---------------------------------------------------------------------------
# Slot engine vs the single-stream oracle (no launcher)
# ---------------------------------------------------------------------------


def test_engine_continuous_batch_matches_generate():
    """The acceptance core, distilled: requests admitted at different
    steps into a shared pool — including mid-decode admissions — each
    produce exactly the tokens single-stream ``generate`` produces."""
    model = _model(pos_embedding="rope")
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    engine = SlotEngine(cfg, params, num_slots=2)
    sched = SlotScheduler(2)
    rng = np.random.RandomState(5)
    reqs = {}
    for i in range(5):
        prompt = tuple(int(t) for t in rng.randint(0, 64,
                                                   rng.randint(3, 9)))
        reqs[f"r{i}"] = Request(rid=f"r{i}", prompt=prompt,
                                max_new_tokens=int(rng.randint(2, 6)))
    oracle = {
        rid: np.asarray(generate(
            cfg, params, jnp.asarray([req.prompt], jnp.int32),
            req.max_new_tokens,
        ))[0].tolist()
        for rid, req in reqs.items()
    }
    # stagger arrivals: two up front, the rest dripped in mid-decode
    pending = list(reqs.values())
    finished = {}
    mid_decode_admission = False
    for step in range(1, 60):
        if pending and (step == 1 or step % 3 == 0):
            sched.enqueue(pending.pop(0))
        admits = sched.admit(step)
        for adm in admits:
            if sched.active_slots > len(admits):
                mid_decode_admission = True
            tok = engine.admit(adm.slot, adm.req.prompt, adm.resume)
            sched.record(adm.slot, tok)
        for ev in sched.evict_finished():
            finished[ev.rid] = list(ev.tokens)
        active = sorted(sched.active)
        if active:
            toks = engine.step(active)
            for slot in active:
                sched.record(slot, toks[slot])
        for ev in sched.evict_finished():
            finished[ev.rid] = list(ev.tokens)
        if len(finished) == len(reqs):
            break
    assert finished == oracle
    assert mid_decode_admission, "no admission ever overlapped a decode"


def test_engine_replay_resumes_mid_stream():
    """Elastic-replay primitive: rebuilding a slot from prompt + the
    tokens already streamed continues the generation bit-exactly."""
    model = _model()
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))
    prompt = tuple(int(t) for t in
                   np.random.RandomState(2).randint(0, 64, 6))
    want = np.asarray(generate(
        cfg, params, jnp.asarray([prompt], jnp.int32), 6))[0].tolist()

    fresh = SlotEngine(cfg, params, num_slots=1)
    replay = SlotEngine(cfg, params, num_slots=1)
    # fresh run, interrupted after 3 tokens
    toks = [fresh.admit(0, prompt)]
    for _ in range(2):
        toks.append(fresh.step([0])[0])
    assert toks == want[:3]
    # replayed engine: admit with the emitted prefix, then continue
    assert replay.admit(0, prompt, resume=tuple(toks)) is None
    for _ in range(3):
        toks.append(replay.step([0])[0])
    assert toks == want


# ---------------------------------------------------------------------------
# Long-context: sequence-sharded attention over the 8-device CPU mesh
# ---------------------------------------------------------------------------


def test_sharded_decode_attention_matches_replicated():
    from horovod_tpu.models.decode import _attend_cached
    from horovod_tpu.serve.longctx import sharded_decode_attention

    model = _model(num_kv_heads=2, num_heads=4, emb_dim=64)
    cfg = model.cfg
    rng = np.random.RandomState(3)
    b, s, h, hd = 3, 32, cfg.num_heads, cfg.head_dim
    q = jnp.asarray(rng.randn(b, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, cfg.kv_heads, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, cfg.kv_heads, hd), jnp.float32)
    # per-slot positions, including a fresh slot (0) and a full one
    pos = jnp.asarray([5, 0, s - 1], jnp.int32)
    want = _attend_cached(cfg, q, k, v, pos)

    mesh = Mesh(np.asarray(jax.devices()[:4]), (AXIS,))
    fn = jax.jit(
        shard_map(
            lambda q, k, v, pos: sharded_decode_attention(
                cfg, q, k, v, pos, AXIS),
            mesh=mesh,
            in_specs=(P(), P(None, AXIS), P(None, AXIS), P()),
            out_specs=P(),
        )
    )
    got = fn(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # Deeply negative scores: every real max sits far below the 0.0 a
    # fully-masked chunk's clamped max would contribute — the merge
    # must rescale against the true contributing max, not underflow
    # every exp to zero (review finding on the pmax mask).
    q_neg = q - 40.0
    k_neg = k + 40.0
    want_neg = _attend_cached(cfg, q_neg, k_neg, v, pos)
    got_neg = fn(q_neg, k_neg, v, pos)
    assert np.abs(np.asarray(got_neg)).max() > 0.0
    # These scores are near -2.6e4, where float32 numbers lie 2e-3
    # apart, so the two schedules' exponentials differ in their fourth
    # digit: observed 1.54e-5 absolute on outputs up to 3.3.  The limit
    # is ten times that; an underflowed merge would be off by the
    # outputs' own size.
    np.testing.assert_allclose(np.asarray(got_neg),
                               np.asarray(want_neg),
                               atol=2e-4, rtol=0)


def test_ulysses_prefill_attention_matches_local():
    from horovod_tpu.parallel.ring_attention import local_attention
    from horovod_tpu.serve.longctx import ulysses_prefill_attention

    rng = np.random.RandomState(4)
    b, s, h, hd = 2, 32, 4, 8
    q = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    want = local_attention(q, k, v, causal=True)

    mesh = Mesh(np.asarray(jax.devices()[:4]), (AXIS,))
    got = jax.jit(
        shard_map(
            lambda q, k, v: ulysses_prefill_attention(q, k, v, AXIS),
            mesh=mesh,
            in_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS)),
            out_specs=P(None, AXIS),
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end: the elastic serving fleet (real processes)
# ---------------------------------------------------------------------------

_OVERRIDES = dict(num_layers=1, num_heads=2, emb_dim=32, max_len=64,
                  vocab_size=64, dtype="float32",
                  attention_impl="reference")


def _spec(slots=2):
    o = dict(_OVERRIDES)
    o["dtype"] = jnp.float32
    return {"size": "nano", "overrides": o, "seed": 3,
            "num_slots": slots, "idle_secs": 0.005}


def _oracle(prompts, steps):
    o = dict(_OVERRIDES)
    o["dtype"] = jnp.float32
    model = gpt("nano", **o)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))
    return [
        np.asarray(generate(model.cfg, params,
                            jnp.asarray([p], jnp.int32), s))[0].tolist()
        for p, s in zip(prompts, steps)
    ]


@pytest.mark.multiprocess
@pytest.mark.slow
def test_serve_job_staggered_requests_and_rejection():
    """Single-rank fleet: staggered mixed-length requests all complete
    with oracle tokens through slot churn; an oversized request is
    rejected with a reason instead of wedging the loop."""
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 64, rs.randint(3, 9)).tolist()
               for _ in range(5)]
    steps = [3, 5, 2, 4, 3]
    oracle = _oracle(prompts, steps)
    job = ServeJob(_spec(), np=1, env={"JAX_PLATFORMS": "cpu"},
                   timeout=240).start()
    try:
        rids = []
        for p, s in zip(prompts, steps):
            rids.append(job.client.submit(p, max_new_tokens=s))
            time.sleep(0.03)
        bad = job.client.submit([1] * 60, max_new_tokens=30)
        docs = [job.client.result(r, timeout=180) for r in rids]
        with pytest.raises(RuntimeError, match="exceeds"):
            job.client.result(bad, timeout=180)
        results, ejob = job.stop()
    finally:
        job.shutdown()
    assert [d["tokens"] for d in docs] == oracle
    # slot exhaustion forced at least one post-start admission
    assert max(d["admitted_step"] for d in docs) > 1
    assert results[0]["completed"] == 5
    assert [e[0] for e in ejob.trace] == ["spawn"]


@pytest.mark.multiprocess
@pytest.mark.slow
def test_serve_chaos_kill_leader_respawn_zero_dropped():
    """ISSUE 10 acceptance: 2-proc fleet, 8 staggered mixed-length
    requests, the LEADER (rank 0 — the only rank that reads the ingest
    log and writes result streams) killed mid-stream at its own step 6,
    which is deterministically mid-stream (8 requests x >=3 tokens
    through 2 slots need far more than 6 busy steps).  The launcher
    respawns it into a fresh epoch, the scheduler replays every
    in-flight request from the durable rank-0 queue, and every request
    completes with tokens identical to single-stream ``generate`` —
    zero dropped."""
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, 64, rs.randint(3, 9)).tolist()
               for _ in range(8)]
    steps = [3, 4, 5, 6, 3, 4, 5, 6]
    oracle = _oracle(prompts, steps)
    job = ServeJob(
        _spec(), np=2,
        env={"JAX_PLATFORMS": "cpu",
             "HVDTPU_FAULT_SPEC": "worker_exit:step=6:rank=0"},
        max_retries=2, timeout=300,
    ).start()
    try:
        rids = []
        for p, s in zip(prompts, steps):
            rids.append(job.client.submit(p, max_new_tokens=s))
            time.sleep(0.05)
        docs = [job.client.result(r, timeout=240) for r in rids]
        results, ejob = job.stop()
    finally:
        job.shutdown()
    assert [d["tokens"] for d in docs] == oracle
    events = [e[0] for e in ejob.trace]
    assert events.count("failure") == 1 and events.count("respawn") == 1
    # some request finished in the post-recovery epoch (the kill was
    # mid-stream), and the recovery replayed rather than restarted:
    # requests finished before the break keep their epoch-0 stamp
    assert max(d["epoch"] for d in docs) >= 1
    # both ranks drained cleanly and returned summaries
    assert sorted(results) == [0, 1]
    assert all(v["completed"] >= 1 for v in results.values())


@pytest.mark.multiprocess
@pytest.mark.slow
def test_serve_width_fleet_partition_chaos_and_sampling():
    """ISSUE 15 acceptance: a width-sharded fleet (np=2, width=1 -> 2
    independent serving groups over the log partition n % 2) serves 8
    mixed requests — two of them SAMPLED (temperature/top-k) — with a
    mid-stream kill of rank 1 (group 1's leader).  Every stream must
    equal the single-engine oracle bit-for-bit: greedy via
    ``generate``, sampled via the shared (rid, emission index, seed)
    key derivation — the fleet shape, the chaos replay with paged
    block tables, and the sampler must all be invisible in the tokens.
    Both groups must have actually served (the partition is capacity,
    not standby)."""
    from horovod_tpu.serve.engine import SlotEngine as _Eng

    spec = _spec()
    spec.update({"width": 1, "kv_mode": "paged", "page_size": 8,
                 "kv_pages": 16})
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 64, rs.randint(3, 9)).tolist()
               for _ in range(8)]
    steps = [3, 4, 5, 6, 3, 4, 5, 6]
    temps = [0.0, 0.0, 0.9, 0.0, 0.0, 0.8, 0.0, 0.0]
    rids = [f"flt{i}" for i in range(8)]

    o = dict(_OVERRIDES)
    o["dtype"] = jnp.float32
    model = gpt("nano", **o)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))

    def oracle_one(prompt, n, temp, rid):
        eng = _Eng(model.cfg, params, 1, kv_mode="paged", page_size=8,
                   sample_seed=spec["seed"])
        toks = [eng.admit(0, prompt, temperature=temp, top_k=8,
                          rid=rid, total_len=len(prompt) + n)]
        for _ in range(n - 1):
            toks.append(eng.step([0])[0])
        return toks

    oracle = [oracle_one(p, n, t, r)
              for p, n, t, r in zip(prompts, steps, temps, rids)]

    job = ServeJob(
        spec, np=2,
        env={"JAX_PLATFORMS": "cpu",
             "HVDTPU_FAULT_SPEC": "worker_exit:step=6:rank=1"},
        max_retries=2, timeout=300,
    ).start()
    try:
        for p, n, t, r in zip(prompts, steps, temps, rids):
            job.client.submit(p, max_new_tokens=n, temperature=t,
                              top_k=8, rid=r)
            time.sleep(0.05)
        docs = [job.client.result(r, timeout=240) for r in rids]
        results, ejob = job.stop()
    finally:
        job.shutdown()
    assert [d["tokens"] for d in docs] == oracle
    events = [e[0] for e in ejob.trace]
    assert events.count("failure") == 1 and events.count("respawn") == 1
    # the partition is real capacity: each rank completed ITS group
    assert sorted(results) == [0, 1]
    assert all(v["completed"] >= 1 for v in results.values())
    assert {v.get("group") for v in results.values()} == {0, 1}
    # completed is per-incarnation: requests group 1 finished BEFORE
    # the kill died with that incarnation's summary (their done docs
    # survive, which is why the streams above are 8/8) — so the sum is
    # >= 8 minus what pre-kill group 1 finished, never more than 8.
    total_done = sum(v["completed"] for v in results.values())
    assert 4 <= total_done <= 8
