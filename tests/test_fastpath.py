"""Steady-state fast path: schedule replay + online autotuner.

In-process tests drive a real EagerEngine through hand-cranked cycles
with a faked 2-rank exchange/data plane (the test_autotune.py
TestParamSync pattern): replay entry after K stable cycles, the
epoch-check flag lane, and a break-and-renegotiate case for every
deviation class (miss / conflict / shutdown / join / tuner move / peer
flag / stall).  The 2-proc chaos case (`action=delay` mid-replay must
break the epoch on every rank, not hang) goes through the REAL launcher
and the existing fault registry.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np
import pytest

import horovod_tpu.run as hvdrun
from horovod_tpu.runtime import response_cache as rcache
from horovod_tpu.runtime.autotune import (
    STATE_CONVERGED,
    STATE_RETUNING,
    ParameterManager,
    TunedParams,
)
from horovod_tpu.runtime.engine import EagerEngine, _replay_plan_ok
from horovod_tpu.runtime.messages import (
    Request,
    RequestList,
    RequestType,
    Response,
    ResponseType,
)
from horovod_tpu.ops.collectives import ReduceOp


# --------------------------------------------------------------- harness


def _mk_engine(monkeypatch, replay_after=3):
    """A real engine believing in a 2-rank world, with the coordination
    service faked: the peer mirrors our requests and echoes our armed
    bits, and the data plane stacks our buffer twice (an equal-
    contributing peer).  No thread — cycles are cranked by hand."""
    import horovod_tpu as hvd

    hvd.init()
    eng = EagerEngine()  # world=1 topology; promote it to a fake pair
    eng.world = 2
    eng._controller.world_size = 2
    eng.replay_enabled = True
    eng.replay_after = replay_after
    calls = {"exchange": 0}

    def _ex(payload, shutdown, joined):
        calls["exchange"] += 1
        bits = np.zeros((2, eng._cache.num_bits), np.uint8)
        for slot in eng._armed:
            bits[:, slot >> 3] |= np.uint8(1 << (slot & 7))
        sd = {0} if shutdown else set()
        jn = {0, 1} if joined else set()
        if payload:
            rl = RequestList.deserialize(payload)
            peer = RequestList(
                requests=[
                    dataclasses.replace(r, request_rank=1)
                    for r in rl.requests
                ],
                tuned_params=rl.tuned_params,
            )
            return sd, jn, bits, [rl, peer]
        return sd, jn, bits, None

    def _gather(local):
        local = np.ascontiguousarray(local)
        return np.stack([local, local])

    monkeypatch.setattr(eng, "_exchange", _ex)
    monkeypatch.setattr(eng, "_data_allgather", _gather)
    return eng, calls


def _submit(eng, name="g", shape=(4,), value=1.0):
    return eng.enqueue(
        RequestType.ALLREDUCE,
        name,
        np.full(shape, value, np.float32),
        reduce_op=int(ReduceOp.SUM),
    )


def _spin_into_replay(eng, calls):
    """Negotiate once, then repeat identical cycles until the engine
    opens a replay epoch.  Returns the number of cycles it took."""
    n = 0
    while not eng._replaying:
        n += 1
        assert n < 50, "engine never entered replay"
        fut = _submit(eng)
        eng._run_loop_once()
        np.testing.assert_allclose(fut.result(timeout=5), np.full(4, 2.0))
    return n


# ------------------------------------------------------- replay mechanics


class TestReplayEntry:
    def test_enters_after_k_stable_cycles(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        n = _spin_into_replay(eng, calls)
        # 1 payload cycle + replay_after stable cycles
        assert n == 1 + eng.replay_after
        assert eng.stats["replay_epochs"] == 1
        assert eng.stats["negotiated_cycles"] == n

    def test_replay_cycles_skip_exchange_and_deliver(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        frozen = calls["exchange"]
        for _ in range(10):
            fut = _submit(eng)
            eng._run_loop_once()
            np.testing.assert_allclose(
                fut.result(timeout=5), np.full(4, 2.0)
            )
        assert calls["exchange"] == frozen  # zero control-plane exchange
        assert eng.stats["replay_cycles"] == 10
        assert eng._replaying

    def test_idle_cycles_do_not_break(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        for _ in range(3):
            eng._run_loop_once()  # nothing enqueued: idle, stay in epoch
        assert eng._replaying
        assert eng.stats["replay_idle_cycles"] == 3
        fut = _submit(eng)
        eng._run_loop_once()
        np.testing.assert_allclose(fut.result(timeout=5), np.full(4, 2.0))

    def test_skip_rate_gauge_published(self, monkeypatch):
        from horovod_tpu.obs import get_registry

        eng, calls = _mk_engine(monkeypatch, replay_after=2)
        _spin_into_replay(eng, calls)
        for _ in range(7):
            fut = _submit(eng)
            eng._run_loop_once()
            fut.result(timeout=5)
        get_registry().snapshot()
        skip = get_registry().gauge("engine.negotiation_skip_rate").value
        assert skip == pytest.approx(
            1 - eng.stats["negotiated_cycles"] / eng.stats["cycles"]
        )
        assert skip > 0.5

    def test_disabled_by_env_flag(self, monkeypatch):
        monkeypatch.setenv("HVDTPU_SCHEDULE_REPLAY", "0")
        eng, calls = _mk_engine(monkeypatch, replay_after=2)
        eng.replay_enabled = False  # what __init__ reads from the env
        for _ in range(8):
            fut = _submit(eng)
            eng._run_loop_once()
            fut.result(timeout=5)
        assert not eng._replaying
        assert eng.stats["replay_epochs"] == 0


class TestReplayPlanQualification:
    def _resp(self, reduce_op=int(ReduceOp.SUM), dtype="float32",
              pre=1.0, post=1.0, rtype=ResponseType.ALLREDUCE):
        r = Response(rtype, ["t"])
        r._fuse_meta = (dtype, reduce_op, pre, post)
        r._shapes = [(4,)]
        return r

    def test_sum_and_average_qualify(self):
        assert _replay_plan_ok([self._resp(int(ReduceOp.SUM))], 2)
        assert _replay_plan_ok([self._resp(int(ReduceOp.AVERAGE))], 2)

    def test_disqualifiers(self):
        assert not _replay_plan_ok([], 2)
        assert not _replay_plan_ok([self._resp(int(ReduceOp.MIN))], 2)
        assert not _replay_plan_ok([self._resp(int(ReduceOp.MAX))], 2)
        assert not _replay_plan_ok([self._resp(int(ReduceOp.ADASUM))], 2)
        assert not _replay_plan_ok([self._resp(pre=0.0)], 2)
        assert not _replay_plan_ok([self._resp(post=0.0)], 2)
        assert not _replay_plan_ok(
            [self._resp(int(ReduceOp.AVERAGE), dtype="int32")], 2
        )
        assert not _replay_plan_ok([self._resp(dtype="bool")], 2)
        assert not _replay_plan_ok(
            [self._resp(rtype=ResponseType.BROADCAST)], 2
        )
        # int SUM is exact and keeps a lone flag nonzero: qualifies
        assert _replay_plan_ok([self._resp(int(ReduceOp.SUM), "int32")], 2)

    def test_float16_flag_underflow_guard(self):
        # fp16 + tiny loss-scale prescale: flag would flush to zero
        assert not _replay_plan_ok(
            [self._resp(dtype="float16", pre=1e-7)], 2
        )
        # AVERAGE divides by the world on top of pre/post
        assert _replay_plan_ok(
            [self._resp(int(ReduceOp.AVERAGE), "float16", pre=1e-3)], 2
        )
        assert not _replay_plan_ok(
            [self._resp(int(ReduceOp.AVERAGE), "float16", pre=1e-3)], 4096
        )
        # bf16 has f32-sized exponents: unaffected by the guard
        assert _replay_plan_ok(
            [self._resp(int(ReduceOp.AVERAGE), "bfloat16", pre=1e-7)], 4096
        )


# ------------------------------------------------------- deviation classes


class TestReplayBreaks:
    def _break_reason_counter(self, reason):
        from horovod_tpu.obs import get_registry

        return get_registry().counter("engine.replay_break", reason=reason)

    def test_new_tensor_breaks_and_renegotiates(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        before = self._break_reason_counter("miss").value
        fut = _submit(eng, name="brand_new")
        eng._run_loop_once()  # replay cycle sees the MISS: break
        assert not eng._replaying
        assert eng.stats["replay_breaks"] == 1
        assert self._break_reason_counter("miss").value == before + 1
        eng._run_loop_once()  # negotiated cycle completes the new tensor
        np.testing.assert_allclose(fut.result(timeout=5), np.full(4, 2.0))

    def test_conflict_breaks_and_renegotiates(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        before = self._break_reason_counter("conflict").value
        fut = _submit(eng, name="g", shape=(8,))  # same name, new shape
        eng._run_loop_once()
        assert not eng._replaying
        assert self._break_reason_counter("conflict").value == before + 1
        for _ in range(3):
            if fut.done():
                break
            eng._run_loop_once()
        np.testing.assert_allclose(fut.result(timeout=5), np.full(8, 2.0))

    def test_shutdown_breaks_then_propagates(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        with eng._lock:
            eng._shutdown_requested = True
        assert eng._run_loop_once() is True  # break cycle
        assert not eng._replaying
        assert eng._run_loop_once() is False  # negotiated cycle exits

    def test_join_breaks(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        fut = eng.join()
        eng._run_loop_once()
        assert not eng._replaying
        eng._run_loop_once()  # negotiated: both fake ranks joined -> JOIN
        assert fut.result(timeout=5) == 1

    def test_tuner_move_breaks_and_applies_params(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        tuned = TunedParams(8 * 1048576, 0.002)
        with eng._lock:
            eng._pending_params = tuned.as_wire()
        eng._run_loop_once()  # break: tuner-move
        assert not eng._replaying
        eng._run_loop_once()  # negotiated: params ride rank 0's list
        assert eng.fusion_bytes == tuned.fusion_bytes
        assert eng.cycle_s == pytest.approx(tuned.cycle_s)

    def test_peer_flag_discards_cycle_and_requeues(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)

        def _gather_peer_flag(local):
            local = np.ascontiguousarray(local)
            peer = local.copy()
            peer[-1] = 1.0  # the peer's epoch-check lane says BREAK
            return np.stack([local, peer])

        monkeypatch.setattr(eng, "_data_allgather", _gather_peer_flag)
        fut = _submit(eng)
        eng._run_loop_once()
        # the cycle's data was discarded: future still pending, no
        # garbage delivered, epoch closed on this rank too
        assert not fut.done()
        assert not eng._replaying

        def _gather(local):
            local = np.ascontiguousarray(local)
            return np.stack([local, local])

        monkeypatch.setattr(eng, "_data_allgather", _gather)
        eng._run_loop_once()  # renegotiation completes the requeued op
        np.testing.assert_allclose(fut.result(timeout=5), np.full(4, 2.0))

    def test_local_stall_breaks_epoch(self, monkeypatch):
        eng, calls = _mk_engine(monkeypatch, replay_after=3)
        _spin_into_replay(eng, calls)
        eng.stall_warn = 0.02
        before = self._break_reason_counter("stall").value
        eng._run_loop_once()  # idle: starts the stall clock
        assert eng._replaying
        time.sleep(0.05)
        eng._run_loop_once()  # idle past stall_warn: flagged break
        assert not eng._replaying
        assert self._break_reason_counter("stall").value == before + 1


# ------------------------------------------------- cache schedule fingerprint


class TestScheduleKey:
    def _req(self, name, shape=(4,)):
        return Request(0, RequestType.ALLREDUCE, name, "float32", shape)

    def _resp(self, name):
        r = Response(ResponseType.ALLREDUCE, [name])
        r._fuse_meta = ("float32", int(ReduceOp.SUM), 1.0, 1.0)
        return r

    def test_key_stable_without_mutation(self):
        c = rcache.ResponseCache(16)
        c.insert(self._req("a"), self._resp("a"))
        assert c.schedule_key([0]) == c.schedule_key([0])

    def test_insert_and_evict_change_key(self):
        c = rcache.ResponseCache(16)
        c.insert(self._req("a"), self._resp("a"))
        k1 = c.schedule_key([0])
        c.insert(self._req("b"), self._resp("b"))
        k2 = c.schedule_key([0])
        assert k1 != k2
        c.evict_name("b")
        assert c.schedule_key([0]) != k2

    def test_conflict_reinsert_same_slot_changes_key(self):
        c = rcache.ResponseCache(16)
        c.insert(self._req("a"), self._resp("a"))
        k1 = c.schedule_key([0])
        c.evict_name("a")
        c.insert(self._req("a", shape=(8,)), self._resp("a"))
        assert c.schedule_key([0]) != k1


# --------------------------------------------------------- online autotuner


class TestDriftDetector:
    def _pm(self, **kw):
        kw.setdefault("enabled", True)
        kw.setdefault("initial", TunedParams(4 * 1048576, 0.005))
        kw.setdefault("warmup_samples", 0)
        kw.setdefault("steps_per_sample", 1)
        kw.setdefault("samples_per_category", 4)
        kw.setdefault(
            "categories",
            [{"cache_enabled": True, "hierarchical_allreduce": False}],
        )
        kw.setdefault("drift_threshold", 0.3)
        kw.setdefault("drift_samples", 2)
        return ParameterManager(**kw)

    def _sample(self, pm, score):
        pm._bytes = int(score)
        pm._sample_start -= 1.0  # pretend 1 s elapsed
        return pm.cycle()

    def _converge(self, pm, score=100.0):
        for _ in range(200):
            self._sample(pm, score)
            if pm.converged:
                return
        raise AssertionError("tuner never converged")

    def test_holds_incumbent_while_stable(self):
        pm = self._pm()
        self._converge(pm)
        incumbent = pm.current
        for _ in range(10):
            assert self._sample(pm, 100.0) is None
        assert pm.current == incumbent
        assert pm.state == STATE_CONVERGED
        assert pm.reopens == 0

    def test_jitter_does_not_reopen(self):
        pm = self._pm()
        self._converge(pm)
        for score in (95.0, 104.0, 92.0, 101.0, 97.0):
            assert self._sample(pm, score) is None
        assert pm.reopens == 0

    def _drift_until_reopen(self, pm, score, max_windows=15):
        """Feed regressed windows until the smoothed signal crosses the
        drift threshold (the EWMA needs a few windows to decay)."""
        for _ in range(max_windows):
            moved = self._sample(pm, score)
            if moved is not None:
                return moved
        raise AssertionError("drift detector never re-opened")

    def test_sustained_regression_reopens_and_reconverges(self):
        pm = self._pm()
        self._converge(pm)
        moved = self._drift_until_reopen(pm, 20.0)
        assert moved is not None
        assert pm.state == STATE_RETUNING
        assert pm.reopens == 1
        self._converge(pm, score=50.0)  # new regime: settles again
        assert pm.state == STATE_CONVERGED

    def test_one_noisy_search_peak_does_not_thrash(self):
        """A single search window scoring moderately above steady state
        must not convict the incumbent once real hold windows arrive:
        the search max only seeds the EWMA, its weight decays 0.7^k."""
        pm = self._pm()
        spiked = {"done": False}
        for _ in range(200):
            score = 100.0
            if not spiked["done"]:
                score, spiked["done"] = 115.0, True  # one +15% window
            self._sample(pm, score)
            if pm.converged:
                break
        assert pm.converged
        for _ in range(30):
            assert self._sample(pm, 100.0) is None
        assert pm.reopens == 0

    def test_idle_windows_are_not_drift(self):
        """A training pause (zero-traffic windows) spanning more than
        drift_samples windows must NOT convict the incumbent."""
        pm = self._pm()
        self._converge(pm)
        for _ in range(10):  # eval/checkpoint pause: no bytes move
            assert self._sample(pm, 0.0) is None
        assert pm.reopens == 0
        assert pm.state == STATE_CONVERGED
        self._sample(pm, 100.0)  # traffic resumes, still held
        assert pm.reopens == 0

    def test_reopen_keeps_incumbent_category(self):
        """A drift reopen must retune in the INCUMBENT's categorical
        config, not whatever category the chain swept last."""
        pm = self._pm(categories=[
            {"cache_enabled": True, "hierarchical_allreduce": False},
            {"cache_enabled": False, "hierarchical_allreduce": False},
        ])
        # cache-on windows score high, cache-off low -> incumbent is
        # cache-on even though cache-off is swept last
        for _ in range(200):
            self._sample(pm, 100.0 if pm.current.cache_enabled else 10.0)
            if pm.converged:
                break
        assert pm.converged and pm.current.cache_enabled
        moved = self._drift_until_reopen(pm, 20.0)
        assert moved is not None and pm.state == STATE_RETUNING
        assert moved.cache_enabled  # probe rides the incumbent's config
        for _ in range(10):
            p = self._sample(pm, 50.0)
            if p is not None:
                assert p.cache_enabled

    def test_single_spike_resets_drift_count(self):
        pm = self._pm()
        self._converge(pm)
        self._sample(pm, 20.0)
        self._sample(pm, 100.0)  # recovery resets the counter
        self._sample(pm, 20.0)
        assert pm.reopens == 0

    def test_state_gauges_published(self):
        from horovod_tpu.obs import get_registry

        pm = self._pm()
        self._converge(pm)
        reg = get_registry()
        assert reg.gauge("autotune.state").value == STATE_CONVERGED
        assert reg.gauge("autotune.best_score").value > 0
        assert reg.gauge("autotune.fusion_mb").value == pytest.approx(
            pm.current.fusion_bytes / 1048576
        )


class TestBusyTimeScoring:
    def test_scores_on_busy_time_not_wall_clock(self):
        """An input-bound phase (huge wall-clock gap, tiny busy time)
        must not depress the score: the objective reads cumulative
        (bytes, busy_seconds) from the metrics source."""
        feed = {"bytes": 0.0, "busy": 0.0}
        pm = ParameterManager(
            enabled=True,
            initial=TunedParams(4 * 1048576, 0.005),
            warmup_samples=0,
            steps_per_sample=1,
            metrics_source=lambda: (feed["bytes"], feed["busy"]),
        )
        feed["bytes"] = 1000.0
        feed["busy"] = 0.5
        pm._sample_start -= 100.0  # 100 s of host idle on the wall clock
        pm.cycle()
        assert pm._last_score == pytest.approx(2000.0)  # 1000 B / 0.5 s

    def test_source_deltas_are_per_window(self):
        feed = {"bytes": 0.0, "busy": 0.0}
        pm = ParameterManager(
            enabled=True,
            initial=TunedParams(4 * 1048576, 0.005),
            warmup_samples=0,
            steps_per_sample=1,
            metrics_source=lambda: (feed["bytes"], feed["busy"]),
        )
        feed["bytes"], feed["busy"] = 1000.0, 1.0
        pm.cycle()
        feed["bytes"], feed["busy"] = 1500.0, 2.0
        pm.cycle()
        assert pm._last_score == pytest.approx(500.0)  # 500 B / 1 s


class TestAutotuneLog:
    def test_append_and_single_header_across_respawn(self, tmp_path):
        log = tmp_path / "autotune.csv"
        for _ in range(2):  # second construction = elastic respawn
            pm = ParameterManager(
                enabled=True,
                initial=TunedParams(1048576, 0.005),
                log_path=str(log),
                warmup_samples=0,
                steps_per_sample=1,
            )
            pm.record_bytes(1000)
            pm._sample_start -= 1.0
            pm.cycle()
        lines = log.read_text().strip().splitlines()
        assert lines[0].startswith("sample,score_bytes_per_sec")
        assert sum(
            1 for l in lines if l.startswith("sample,")
        ) == 1  # header never repeated
        assert len(lines) == 3  # header + one row per incarnation

    def test_epoch_tagged_under_elastic(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HVDTPU_ELASTIC_EPOCH", "2")
        log = tmp_path / "autotune.csv"
        pm = ParameterManager(
            enabled=True,
            initial=TunedParams(1048576, 0.005),
            log_path=str(log),
            warmup_samples=0,
            steps_per_sample=1,
        )
        pm.record_bytes(1000)
        pm._sample_start -= 1.0
        pm.cycle()
        assert not log.exists()  # the predecessor's file is untouched
        tagged = tmp_path / "autotune.e2.csv"
        assert tagged.exists()
        assert len(tagged.read_text().strip().splitlines()) == 2


# ------------------------------------------------------- 2-proc integration


def _replay_worker():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import _engine_registry

    hvd.init()
    for i in range(40):
        out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="grad")
        assert float(out[0]) == 2.0, float(out[0])
    eng = _engine_registry.get_engine()
    stats = dict(eng.stats)
    hvd.shutdown()
    return stats


@pytest.mark.multiprocess
def test_two_proc_replay_skips_negotiation():
    env = {
        "HVDTPU_EAGER_ENGINE": "python",
        "HVDTPU_EAGER_DEVICE": "0",  # raw-gather data plane (CI-stable)
        "HVDTPU_SCHEDULE_REPLAY_CYCLES": "5",
        "HVDTPU_CYCLE_TIME": "2",
    }
    results = hvdrun.run(_replay_worker, np=2, use_cpu=True, timeout=180,
                         env=env)
    for stats in results:
        assert stats["replay_epochs"] >= 1, stats
        assert stats["replay_cycles"] > 0, stats
        # steady state: most executed cycles paid no control exchange
        assert (
            stats["negotiated_cycles"] / max(stats["cycles"], 1) < 0.5
        ), stats


def _chaos_delay_worker():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import _engine_registry

    hvd.init()
    for i in range(60):
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="grad")
        assert float(out[0]) == 2.0, float(out[0])
    eng = _engine_registry.get_engine()
    stats = dict(eng.stats)
    hvd.shutdown()
    return stats


@pytest.mark.multiprocess
def test_two_proc_chaos_delay_breaks_epoch_no_hang():
    """A deterministic straggler (fault registry action=delay on rank 1's
    enqueue path) lands mid-replay: the delayed rank idles past the
    stall budget, raises the epoch-check flag, and BOTH ranks fall back
    to negotiation — the job finishes with correct results instead of
    hanging."""
    env = {
        "HVDTPU_EAGER_ENGINE": "python",
        "HVDTPU_EAGER_DEVICE": "0",
        "HVDTPU_SCHEDULE_REPLAY_CYCLES": "5",
        "HVDTPU_CYCLE_TIME": "2",
        # the stall budget doubles as the replay idle-break deadline
        "HVDTPU_STALL_CHECK_TIME_SECONDS": "1",
        # fire once, on rank 1, on its ~30th enqueue (well inside the
        # replay epoch), stalling that thread for 2.5 s
        "HVDTPU_FAULT_SPEC": "enqueue:rank=1:step=30:action=delay:2500",
    }
    results = hvdrun.run(_chaos_delay_worker, np=2, use_cpu=True,
                         timeout=180, env=env)
    assert any(s["replay_breaks"] >= 1 for s in results), results
    for stats in results:
        assert stats["replay_epochs"] >= 1, stats
