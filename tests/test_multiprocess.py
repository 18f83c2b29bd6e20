"""True multi-process integration tests: real 2-process CPU worlds through
the launcher, exercising the eager engine's negotiation/data path across
process boundaries.

This is the reference CI's central trick (SURVEY.md §4: pytest under
`mpirun -np 2 -H localhost:2`) inverted: instead of running the test file
under the launcher, the test calls horovod_tpu.run.run(fn, np=2), the
in-process equivalent the reference covers in test_interactiverun.py."""

import numpy as np
import pytest

import horovod_tpu.run as hvdrun

pytestmark = pytest.mark.multiprocess


# engine_env fixture (python/native cross) lives in tests/conftest.py.


def _world_fn():
    import jax
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    return {
        "rank": hvd.rank(),
        "size": hvd.size(),
        "procs": jax.process_count(),
        "devices": jax.device_count(),
    }


def test_run_api_two_process_world(engine_env):
    results = hvdrun.run(_world_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["size"] == 2 for r in results)
    assert all(r["procs"] == 2 for r in results)


def _eager_ops_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()

    out = {}
    # allreduce: sum of per-rank tensors
    x = np.full(4, float(r + 1), np.float32)
    out["allreduce_sum"] = hvd.allreduce(x, op=hvd.Sum).tolist()
    out["allreduce_avg"] = hvd.allreduce(x, op=hvd.Average).tolist()
    # fused pair in one cycle: enqueue two async then synchronize
    h1 = hvd.allreduce_async(np.ones(2, np.float32), op=hvd.Sum, name="f1")
    h2 = hvd.allreduce_async(np.full(3, 2.0, np.float32), op=hvd.Sum, name="f2")
    out["fused"] = [hvd.synchronize(h1).tolist(), hvd.synchronize(h2).tolist()]
    # ragged allgather: rank r contributes r+1 rows
    g = np.full((r + 1, 2), float(r), np.float32)
    out["allgather"] = hvd.allgather(g).tolist()
    # broadcast from rank 1
    b = np.asarray([100.0 * (r + 1)], np.float32)
    out["broadcast"] = hvd.broadcast(b, root_rank=1).tolist()
    # min/max
    out["min"] = hvd.allreduce(np.asarray([float(r)], np.float32), op=hvd.Min).tolist()
    out["max"] = hvd.allreduce(np.asarray([float(r)], np.float32), op=hvd.Max).tolist()
    hvd.shutdown()
    return out


def test_eager_collectives_across_processes(engine_env):
    results = hvdrun.run(_eager_ops_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    for r in results:
        assert r["allreduce_sum"] == [3.0] * 4  # 1 + 2
        assert r["allreduce_avg"] == [1.5] * 4
        assert r["fused"][0] == [2.0, 2.0]
        assert r["fused"][1] == [4.0, 4.0, 4.0]
        # ragged allgather: rank0's 1 row of 0s then rank1's 2 rows of 1s
        assert r["allgather"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert r["broadcast"] == [200.0]
        assert r["min"] == [0.0]
        assert r["max"] == [1.0]


def _join_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    # Uneven data: rank 0 has 3 batches, rank 1 has 1 (reference
    # test strategy for join, §3.5)
    n_batches = 3 if r == 0 else 1
    sums = []
    for i in range(n_batches):
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name=f"batch{i}")
        sums.append(out.tolist())
    hvd.join()
    hvd.shutdown()
    return sums


def test_join_uneven_batches(engine_env):
    results = hvdrun.run(_join_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    # batch 0: both ranks -> 2.0; batches 1-2: only rank 0 (rank 1 joined,
    # contributes zeros) -> 1.0
    assert results[0] == [[2.0, 2.0], [1.0, 1.0], [1.0, 1.0]]
    assert results[1] == [[2.0, 2.0]]


def _mismatch_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    x = np.ones(4 if hvd.rank() == 0 else 5, np.float32)
    try:
        hvd.allreduce(x, op=hvd.Sum, name="bad")
        return "no error"
    except RuntimeError as e:
        return str(e)
    finally:
        hvd.shutdown()


def test_shape_mismatch_raises_on_all_ranks(engine_env):
    results = hvdrun.run(_mismatch_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    for msg in results:
        assert "Mismatched shapes" in msg


def _raising_fn():
    raise ValueError("bad learning rate 42")


def test_worker_exception_traceback_surfaces():
    with pytest.raises(RuntimeError, match="bad learning rate 42"):
        hvdrun.run(_raising_fn, np=2, use_cpu=True, timeout=120)


def _broadcast_params_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    params = {"w": np.full((3,), float(r), np.float32),
              "b": {"x": np.full((2,), 10.0 * r, np.float32)}}
    out = hvd.broadcast_parameters(params, root_rank=0)
    obj = hvd.broadcast_object({"epoch": 7} if r == 0 else None, root_rank=0)
    hvd.shutdown()
    return {
        "w": np.asarray(out["w"]).tolist(),
        "x": np.asarray(out["b"]["x"]).tolist(),
        "obj": obj,
    }


def test_broadcast_parameters_across_processes(engine_env):
    results = hvdrun.run(_broadcast_params_fn, np=2, use_cpu=True,
                         timeout=180, env=engine_env)
    for r in results:
        assert r["w"] == [0.0, 0.0, 0.0]
        assert r["x"] == [0.0, 0.0]
        assert r["obj"] == {"epoch": 7}


def _ckpt_fn(ckpt_dir):
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import restore_checkpoint, save_checkpoint

    hvd.init()
    r = hvd.rank()
    # per-rank divergent state; save writes rank 0's copy only
    state = {"w": np.full((3,), float(r + 1), np.float32)}
    save_checkpoint(ckpt_dir, state, step=1)
    # restore with broadcast: every rank must come back with rank 0's values
    out = restore_checkpoint(ckpt_dir, {"w": np.zeros((3,), np.float32)})
    hvd.shutdown()
    return np.asarray(out["w"]).tolist()


def test_checkpoint_rank0_write_broadcast_restore(engine_env, tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    results = hvdrun.run(_ckpt_fn, (ckpt_dir,), np=2, use_cpu=True,
                         timeout=180, env=engine_env)
    for r in results:
        assert r == [1.0, 1.0, 1.0]  # rank 0's state everywhere


def _ckpt_async_fn(ckpt_dir):
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import (
        restore_checkpoint, save_checkpoint_async,
    )

    hvd.init()
    r = hvd.rank()
    state = {"w": np.full((3,), float(r + 1), np.float32)}
    handle = save_checkpoint_async(ckpt_dir, state, step=1)
    # training would continue here; wait() is the commit point + barrier
    handle.wait()
    out = restore_checkpoint(ckpt_dir, {"w": np.zeros((3,), np.float32)})
    hvd.shutdown()
    return np.asarray(out["w"]).tolist()


def test_checkpoint_async_rank0_write_broadcast_restore(engine_env,
                                                        tmp_path):
    ckpt_dir = str(tmp_path / "ckpt_async")
    results = hvdrun.run(_ckpt_async_fn, (ckpt_dir,), np=2, use_cpu=True,
                         timeout=180, env=engine_env)
    for r in results:
        assert r == [1.0, 1.0, 1.0]


def _ckpt_nonshared_fn(ckpt_dir):
    import os

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.checkpoint import restore_checkpoint, save_checkpoint

    hvd.init()
    r = hvd.rank()
    # Simulate a NON-shared filesystem: each rank gets a private directory;
    # only rank 0's ever receives the checkpoint.
    my_dir = os.path.join(ckpt_dir, f"private_{r}")
    state = {"w": np.full((2,), 42.0 if r == 0 else -1.0, np.float32)}
    save_checkpoint(my_dir, state, step=3)
    # step=None: rank 0 resolves "latest" and broadcasts it; rank 1's
    # directory has no checkpoints but must still restore successfully.
    restore_dir = my_dir if r == 0 else os.path.join(ckpt_dir, "nowhere")
    out = restore_checkpoint(restore_dir, {"w": np.zeros((2,), np.float32)})
    hvd.shutdown()
    return np.asarray(out["w"]).tolist()


def test_checkpoint_restore_without_shared_filesystem(engine_env, tmp_path):
    results = hvdrun.run(_ckpt_nonshared_fn, (str(tmp_path),), np=2,
                         use_cpu=True, timeout=180, env=engine_env)
    for r in results:
        assert r == [42.0, 42.0]


def _stall_fn():
    import time

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    t0 = time.monotonic()
    if r == 0:
        # Submit immediately; rank 1 never will -> stall -> shutdown.
        try:
            hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="stalled")
            out = ("no error", 0.0)
        except RuntimeError as e:
            out = (str(e), time.monotonic() - t0)
    else:
        time.sleep(25)  # deliberately never submit (reference test_stall.py)
        out = ("slept", time.monotonic() - t0)
    try:
        hvd.shutdown()
    except Exception:
        pass
    return out


@pytest.mark.slow  # tier-1 budget triage (ISSUE 15): run by node id in ci/test_matrix.sh slow_multiproc gate
def test_stall_shutdown_aborts_instead_of_hanging():
    """Reference test_stall.py: a rank that never submits triggers the
    stall inspector's warning then coordinated shutdown
    (HOROVOD_STALL_SHUTDOWN_TIME_SECONDS; stall_inspector.cc).

    Native engine only: its background loop starts at init() on every rank
    (own TCP mesh), so rank 1's controller cycles without rank 1 ever
    enqueueing — the precondition for observing the stall."""
    from horovod_tpu.runtime.native import native_available

    if not native_available():
        pytest.skip("native library not built (make -C cpp)")
    env = {
        "HVDTPU_EAGER_ENGINE": "native",
        "HVDTPU_STALL_CHECK_TIME_SECONDS": "2",
        "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS": "5",
    }
    results = hvdrun.run(_stall_fn, np=2, use_cpu=True, timeout=120, env=env)
    msg, t_err = results[0]
    # The pending op fails with the coordinated shutdown error (reference:
    # outstanding callbacks get SHUT_DOWN_ERROR, operations.cc:526-532;
    # the "Stalled tensor ..." detail lands in the rank-0 engine log).
    assert "stall" in msg.lower() or "shut down" in msg.lower()
    # Must be the STALL inspector (fires ~5-7 s in), not rank 1's exit at
    # 25 s — wrong env names would make this pass via the slow path.
    assert t_err < 15, f"stall shutdown should fire ~6s in, got {t_err:.0f}s"


def _torch_interop_fn():
    import numpy as np
    import torch

    import horovod_tpu.interop.torch as hvd

    hvd.init()
    r = hvd.rank()
    out = {}
    out["allreduce"] = hvd.allreduce(
        torch.full((3,), float(r + 1)), op=hvd.Sum
    ).tolist()
    out["allgather"] = hvd.allgather(
        torch.full((r + 1, 2), float(r))
    ).tolist()
    out["broadcast"] = hvd.broadcast(
        torch.tensor([float(10 * (r + 1))]), root_rank=1
    ).tolist()

    # autograd across processes: grad of allreduce is allreduced
    x = torch.ones(2, requires_grad=True)
    y = hvd.allreduce(x, op=hvd.Sum)
    y.backward(torch.full((2,), float(r + 1)))
    out["grad"] = x.grad.tolist()  # sum of [1,2] per-rank grads = 3

    # DistributedOptimizer: ranks start identical, divergent grads are
    # averaged, so weights stay identical after step
    torch.manual_seed(0)
    model = torch.nn.Linear(2, 1, bias=False)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters(),
    )
    loss = (model(torch.ones(1, 2)) * float(r + 1)).sum()
    loss.backward()
    opt.step()
    out["weights"] = model.weight.detach().flatten().tolist()
    hvd.shutdown()
    return out


def test_torch_interop_across_processes(engine_env):
    results = hvdrun.run(_torch_interop_fn, np=2, use_cpu=True,
                         timeout=180, env=engine_env)
    for r in results:
        assert r["allreduce"] == [3.0, 3.0, 3.0]
        assert r["allgather"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert r["broadcast"] == [20.0]
        assert r["grad"] == [3.0, 3.0]
    # weight sync: both ranks identical after averaged update
    assert results[0]["weights"] == results[1]["weights"]


def _fastpath_fn():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import get_engine

    hvd.init()
    r = hvd.rank()

    # Repeated same-name workload: cycle 1 negotiates + fills the cache,
    # every later submission must ride the bit-vote fast path.
    last = None
    for i in range(6):
        last = hvd.allreduce(
            np.full(4, float(r + 1 + i), np.float32), op=hvd.Sum, name="grad"
        )
    stats = dict(get_engine().stats)

    # dtype-native data plane: int64 beyond 2^53 round-trips exactly
    # (a float64 wire would quantize to multiples of 1024 at 2^60).
    big = hvd.allreduce(
        np.asarray([2**60 + 3 + r], np.int64), op=hvd.Sum, name="big"
    )

    # bf16 stays bf16 on the wire, accumulates in f32
    import ml_dtypes

    half = hvd.allreduce(
        np.ones(4, ml_dtypes.bfloat16), op=hvd.Sum, name="half"
    )
    bf16_ok = half.dtype == ml_dtypes.bfloat16 and np.all(
        half.astype(np.float32) == 2.0
    )

    # overlapping barriers queue instead of DUPLICATE_NAME
    eng = get_engine()
    b1, b2 = eng.barrier(), eng.barrier()
    b1.result()
    b2.result()

    out = {
        "last": last.tolist(),
        "stats": stats,
        "big": [int(v) for v in big.tolist()],
        "bf16_ok": bool(bf16_ok),
    }
    hvd.shutdown()
    return out


def test_python_engine_steady_state_fast_path():
    """VERDICT r1 #3: second-and-later cycles of a repeated workload
    exchange only cache votes (reference response_cache.cc:468 bitvector
    sync), the data plane is dtype-native (exact int64 > 2^53), and
    barriers queue.  Python engine only — the native engine has its own
    C++ response cache covered by its tests."""
    results = hvdrun.run(_fastpath_fn, np=2, use_cpu=True, timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    for res in results:
        # 1 + 2 + i adjustments: ranks sent (i+1) and (i+2) at step i=5
        assert res["last"] == [13.0] * 4  # 6+7 on the final iteration
        # exactly one negotiated allreduce for "grad"; the other five rode
        # the cache (big/half/barriers add their own negotiated ops)
        st = res["stats"]
        assert st["cached_responses"] >= 5, st
        assert st["cache_hits"] >= 5, st
        assert st["fast_cycles"] >= 1, st
        # exact int64: 2*2^60 + 3 + 4 = 2305843009213693959
        assert res["big"] == [2**61 + 7], res["big"]
        assert res["bf16_ok"]


def _join_with_cached_votes_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    # negotiate + cache "g" on both ranks
    first = hvd.allreduce(
        np.full(4, float(r + 1), np.float32), op=hvd.Sum, name="g"
    ).tolist()
    if r == 1:
        # rank 1 runs out of data: join.  While blocked it must still
        # participate (with zeros) in rank 0's CACHED collectives — the
        # fast path must include joined ranks in the vote execution.
        last = hvd.join()
        out = {"first": first, "cached_during_join": None, "join": last}
    else:
        vals = []
        for i in range(3):
            vals.append(
                hvd.allreduce(
                    np.full(4, float(10 + i), np.float32),
                    op=hvd.Sum, name="g",
                ).tolist()
            )
        last = hvd.join()
        out = {"first": first, "cached_during_join": vals, "join": last}
    hvd.shutdown()
    return out


def test_join_participates_in_cached_votes():
    """Regression: a joined rank computed ready=[] from its empty local
    armed set and skipped the cached collective its peers executed,
    desynchronizing the data-plane allgathers."""
    results = hvdrun.run(_join_with_cached_votes_fn, np=2, use_cpu=True,
                         timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    r0 = next(r for r in results if r["cached_during_join"] is not None)
    assert r0["first"] == [3.0] * 4
    # joined rank contributed zeros: sums are rank 0's values alone
    assert r0["cached_during_join"] == [[10.0] * 4, [11.0] * 4, [12.0] * 4]


def _cache_conflict_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    out = {}
    # negotiate + cache "t" as f32 shape (2,)
    out["first"] = hvd.allreduce(
        np.ones(2, np.float32), op=hvd.Sum, name="t"
    ).tolist()
    out["again"] = hvd.allreduce(
        np.full(2, 2.0, np.float32), op=hvd.Sum, name="t"
    ).tolist()
    # re-submit the SAME name with different geometry on every rank: the
    # stale cache entry must be evicted and renegotiated, not collide
    out["reshaped"] = hvd.allreduce(
        np.ones(3, np.float32), op=hvd.Sum, name="t"
    ).tolist()
    # and mismatched ACROSS ranks must produce the negotiated error
    try:
        hvd.allreduce(
            np.ones(2 + r, np.float32), op=hvd.Sum, name="t"
        )
        out["mismatch"] = "no error"
    except RuntimeError as exc:
        out["mismatch"] = (
            "shapes" if "Mismatched shapes" in str(exc) else str(exc)
        )
    hvd.shutdown()
    return out


def test_cache_conflict_renegotiates():
    results = hvdrun.run(_cache_conflict_fn, np=2, use_cpu=True,
                         timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    for res in results:
        assert res["first"] == [2.0, 2.0]
        assert res["again"] == [4.0, 4.0]
        assert res["reshaped"] == [2.0, 2.0, 2.0]
        assert res["mismatch"] == "shapes"


def _reducescatter_fn():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import eager

    hvd.init()
    r = hvd.rank()
    out = {}
    # even split: dim0=4, world=2 -> 2 rows each; sum of (1s, 2s) = 3s
    x = np.full((4, 3), float(r + 1), np.float32)
    out["even"] = eager.reducescatter(x, op=hvd.Sum).tolist()
    # uneven split: dim0=3 -> rank0 gets 2 rows, rank1 gets 1
    y = np.arange(6, dtype=np.float32).reshape(3, 2) * (r + 1)
    out["uneven"] = eager.reducescatter(y, op=hvd.Sum).tolist()
    out["avg"] = eager.reducescatter(
        np.full(2, float(r + 1), np.float32), op=hvd.Average
    ).tolist()
    # scalar input -> negotiated error
    try:
        eager.reducescatter(np.float32(1.0), op=hvd.Sum)
        out["scalar"] = "no error"
    except RuntimeError as exc:
        out["scalar"] = "scalar" if "1-dimensional" in str(exc) else str(exc)
    hvd.shutdown()
    return out


def test_reducescatter_across_processes(engine_env):
    """VERDICT r1 #10: eager reducescatter on both engines (it was the one
    collective that just raised NotImplementedError)."""
    results = hvdrun.run(_reducescatter_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    # sum over ranks of arange*([1,2]) = arange*3
    full = (np.arange(6, dtype=np.float32).reshape(3, 2) * 3).tolist()
    for rk, res in enumerate(results):
        assert res["even"] == [[3.0] * 3] * 2
        assert res["uneven"] == (full[:2] if rk == 0 else full[2:])
        assert res["avg"] == [1.5]  # one of the two elements per rank
        assert res["scalar"] == "scalar"


def _native_autotune_fn():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import get_engine

    hvd.init()
    eng = get_engine()
    initial_fusion = eng.lib.hvdtpu_get_fusion_bytes()
    # Steady eager traffic for the tuner to score (bytes/sec per sample
    # window, reference parameter_manager.h:178-220).
    import time

    # Generous deadline: the tuner's move cadence is wall-clock (one score
    # sample per ~steps_per_sample cycles); under a loaded CI machine the
    # cycles stretch, which made an 8 s window flaky (ADVICE r2).
    deadline = time.monotonic() + 30.0
    i = 0
    moved_fusion = initial_fusion
    moved_cycle = None
    while time.monotonic() < deadline:
        hvd.allreduce(
            np.ones(4096, np.float32), op=hvd.Sum, name=f"t{i % 4}"
        )
        i += 1
        moved_fusion = eng.lib.hvdtpu_get_fusion_bytes()
        moved_cycle = eng.lib.hvdtpu_get_cycle_ms()
        if moved_fusion != initial_fusion:
            break
    out = {
        "initial": int(initial_fusion),
        "fusion": int(moved_fusion),
        "cycle_ms": float(moved_cycle),
        "perf_bytes": int(eng.lib.hvdtpu_perf_bytes()),
        "iters": i,
    }
    # The ranks see the move one loop apart when a cycle is starved:
    # join() lets the later rank's last allreduce complete (as in
    # _python_autotune_fn) instead of meeting the other's shutdown.
    hvd.join()
    hvd.shutdown()
    return out


@pytest.mark.serial
def test_native_autotune_moves_params():
    """VERDICT r1 #2: under HVDTPU_AUTOTUNE=1 the native engine's
    fusion/cycle move (rank 0 tunes, params ride the ResponseList to every
    rank — reference parameter_manager.cc:528 + controller.cc:33-47).

    serial: the autotuner samples real bytes/sec cycle timings; an
    oversubscribed parallel pass can starve a cycle and flake it."""
    from horovod_tpu.runtime.native import native_available

    if not native_available():
        pytest.skip("native library not built (make -C cpp)")
    env = {
        "HVDTPU_EAGER_ENGINE": "native",
        "HVDTPU_AUTOTUNE": "1",
        # distinctive initial so a tuner move is detectable
        "HVDTPU_FUSION_THRESHOLD": str(3 * 1024 * 1024),
        "HVDTPU_CYCLE_TIME": "2",
        # Deterministic tuner cadence (reference common.h:67-69 knobs):
        # first move after (1 warmup + 1) samples x 2 cycles instead of
        # (3 + 1) x 10 — the wall-clock-window flakiness ADVICE r2 flagged.
        "HVDTPU_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE": "2",
    }
    results = hvdrun.run(_native_autotune_fn, np=2, use_cpu=True,
                         timeout=240, env=env)
    for res in results:
        assert res["initial"] == 3 * 1024 * 1024
        assert res["perf_bytes"] > 0, res
        # BOTH ranks applied a tuner move (rank 1 only via the wire)
        assert res["fusion"] != res["initial"], res


def _tf_interop_fn():
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.interop.tf as hvd

    hvd.init()
    r = hvd.rank()
    out = {}
    out["allreduce"] = hvd.allreduce(
        tf.fill((3,), float(r + 1)), op=hvd.Sum
    ).numpy().tolist()
    out["allgather"] = hvd.allgather(
        tf.fill((r + 1, 2), float(r))
    ).numpy().tolist()
    out["broadcast"] = hvd.broadcast(
        tf.constant([float(10 * (r + 1))]), root_rank=1
    ).numpy().tolist()

    # IndexedSlices across processes: rank r contributes row index r
    slices = tf.IndexedSlices(
        values=tf.constant([[float(r + 1), float(r + 1)]]),
        indices=tf.constant([r], dtype=tf.int64),
        dense_shape=tf.constant([4, 2], dtype=tf.int64),
    )
    red = hvd.allreduce(slices, op=hvd.Sum)
    out["sparse_values"] = red.values.numpy().tolist()
    out["sparse_indices"] = red.indices.numpy().tolist()

    # DistributedGradientTape: divergent per-rank grads are averaged
    v = tf.Variable([2.0])
    with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
        loss = tf.reduce_sum(v * float(r + 1))
    grad = tape.gradient(loss, v)
    out["tape_grad"] = grad.numpy().tolist()  # avg of [1, 2] = 1.5

    # Keras DistributedOptimizer: identical start + averaged grads ->
    # identical weights after the step
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    w = tf.Variable([[1.0, 1.0]])
    hvd.broadcast_variables([w], root_rank=0)
    with tf.GradientTape() as t2:
        loss2 = tf.reduce_sum(w * float(r + 1))
    g2 = t2.gradient(loss2, w)
    opt.apply_gradients([(g2, w)])
    out["weights"] = w.numpy().flatten().tolist()
    hvd.shutdown()
    return out


@pytest.mark.slow  # tier-1 budget triage (ISSUE 15): run by node id in ci/test_matrix.sh slow_multiproc gate
def test_tf_interop_across_processes(engine_env):
    pytest.importorskip("tensorflow")
    results = hvdrun.run(_tf_interop_fn, np=2, use_cpu=True,
                         timeout=240, env=engine_env)
    for r in results:
        assert r["allreduce"] == [3.0, 3.0, 3.0]
        assert r["allgather"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert r["broadcast"] == [20.0]
        assert r["sparse_values"] == [[1.0, 1.0], [2.0, 2.0]]
        assert r["sparse_indices"] == [0, 1]
        assert r["tape_grad"] == [1.5]
    # weight sync: both ranks identical after averaged update
    assert results[0]["weights"] == results[1]["weights"]


def _sync_bn_fn():
    import numpy as np
    import torch

    import horovod_tpu.interop.torch as hvd

    hvd.init()
    r = hvd.rank()
    torch.manual_seed(0)
    full = torch.randn(8, 3, 4, 4, dtype=torch.float64)
    x = full[r * 4:(r + 1) * 4].clone().requires_grad_(True)

    sbn = hvd.SyncBatchNorm(3).double()
    out = sbn(x)
    g = torch.ones_like(out)
    out.backward(g)

    # reference: plain BN over the FULL batch on one process
    ref_x = full.clone().requires_grad_(True)
    bn = torch.nn.BatchNorm2d(3).double()
    ref = bn(ref_x)
    ref.backward(torch.ones_like(ref))
    ok_fwd = torch.allclose(out, ref[r * 4:(r + 1) * 4], atol=1e-8)
    ok_bwd = torch.allclose(x.grad, ref_x.grad[r * 4:(r + 1) * 4], atol=1e-8)
    ok_stats = torch.allclose(
        sbn.running_mean, bn.running_mean, atol=1e-8
    ) and torch.allclose(sbn.running_var, bn.running_var, atol=1e-8)

    # momentum=None: cumulative moving average (factor 1/num_batches),
    # matching torch._BatchNorm.forward — NOT a fixed 0.1.
    sbn_n = hvd.SyncBatchNorm(3, momentum=None).double()
    bn_n = torch.nn.BatchNorm2d(3, momentum=None).double()
    for step in range(3):
        batch = torch.randn(
            8, 3, 4, 4, dtype=torch.float64,
            generator=torch.Generator().manual_seed(step),
        )
        sbn_n(batch[r * 4:(r + 1) * 4])
        bn_n(batch)
    ok_cma = torch.allclose(
        sbn_n.running_mean, bn_n.running_mean, atol=1e-8
    ) and torch.allclose(sbn_n.running_var, bn_n.running_var, atol=1e-8)
    hvd.shutdown()
    return {"fwd": bool(ok_fwd), "bwd": bool(ok_bwd),
            "stats": bool(ok_stats), "cma": bool(ok_cma)}


def test_sync_batch_norm_matches_full_batch(engine_env):
    """SyncBatchNorm over rank-split batches == plain BN over the full
    batch (reference test_torch.py sync BN cases)."""
    results = hvdrun.run(_sync_bn_fn, np=2, use_cpu=True, timeout=180,
                         env=engine_env)
    for r in results:
        assert r == {"fwd": True, "bwd": True, "stats": True, "cma": True}


def test_estimator_launcher_backend(tmp_path):
    """Estimator fit through the launcher (≙ Spark-task training,
    horovod/spark/runner.py): 2 worker processes, eager gradient averaging."""
    import numpy as np
    import optax

    from horovod_tpu.checkpoint import LocalStore
    from horovod_tpu.estimator import Estimator
    from horovod_tpu.models.simple import MLP

    rng = np.random.RandomState(0)
    n = 128
    x = np.concatenate([
        rng.randn(n // 2, 2).astype(np.float32) + 2.0,
        rng.randn(n // 2, 2).astype(np.float32) - 2.0,
    ])
    y = np.concatenate([
        np.zeros(n // 2, np.int32), np.ones(n // 2, np.int32)
    ])

    est = Estimator(
        MLP(features=(8,), num_classes=2),
        optax.adam(1e-2),
        batch_size=32,
        epochs=3,
        backend="launcher",
        np_workers=2,
        use_cpu=True,
        store=LocalStore(str(tmp_path)),
        run_id="launcher",
    )
    model = est.fit({"features": x, "label": y})
    assert len(model.history) == 3
    assert model.history[-1]["loss"] < model.history[0]["loss"]
    acc = (model.transform({"features": x})["prediction"] == y).mean()
    assert acc > 0.9


# ---------------------------------------------------------------------------
# device data plane (VERDICT r2 item 2): jax.Array payloads execute as XLA
# collectives over the process mesh — no host round-trip.
# ---------------------------------------------------------------------------


def _device_plane_fn():
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import peek_engine

    hvd.init()
    r = hvd.rank()
    out = {}

    x = jnp.full((4,), float(r + 1), jnp.float32)
    s = hvd.allreduce(x, op=hvd.Sum)
    out["sum_is_device"] = isinstance(s, jax.Array)
    out["sum"] = np.asarray(s).tolist()

    b = jnp.asarray([100.0 * (r + 1)], jnp.float32)
    bc = hvd.broadcast(b, root_rank=1)
    out["bcast_is_device"] = isinstance(bc, jax.Array)
    out["bcast"] = np.asarray(bc).tolist()

    g = jnp.full((r + 1, 2), float(r), jnp.float32)
    ag = hvd.allgather(g)
    out["ag_is_device"] = isinstance(ag, jax.Array)
    out["ag"] = np.asarray(ag).tolist()

    # bf16 rides the device wire at 2 B/elt with f32 accumulation
    hb = hvd.allreduce(jnp.full((3,), 0.5, jnp.bfloat16), op=hvd.Average)
    out["bf16"] = np.asarray(hb.astype(jnp.float32)).tolist()

    eng = peek_engine()
    out["device_data_ops"] = eng.stats["device_data_ops"]
    out["host_data_ops"] = eng.stats["host_data_ops"]
    out["device_payload_bytes"] = eng.stats["device_payload_bytes"]
    hvd.shutdown()
    return out


def test_device_plane_no_host_round_trip():
    """Device-array eager collectives return device arrays, computed by the
    XLA data plane: the device-op counter moves, the HOST data plane is
    never touched (the assertion that there is no host round-trip)."""
    results = hvdrun.run(_device_plane_fn, np=2, use_cpu=True, timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    for r in results:
        assert r["sum_is_device"] and r["bcast_is_device"] and r["ag_is_device"]
        assert r["sum"] == [3.0] * 4
        assert r["bcast"] == [200.0]
        assert r["ag"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert r["bf16"] == [0.5, 0.5, 0.5]
        assert r["device_data_ops"] >= 4
        assert r["host_data_ops"] == 0, "payload took a host round-trip"
        assert r["device_payload_bytes"] > 0


def _multi_local_device_fn():
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import peek_engine

    hvd.init()
    r = hvd.rank()
    out = {"n_local": len(jax.local_devices()),
           "n_global": len(jax.devices())}

    # non-divisible length (11 % 4 != 0) exercises the pad/unpad path
    x = jnp.arange(11, dtype=jnp.float32) + float(r)
    s = hvd.allreduce(x, op=hvd.Sum)
    out["sum_is_device"] = isinstance(s, jax.Array)
    out["sum"] = np.asarray(s).tolist()

    # caller committed to a NON-anchor local chip: result must come back
    # committed to that same chip
    dev = jax.local_devices()[2]
    y = jax.device_put(jnp.full((8,), float(r + 1), jnp.float32), dev)
    sy = hvd.allreduce(y, op=hvd.Average)
    out["y_dev_preserved"] = next(iter(sy.devices())) == dev
    out["y"] = np.asarray(sy).tolist()

    hb = hvd.allreduce(jnp.full((5,), 0.5, jnp.bfloat16), op=hvd.Average)
    out["bf16"] = np.asarray(hb.astype(jnp.float32)).tolist()

    mn = hvd.allreduce(jnp.asarray([float(r)], jnp.float32), op=hvd.Min)
    out["min"] = np.asarray(mn).tolist()

    # row-shaped collectives under the multi-chip topology: every one of
    # allgather/broadcast/reducescatter/alltoall fans its payload across
    # all k local chips (hierarchical: cross-host on 1/k chunks + local
    # reassembly) and never touches the host plane
    g = jax.device_put(
        jnp.full((2,), float(r), jnp.float32), jax.local_devices()[1]
    )
    ag = hvd.allgather(g)
    out["ag"] = np.asarray(ag).tolist()
    bc = hvd.broadcast(
        jnp.asarray([10.0 * (r + 1)], jnp.float32), root_rank=1
    )
    out["bcast"] = np.asarray(bc).tolist()
    # reducescatter: (world*3,) rows of value r+1 -> each rank keeps 3
    # rows of the sum; length 6 is not divisible by k=4 local chips, so
    # the per-block sub-chunk pad/unpad path is exercised too
    rs = hvd.reducescatter(jnp.full((6,), float(r + 1), jnp.float32))
    out["rs"] = np.asarray(rs).tolist()
    # alltoall: rank r sends block d (value 10r+d, 3 elements) to rank d
    a2a_in = jnp.repeat(jnp.arange(2, dtype=jnp.float32), 3) + 10.0 * r
    a2a = hvd.alltoall(a2a_in)
    out["a2a"] = np.asarray(a2a).tolist()

    eng = peek_engine()
    plane = eng._device_plane
    out["plane_n_local"] = plane.n_local
    out["plane_mesh2d_devices"] = (
        0 if plane.mesh2d is None else plane.mesh2d.devices.size
    )
    # cache_info().currsize > 0 proves the SHARDED (all-local-chip) jits
    # actually built — i.e. the row ops took the hierarchical path, not
    # the anchor-row fallback
    out["sharded_fns_built"] = {
        "allgather": plane._allgather_sharded_fn.cache_info().currsize,
        "broadcast": plane._broadcast_sharded_fn.cache_info().currsize,
        "reducescatter":
            plane._reducescatter_sharded_fn.cache_info().currsize,
        "alltoall": plane._alltoall_sharded_fn.cache_info().currsize,
    }
    out["device_data_ops"] = eng.stats["device_data_ops"]
    out["host_data_ops"] = eng.stats["host_data_ops"]
    hvd.shutdown()
    return out


def test_multi_local_device_plane():
    """VERDICT r3 item 3: a process owning k>1 chips meshes ALL of them —
    on an 8-device world (np=2 x 4 local), eager allreduce executes over
    the full (2, 4) mesh (chunks fanned across local chips), results
    commit back to the caller's own chip, and the host data plane is never
    touched."""
    results = hvdrun.run(
        _multi_local_device_fn, np=2, use_cpu=True, timeout=240,
        env={
            "HVDTPU_EAGER_ENGINE": "python",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    for d, r in enumerate(results):
        assert r["n_local"] == 4 and r["n_global"] == 8
        assert r["plane_n_local"] == 4
        assert r["plane_mesh2d_devices"] == 8, "plane did not mesh all chips"
        assert r["sum_is_device"]
        assert r["sum"] == [2.0 * i + 1.0 for i in range(11)]
        assert r["y_dev_preserved"], "result not committed to caller's chip"
        # hierarchical row ops: values correct AND the all-local-chip
        # sharded jits were the ones that ran (VERDICT r4 missing #3)
        assert r["ag"] == [0.0, 0.0, 1.0, 1.0]
        assert r["bcast"] == [20.0]
        assert r["rs"] == [1.5, 1.5, 1.5]
        assert r["a2a"] == [10.0 * src + d for src in (0, 1)
                            for _ in range(3)]
        assert all(v > 0 for v in r["sharded_fns_built"].values()), (
            r["sharded_fns_built"]
        )
        assert r["host_data_ops"] == 0, "payload took a host round-trip"
        assert r["y"] == [1.5] * 8
        assert r["bf16"] == [0.5] * 5
        assert r["min"] == [0.0]
        assert r["ag"] == [0.0, 0.0, 1.0, 1.0]
        assert r["bcast"] == [20.0]
        assert r["device_data_ops"] >= 6
        assert r["host_data_ops"] == 0, "payload took a host round-trip"


def _mixed_plane_fn():
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    # rank 0 submits a HOST buffer, rank 1 a device array: negotiation must
    # demote the op to the host plane on BOTH ranks (Request.device AND),
    # and each caller still gets its own kind back.
    if r == 0:
        x = np.full((4,), 1.0, np.float32)
    else:
        x = jnp.full((4,), 2.0, jnp.float32)
    s = hvd.allreduce(x, op=hvd.Sum, name="mixed")
    kind = "device" if isinstance(s, jax.Array) else "host"
    out = {"sum": np.asarray(s).tolist(), "kind": kind}
    hvd.shutdown()
    return out


def test_mixed_plane_demotes_coherently():
    results = hvdrun.run(_mixed_plane_fn, np=2, use_cpu=True, timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    assert results[0]["sum"] == [3.0] * 4
    assert results[1]["sum"] == [3.0] * 4
    assert results[0]["kind"] == "host"
    assert results[1]["kind"] == "device"  # committed back to the caller


def _native_device_roundtrip_fn():
    import jax

    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    x = jnp.full((4,), float(r + 1), jnp.float32)
    s = hvd.allreduce(x, op=hvd.Sum)
    out = {
        "is_device": isinstance(s, jax.Array),
        "sum": np.asarray(s).tolist(),
    }
    hvd.shutdown()
    return out


def test_native_engine_returns_device_arrays(engine_env):
    """Both engines honor the device-array contract at the API boundary:
    eager allreduce of a jax.Array returns a committed jax.Array (the
    native engine ingests a zero-copy view and commits the result back)."""
    results = hvdrun.run(_native_device_roundtrip_fn, np=2, use_cpu=True,
                         timeout=180, env=engine_env)
    for r in results:
        assert r["is_device"]
        assert r["sum"] == [3.0] * 4


# ---------------------------------------------------------------------------
# halves on the wire (VERDICT r2 item 4): bf16/f16 frontend tensors must ride
# the engine at 2 B/elt — Compression.fp16 actually halves wire bytes.
# ---------------------------------------------------------------------------


def _halves_wire_fn():
    import numpy as np
    import torch

    import horovod_tpu.interop.torch as hvt
    from horovod_tpu._engine_registry import get_engine

    hvt.init()
    r = hvt.rank()
    eng = get_engine()
    out = {}

    def wire_delta(fn):
        before = eng.stats["host_wire_bytes"]
        result = fn()
        return result, eng.stats["host_wire_bytes"] - before

    n = 1024
    o32, d32 = wire_delta(
        lambda: hvt.allreduce(
            torch.full((n,), float(r + 1), dtype=torch.float32),
            op=hvt.Sum, name="w32",
        )
    )
    o16, d16 = wire_delta(
        lambda: hvt.allreduce(
            torch.full((n,), float(r + 1), dtype=torch.bfloat16),
            op=hvt.Sum, name="w16",
        )
    )
    # Compression.fp16: f32 input compressed to f16 for the wire
    comp, ctx = hvt.Compression.fp16.compress(
        torch.full((n,), float(r + 1), dtype=torch.float32)
    )
    oc, dc = wire_delta(
        lambda: hvt.Compression.fp16.decompress(
            hvt.allreduce(comp, op=hvt.Sum, name="wc"), ctx
        )
    )
    out["bytes_f32"] = d32
    out["bytes_bf16"] = d16
    out["bytes_fp16_compressed"] = dc
    out["sum_f32"] = o32[:2].tolist()
    out["sum_bf16"] = o16.to(torch.float32)[:2].tolist()
    out["sum_fp16c"] = oc[:2].tolist()
    out["dtype_bf16"] = str(o16.dtype)
    out["dtype_fp16c"] = str(oc.dtype)
    hvt.shutdown()
    return out


def test_halves_ride_the_wire_natively():
    results = hvdrun.run(_halves_wire_fn, np=2, use_cpu=True, timeout=180,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    for r in results:
        # halves cost exactly half the wire bytes of f32
        assert r["bytes_f32"] == 4096
        assert r["bytes_bf16"] == 2048, r
        assert r["bytes_fp16_compressed"] == 2048, r
        assert r["sum_f32"] == [3.0, 3.0]
        assert r["sum_bf16"] == [3.0, 3.0]  # exact at these magnitudes
        assert abs(r["sum_fp16c"][0] - 3.0) < 1e-2  # half precision tol
        assert r["dtype_bf16"] == "torch.bfloat16"
        assert r["dtype_fp16c"] == "torch.float32"  # decompressed back


# ---------------------------------------------------------------------------
# O(bytes) host data plane (VERDICT r2 item 8): host payloads reduce via a
# staged XLA collective, not gather-everything.
# ---------------------------------------------------------------------------


def _staged_host_plane_fn():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import get_engine

    hvd.init()
    r = hvd.rank()
    eng = get_engine()
    out = {}

    n = 4096
    before = eng.stats["host_recv_bytes"]
    s = hvd.allreduce(np.full((n,), float(r + 1), np.float32), op=hvd.Sum)
    out["f32_recv"] = eng.stats["host_recv_bytes"] - before
    out["f32_ok"] = bool((np.asarray(s) == 3.0).all())

    # 64-bit payloads must stay on the exact raw-bytes gather
    big = np.full((8,), 2**60, np.int64)
    before = eng.stats["host_recv_bytes"]
    s64 = hvd.allreduce(big, op=hvd.Sum)
    out["i64_recv"] = eng.stats["host_recv_bytes"] - before
    out["i64_ok"] = bool((np.asarray(s64) == 2**61).all())

    before = eng.stats["host_recv_bytes"]
    b = hvd.broadcast(np.full((n,), float(10 * (r + 1)), np.float32),
                      root_rank=1)
    out["bcast_recv"] = eng.stats["host_recv_bytes"] - before
    out["bcast_ok"] = bool((np.asarray(b) == 20.0).all())

    out["staged_ops"] = eng.stats["host_staged_ops"]
    hvd.shutdown()
    return out


def test_host_plane_reduce_is_o_bytes():
    """A large f32 allreduce/broadcast of HOST payloads receives O(bytes),
    not O(world x bytes): the engine stages it through the XLA plane's real
    reduce.  64-bit payloads keep the exact raw-bytes gather."""
    results = hvdrun.run(_staged_host_plane_fn, np=2, use_cpu=True,
                         timeout=180, env={"HVDTPU_EAGER_ENGINE": "python"})
    n_bytes = 4096 * 4
    for r in results:
        assert r["f32_ok"] and r["bcast_ok"] and r["i64_ok"]
        assert r["f32_recv"] == n_bytes, r  # O(bytes), not world x bytes
        assert r["bcast_recv"] == n_bytes, r
        assert r["i64_recv"] == 8 * 8 * 2, r  # raw gather: world x bytes
        assert r["staged_ops"] >= 2


def _python_autotune_fn(log_path):
    import time

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    deadline = time.monotonic() + 45.0
    i = 0
    while time.monotonic() < deadline:
        hvd.allreduce(np.ones(2048, np.float32), op=hvd.Sum,
                      name=f"t{i % 4}")
        i += 1
        if i % 50 == 0:  # both ranks read rank 0's log: neither waits
            try:             # out the deadline once both states are there
                with open(log_path) as f:
                    cache_col = {
                        line.split(",")[4] for line in f.readlines()[1:]
                    }
                if {"0", "1"} <= cache_col:
                    break  # both cache states explored — done
            except (OSError, IndexError):
                pass
    # Ranks leave the loop at different times (rank 0 early-breaks on the
    # log condition): join() lets the slower rank's remaining allreduces
    # complete with zero contributions instead of deadlocking — the exact
    # uneven-data semantics Join exists for (§3.5).
    hvd.join()
    hvd.shutdown()
    if rank != 0:
        return None
    with open(log_path) as f:
        rows = f.readlines()
    return {"header": rows[0].strip(), "n": len(rows) - 1,
            "cache_states": sorted({r.split(",")[4] for r in rows[1:]})}


def _alltoall_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r, n = hvd.rank(), hvd.size()
    # rank r sends block d of its buffer to rank d: block value = 10*r + d
    x = np.repeat(np.arange(n), 2).astype(np.float32)
    x = 10.0 * r + x
    out = hvd.alltoall(x, name="a2a")
    hvd.shutdown()
    return np.asarray(out).tolist()


def test_alltoall_across_processes(engine_env):
    """alltoall: rank d ends with every rank's d-th block (pairwise
    exchange over the host data plane; the jit-path analog is
    lax.all_to_all over the mesh)."""
    results = hvdrun.run(_alltoall_fn, np=2, use_cpu=True, timeout=240,
                         env=engine_env)
    for d, res in enumerate(results):
        want = []
        for src in (0, 1):
            want += [10.0 * src + d] * 2
        assert res == want, (d, res)


def _timeline_cycles_fn():
    # the timeline path flows through the HVDTPU_TIMELINE env var
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    for i in range(4):
        hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name=f"t{i}")
    hvd.shutdown()


def test_timeline_cycle_markers_across_processes(tmp_path):
    """HVDTPU_TIMELINE_MARK_CYCLES puts CYCLE markers in rank 0's Chrome
    trace (reference HOROVOD_TIMELINE_MARK_CYCLES, operations.cc:415;
    asserted like the reference's test_timeline.py:40-57)."""
    import json

    path = str(tmp_path / "timeline.json")
    hvdrun.run(_timeline_cycles_fn, np=2, use_cpu=True,
               timeout=240,
               env={
                   "HVDTPU_EAGER_ENGINE": "python",
                   "HVDTPU_TIMELINE": path,
                   "HVDTPU_TIMELINE_MARK_CYCLES": "1",
               })
    events = json.loads(open(path).read())
    names = {e.get("name") for e in events if isinstance(e, dict)}
    assert any("CYCLE" in (n or "") for n in names), sorted(names)[:20]
    # negotiation + op phases also present (reference asserts
    # NEGOTIATE_ALLREDUCE / ALLREDUCE)
    assert any("ALLREDUCE" in (n or "") for n in names)


def _adasum_per_tensor_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    # two Adasum tensors in flight in the SAME cycle, deliberately
    # non-parallel across ranks so the projection outcome is sensitive to
    # its input span
    a = np.asarray([1.0, 0.0] if r == 0 else [0.0, 1.0], np.float32)
    b = np.asarray([2.0, 2.0] if r == 0 else [2.0, -2.0], np.float32)
    ha = hvd.allreduce_async(a, op=hvd.Adasum, name="ad_a")
    hb = hvd.allreduce_async(b, op=hvd.Adasum, name="ad_b")
    out = {
        "a": np.asarray(hvd.synchronize(ha)).tolist(),
        "b": np.asarray(hvd.synchronize(hb)).tolist(),
    }
    hvd.shutdown()
    return out


def test_adasum_projection_is_per_tensor(engine_env):
    """Two Adasum tensors negotiated in one cycle reduce with PER-TENSOR
    VHDD coefficients (reference adasum.h tensor_counts: one projection
    per layer), not one projection over a fused concatenation."""
    from horovod_tpu.ops.adasum import _numpy_adasum_rows

    results = hvdrun.run(_adasum_per_tensor_fn, np=2, use_cpu=True,
                         timeout=240, env=engine_env)
    want_a = _numpy_adasum_rows([[1.0, 0.0], [0.0, 1.0]])
    want_b = _numpy_adasum_rows([[2.0, 2.0], [2.0, -2.0]])
    for res in results:
        np.testing.assert_allclose(res["a"], want_a, rtol=1e-5)
        np.testing.assert_allclose(res["b"], want_b, rtol=1e-5)


def _torch_adasum_opt_fn():
    import numpy as np
    import torch

    import horovod_tpu.interop.torch as hvd

    hvd.init()
    r = hvd.rank()
    w = torch.nn.Parameter(torch.tensor([1.0, 0.0]))
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD([w], lr=0.1),
        named_parameters=[("w", w)],
        op=hvd.Adasum,
    )
    # rank-dependent, non-parallel gradients so the Adasum projection is
    # non-trivial (parallel deltas would degenerate to an average)
    target = torch.tensor([1.0, 0.0]) if r == 0 else torch.tensor([0.3, 0.9])
    loss = (w * target).sum()
    loss.backward()
    opt.step()
    out = w.detach().numpy().tolist()
    hvd.shutdown()
    return out


def test_torch_adasum_optimizer_matches_numpy_reference(engine_env):
    """The delta-based Adasum optimizer's result equals start +
    numpy-VHDD(deltas) — the projection runs on update directions, not raw
    grads (reference _DistributedAdasumOptimizer, torch/__init__.py:225-393)."""
    from horovod_tpu.ops.adasum import _numpy_adasum_rows

    results = hvdrun.run(_torch_adasum_opt_fn, np=2, use_cpu=True,
                         timeout=240, env=engine_env)
    deltas = [
        -0.1 * np.array([1.0, 0.0]),
        -0.1 * np.array([0.3, 0.9]),
    ]
    want = np.array([1.0, 0.0]) + _numpy_adasum_rows(deltas)
    for res in results:
        np.testing.assert_allclose(res, want, rtol=1e-5)


def _tf_session_hook_fn():
    import numpy as np
    import tensorflow as tf

    tf.compat.v1.disable_eager_execution()  # TF1-style graph/session job

    import horovod_tpu.interop.tf as hvd

    hvd.init()
    r = hvd.rank()
    with tf.Graph().as_default():
        v = tf.compat.v1.get_variable(
            "v", initializer=tf.constant([float(r + 1)] * 3)
        )
        hook = hvd.BroadcastGlobalVariablesHook(root_rank=1)
        with tf.compat.v1.train.MonitoredTrainingSession(
            hooks=[hook]
        ) as sess:
            out = np.asarray(sess.run(v)).tolist()
    hvd.shutdown()
    return out


@pytest.mark.slow  # tier-1 budget triage (ISSUE 15): run by node id in ci/test_matrix.sh slow_multiproc gate
def test_tf_broadcast_hook_in_monitored_session(engine_env):
    """BroadcastGlobalVariablesHook broadcasts on session creation — the
    TF1 estimator migration path (reference tensorflow/__init__.py:194-227)."""
    results = hvdrun.run(_tf_session_hook_fn, np=2, use_cpu=True,
                         timeout=240, env=engine_env)
    for res in results:
        assert res == [2.0, 2.0, 2.0]  # root 1's initial value


def _tf_adasum_opt_fn():
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.interop.tf as hvd

    hvd.init()
    r = hvd.rank()
    v = tf.Variable([1.0, 0.0])
    opt = hvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(learning_rate=0.1), op=hvd.Adasum
    )
    grad = tf.constant([1.0, 0.0]) if r == 0 else tf.constant([0.3, 0.9])
    opt.apply_gradients([(grad, v)])
    out = v.numpy().tolist()

    # Regression: Keras-3 variables carry unscoped duplicate names
    # ('kernel', 'kernel'); the delta exchange must not collide on the
    # engine's duplicate-in-flight-name guard.
    a = tf.Variable([1.0], name="kernel")
    b = tf.Variable([2.0], name="kernel")
    opt2 = hvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(learning_rate=0.1), op=hvd.Adasum
    )
    opt2.apply_gradients(
        [(tf.constant([1.0]), a), (tf.constant([1.0]), b)]
    )
    dup_ok = np.isfinite(float(a.numpy()[0])) and np.isfinite(
        float(b.numpy()[0])
    )

    hvd.shutdown()
    return {"v": out, "dup_ok": bool(dup_ok)}


@pytest.mark.slow  # tier-1 budget triage (ISSUE 15): run by node id in ci/test_matrix.sh slow_multiproc gate
def test_tf_adasum_optimizer_matches_numpy_reference(engine_env):
    """TF frontend delta-Adasum: final var == start + numpy-VHDD(deltas)
    (reference _DistributedAdasumOptimizer, tensorflow/__init__.py:313-407)."""
    from horovod_tpu.ops.adasum import _numpy_adasum_rows

    results = hvdrun.run(_tf_adasum_opt_fn, np=2, use_cpu=True,
                         timeout=240, env=engine_env)
    deltas = [
        -0.1 * np.array([1.0, 0.0]),
        -0.1 * np.array([0.3, 0.9]),
    ]
    want = np.array([1.0, 0.0]) + _numpy_adasum_rows(deltas)
    for res in results:
        np.testing.assert_allclose(res["v"], want, rtol=1e-5)
        assert res["dup_ok"]


def _cache_divergence_fn():
    """Recreate the classification divergence a tuner cache toggle can
    cause: rank 1 holds a tensor cached (arms a slot vote) while rank 0
    negotiates the same tensor through the slow path.  Without the
    divergence repair this deadlocks — the slot vote waits on rank 0, the
    message-table entry waits on rank 1."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import get_engine

    hvd.init()
    eng = get_engine()
    r = hvd.rank()
    # prime the (coherent) cache on both ranks
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="div")
    # let the insert settle so the next submission is a clean cache HIT on
    # rank 1 (insertion rides the same cycle's response application)
    hvd.allreduce(np.zeros(1, np.float32), op=hvd.Sum, name="sync")
    if r == 0:  # flip ONLY rank 0's gate — the divergence injection
        if hasattr(eng, "lib"):
            eng.lib.hvdtpu_inject_local_cache_enabled(0)
        else:
            eng.cache_enabled = False
    out = hvd.allreduce(
        np.full(8, float(r + 1), np.float32), op=hvd.Sum, name="div"
    )
    hvd.shutdown()
    return np.asarray(out).tolist()


def test_cache_divergence_repair(engine_env):
    """A cache-hit slot vote on one rank reconciles against a slow-path
    request for the same tensor on another (both engines), instead of
    deadlocking until the stall inspector fires."""
    results = hvdrun.run(_cache_divergence_fn, np=2, use_cpu=True,
                         timeout=120, env=engine_env)
    for res in results:
        assert res == [3.0] * 8  # 1 + 2: the collective completed


def test_python_autotune_explores_cache_axis(tmp_path):
    """VERDICT r2 weak #6: the Python engine's response cache is a real
    code path now, so its tuner explores cache_enabled — both states show
    up in the autotune log (reference LogParameters CSV).  With schedule
    replay off: while it is on (the default) ``build_categories`` leaves
    ``cache_enabled: False`` out by construction, since disabling the
    cache forfeits the negotiation-free steady state
    (tests/test_multislice.py pins that side)."""
    log_path = str(tmp_path / "autotune.csv")
    results = hvdrun.run(
        _python_autotune_fn, (log_path,), np=2, use_cpu=True, timeout=240,
        env={
            "HVDTPU_EAGER_ENGINE": "python",
            "HVDTPU_AUTOTUNE": "1",
            "HVDTPU_SCHEDULE_REPLAY": "0",
            "HVDTPU_AUTOTUNE_LOG": log_path,
            "HVDTPU_CYCLE_TIME": "2",
            # Deterministic tuner cadence (reference common.h:67-69): the
            # cache axis flips after 1 warmup + 3 samples x 2 cycles, not
            # 3 + 12 x 10 — wall-clock windows under CI load were flaky.
            "HVDTPU_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE": "2",
            "HVDTPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "3",
        },
    )
    r0 = results[0]
    assert "cache_enabled" in r0["header"]
    assert r0["n"] > 0
    assert r0["cache_states"] == ["0", "1"], r0


# ---------------------------------------------------------------------------
# Keras model.fit across processes (VERDICT r2 item 7): broadcast-on-start
# + averaged epoch metrics through real tf.keras callbacks.
# ---------------------------------------------------------------------------


def _keras_fit_fn():
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.interop.tf_keras as hvk

    hvk.init()
    r = hvk.rank()

    tf.keras.utils.set_random_seed(1234 + r)  # divergent initial weights
    model = tf.keras.Sequential(
        [tf.keras.Input(shape=(2,)),
         tf.keras.layers.Dense(1, use_bias=False)]
    )
    model.compile(
        optimizer=hvk.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=0.05)
        ),
        loss="mse",
    )
    # rank-dependent CONSTANT targets so per-rank losses differ unless the
    # MetricAverageCallback averages them
    x = np.random.RandomState(7).randn(32, 2).astype(np.float32)
    y = np.full((32, 1), float(r), np.float32)
    hist = model.fit(
        x, y, epochs=2, batch_size=8, verbose=0,
        callbacks=[
            hvk.callbacks.BroadcastGlobalVariablesCallback(0),
            hvk.callbacks.MetricAverageCallback(),
        ],
    )
    out = {
        "weights": model.get_weights()[0].ravel().tolist(),
        "loss": [float(v) for v in hist.history["loss"]],
    }
    hvk.shutdown()
    return out


@pytest.mark.slow  # tier-1 budget triage (ISSUE 15): run by node id in ci/test_matrix.sh slow_multiproc gate
def test_keras_fit_across_processes():
    results = hvdrun.run(_keras_fit_fn, np=2, use_cpu=True, timeout=300,
                         env={"HVDTPU_EAGER_ENGINE": "python"})
    # Broadcast-on-start + identical (averaged) gradients => identical
    # weights on both ranks at the end of fit.
    np.testing.assert_allclose(
        results[0]["weights"], results[1]["weights"], rtol=1e-6
    )
    # MetricAverageCallback: both ranks report the SAME averaged loss even
    # though their local targets (and hence local losses) differ.
    np.testing.assert_allclose(
        results[0]["loss"], results[1]["loss"], rtol=1e-6
    )


# ---------------------------------------------------------------------------
# dtype x dims grid across processes (reference test_torch.py/test_tensorflow
# strategy: allreduce/allgather/broadcast over dtype and dimension grids)
# ---------------------------------------------------------------------------


def _dtype_grid_fn():
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    out = {}
    dtypes = ["float32", "float64", "int32", "int64", "uint8", "float16",
              "bfloat16"]
    for dt in dtypes:
        if dt == "bfloat16":
            import ml_dtypes

            npdt = np.dtype(ml_dtypes.bfloat16)
        else:
            npdt = np.dtype(dt)
        for dim in (1, 2, 3):
            shape = (2,) * dim
            x = (np.arange(2 ** dim).reshape(shape) % 3 + r).astype(npdt)
            s = hvd.allreduce(x, op=hvd.Sum, name=f"grid_{dt}_{dim}")
            out[f"{dt}_{dim}"] = np.asarray(s, np.float64).tolist()
    # int64 beyond float64's exact range must survive the wire bit-exactly
    big = np.asarray([2 ** 60 + 1, -(2 ** 61)], np.int64)
    s = hvd.allreduce(big, op=hvd.Sum, name="grid_big_i64")
    out["big_i64"] = [int(v) for v in np.asarray(s)]
    # scalar (0-d) allreduce and broadcast round-trip with shape intact
    sc = hvd.allreduce(np.float32(r + 1.0), op=hvd.Sum, name="grid_scalar")
    out["scalar"] = [float(np.asarray(sc).reshape(-1)[0]),
                     list(np.asarray(sc).shape)]
    hvd.shutdown()
    return out


def test_dtype_dims_grid_across_processes(engine_env):
    results = hvdrun.run(_dtype_grid_fn, np=2, use_cpu=True, timeout=240,
                         env=engine_env)
    for res in results:
        for dt in ["float32", "float64", "int32", "int64", "uint8",
                   "float16", "bfloat16"]:
            for dim in (1, 2, 3):
                base = (np.arange(2 ** dim).reshape((2,) * dim) % 3)
                want = (2 * base + 1).astype(np.float64)  # ranks 0+1
                got = np.asarray(res[f"{dt}_{dim}"])
                np.testing.assert_allclose(got, want.tolist(), rtol=1e-2)
        assert res["big_i64"] == [2 ** 61 + 2, -(2 ** 62)]
        assert res["scalar"][0] == 3.0
        assert res["scalar"][1] == []  # 0-d shape survives the round-trip


def _device_disabled_fn():
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu._engine_registry import get_engine

    hvd.init()
    r = hvd.rank()
    x = jnp.full((4,), float(r + 1), jnp.float32)
    s = hvd.allreduce(x, op=hvd.Sum)
    eng = get_engine()
    out = {
        "sum": np.asarray(s).tolist(),
        "is_device_result": isinstance(s, jax.Array),
        "device_data_ops": eng.stats["device_data_ops"],
    }
    hvd.shutdown()
    return out


def test_eager_device_kill_switch_demotes_globally():
    """HVDTPU_EAGER_DEVICE=0 disables the device plane: jax payloads still
    work (host plane), results still come back as device arrays, and no
    device-plane collective runs — on any rank, coherently."""
    import numpy as np

    results = hvdrun.run(
        _device_disabled_fn, np=2, use_cpu=True, timeout=180,
        env={"HVDTPU_EAGER_ENGINE": "python", "HVDTPU_EAGER_DEVICE": "0"},
    )
    for r in results:
        assert r["sum"] == [3.0] * 4
        assert r["is_device_result"]  # synchronize still restores device
        assert r["device_data_ops"] == 0
