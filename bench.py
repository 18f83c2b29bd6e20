#!/usr/bin/env python
"""Synthetic ResNet benchmark — the TPU equivalent of the reference's
examples/pytorch_synthetic_benchmark.py (ResNet-50, synthetic images,
img/sec reporting; docs/benchmarks.rst:66-79).

Prints ONE JSON line:
    {"metric": "resnet50_bf16_images_per_sec_per_chip", "value": N,
     "unit": "images/sec/chip", "vs_baseline": N / 103.55,
     "mfu": M, "flops_per_image": F, "device": "..."}

vs_baseline denominator: the only absolute per-accelerator throughput the
reference publishes in-tree — tf_cnn_benchmarks ResNet-101, batch 64,
1656.82 img/sec over 16 Pascal GPUs = 103.55 img/sec/GPU
(docs/benchmarks.rst:29-43).  The ratio therefore mixes model generation
and hardware generation; the scaling-efficiency story lives in the
multi-chip tests.  ``mfu`` is the honest absolute figure: achieved
training FLOP/s (from XLA's compiled cost analysis of the actual step
function) over the chip's peak matmul FLOP/s.

A measurement needs the chip: without ``--cpu`` a platform other than
``tpu`` is an error before anything is built.  ``--cpu`` is the tiny dry
run that proves the command end to end; its numbers are marked
``degraded`` and a record of them is written only where
``HVDTPU_BENCH_RECORD_DIR`` points.

Usage: python bench.py [--model resnet50] [--dtype bf16] [--batch-size 256]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.obs.profile import peak_flops
from horovod_tpu.utils.compile_cache import enable_compile_cache

BASELINE_IMG_PER_SEC_PER_ACCEL = 103.55  # docs/benchmarks.rst:43 (1656.82/16)

# Wall-clock bound on one --serve fleet (launch, warmup, the open-loop
# window, drain): a serving run that has not finished by then fails.
SERVE_TIMEOUT_SECS = 1200.0


def _next_record_n(record_dir: str) -> int:
    """1 + the highest round number among existing BENCH_*.json records
    (by their ``n`` payload first, filename as fallback)."""
    import glob
    import re

    best = 0
    for path in glob.glob(os.path.join(record_dir, "BENCH_*.json")):
        n = None
        try:
            with open(path) as f:
                n = json.load(f).get("n")
        except (OSError, ValueError):
            pass
        if not isinstance(n, int):
            m = re.search(r"BENCH_r?0*(\d+)", os.path.basename(path))
            n = int(m.group(1)) if m else 0
        best = max(best, n)
    return best + 1


def _auto_record(why: str, *, rc: int, phase: str, parsed: dict = None,
                 device=None):
    """Land a degraded record where ``HVDTPU_BENCH_RECORD_DIR`` (the
    campaign sets it to its --record-dir) points — and nowhere when it
    is unset: a dry run or a failed run never leaves BENCH_*.json in
    the checkout."""
    record_dir = os.environ.get("HVDTPU_BENCH_RECORD_DIR")
    if not record_dir:
        return None
    try:
        return write_degraded_record(
            why, rc=rc, phase=phase, parsed=parsed, record_dir=record_dir,
            device=device,
        )
    except Exception:
        return None  # a record write must never mask the real exit


def backend_provenance(device=None) -> dict:
    """The backend-provenance stamp every record carries: platform,
    device kind, and the JAX_PLATFORMS env — so scripts/perf_gate.py
    can tell "ran on CPU" from a failed chip run without parsing
    ``why`` strings.  ``device`` is the ``jax.Device`` the measurement
    ran on, or the ``{"platform", "kind"}`` dict a serving rank reports
    in its summary.  With no device (a failure before any was seen, the
    ``--serve`` parent) both fields stay None: this function never
    initialises a backend itself."""
    prov = {
        "platform": None,
        "device_kind": None,
        "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
    }
    if isinstance(device, dict):
        prov["platform"] = device.get("platform")
        prov["device_kind"] = device.get("kind")
    elif device is not None:
        prov["platform"] = device.platform
        prov["device_kind"] = device.device_kind
    return prov


def write_degraded_record(why: str, *, rc: int, phase: str,
                          record_dir: str, parsed: dict = None,
                          device=None):
    """Write a schema-valid ``BENCH_rNN.json`` marked ``"degraded":
    true`` into ``record_dir``: a ``--cpu`` dry run or a run that
    failed in ``phase``.  A degraded record keeps a campaign's
    trajectory explicit and is skipped as a regression baseline (see
    attach_regression).  Returns the written path."""
    n = _next_record_n(record_dir)
    doc = {
        "n": n,
        "cmd": "python bench.py " + " ".join(sys.argv[1:]),
        "rc": rc,
        "tail": why,
        "parsed": parsed,
        "degraded": True,
        "failure_phase": phase,
        "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        # Who actually ran (or failed to): the sentinel's basis for
        # separating a CPU dry run from a failure on the chip.
        "provenance": backend_provenance(device),
    }
    # Degraded records carry the memory breakdown too (census says
    # "source: unavailable" when the failure predates jax init): the
    # item-5 sweep reads headroom off EVERY record on the trajectory,
    # and a record that died in warmup still knows what was resident.
    if parsed is None or "memory" not in parsed:
        try:
            from horovod_tpu.obs import memplane  # noqa: PLC0415

            doc["memory"] = memplane.memory_record()
        except Exception:
            pass
    path = os.path.join(record_dir, f"BENCH_r{n:02d}.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def peak_flops_per_chip(device, dtype: str) -> float:
    """Peak matmul FLOP/s of ``device`` (obs/profile.py's ONE table, so
    the bench headline and the perf.mfu gauge can never disagree).  MFU
    is not meaningful against the CPU estimate: NaN there.  A device
    kind the table does not know raises."""
    peak, estimate = peak_flops(device.device_kind, dtype)
    return float("nan") if estimate else peak


def _put(mesh, tree, spec):
    """Place ``tree`` on ``mesh`` under PartitionSpec ``spec`` — once,
    before the loop, so no step call starts by moving state that was
    built on device 0 to where the compiled program wants it."""
    from jax.sharding import NamedSharding

    return jax.device_put(tree, NamedSharding(mesh, spec))


def build_gpt_step(size: str, dtype: str, batch_size: int, seq_len: int,
                   attention: str = "flash", remat: bool = False,
                   flash_block_q: int = 512, flash_block_k: int = 256,
                   kv_heads: int = 0, pos_embedding: str = "learned",
                   moe_experts: int = 0, attention_window: int = 0,
                   overlap_mode: str = "off",
                   grad_bucket_mb: float = None):
    """GPT causal-LM training step (flash attention) — the long-context
    counterpart of the ResNet bench.  Returns ``(step, state, static)``
    like ``build_step``; throughput is reported in tokens/sec/chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.optim import DistributedOptimizer

    hvd.init()
    n_chips = hvd.num_devices()

    compute_dtype = jnp.float32 if dtype == "fp32" else jnp.bfloat16
    act_store = jnp.float8_e4m3fn if dtype == "fp8" else None
    model = gpt(size, dtype=compute_dtype, max_len=seq_len,
                attention_impl=attention, remat=remat,
                flash_block_q=flash_block_q, flash_block_k=flash_block_k,
                num_kv_heads=kv_heads or None,
                pos_embedding=pos_embedding, moe_experts=moe_experts,
                act_store_dtype=act_store,
                attention_window=attention_window or None)
    vocab = model.cfg.vocab_size

    global_batch = batch_size * n_chips
    tokens = np.random.RandomState(0).randint(
        0, vocab, size=(global_batch, seq_len + 1)
    ).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:2, :-1]))
    params = hvd.broadcast_parameters(params, root_rank=0)

    def make_loss_fn(toks):
        def loss_fn(p):
            if moe_experts:
                logits, state = model.apply(
                    p, toks[:, :-1], mutable=["losses"]
                )
                aux = 0.01 * sum(jax.tree_util.tree_leaves(state["losses"]))
            else:
                logits = model.apply(p, toks[:, :-1])
                aux = 0.0
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks[:, 1:]
            ).mean() + aux

        return loss_fn

    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops.collectives import shard_map_compat

    mesh = hvd.mesh("flat")
    tokens = _put(mesh, tokens, P(hvd.DP_AXIS))
    if overlap_mode != "off":
        # Backward-overlap plane: per-bucket collectives in the
        # cotangent path (+ optional ZeRO-1 sharded update) instead of
        # the end-of-step fused psum DistributedOptimizer runs.
        from horovod_tpu.optim.overlap import OverlapPlan

        plan = OverlapPlan(params, optax.adamw(1e-4), mode=overlap_mode,
                           bucket_mb=grad_bucket_mb, mesh=mesh)
        spec = plan.state_spec()

        def local_step(ostate, toks):
            body = plan.local_step(make_loss_fn(toks))
            ostate, loss = body(ostate)
            # Mean over the DP axis: out_specs P() presents the loss as
            # replicated, so it must actually BE global (see below).
            return ostate, jax.lax.pmean(loss, hvd.DP_AXIS)

        step = jax.jit(
            shard_map_compat(
                local_step,
                mesh=mesh,
                in_specs=(spec, P(hvd.DP_AXIS)),
                out_specs=(spec, P()),
            ),
            donate_argnums=(0,),
        )
        state = (plan.init(params), tokens)
        return step, state, {"n_chips": n_chips,
                             "global_batch": global_batch,
                             "carry_len": 1}

    tx = DistributedOptimizer(optax.adamw(1e-4))
    params = _put(mesh, params, P())
    opt_state = _put(mesh, tx.init(params), P())

    def local_step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(make_loss_fn(toks))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        # Mean over the DP axis: out_specs P() presents the return value as
        # replicated, so the loss must actually BE global — otherwise the
        # printed final_loss is one shard's and the finite-check could miss
        # a NaN confined to another shard's data.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(
        shard_map_compat(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(hvd.DP_AXIS)),
            out_specs=(P(), P(), P()),
        ),
        donate_argnums=(0, 1),
    )
    state = (params, opt_state, tokens)
    return step, state, {"n_chips": n_chips, "global_batch": global_batch,
                         "carry_len": 2}


def build_step(model_name: str, dtype: str, batch_size: int, image_size: int = 224,
               s2d_stem: bool = False, overlap_mode: str = "off",
               grad_bucket_mb: float = None):
    """Build the benchmark's jitted training step and its initial state.

    Shared by bench.py (timing) and scripts/profile_bench.py (tracing) so the
    profiled step is exactly the benchmarked step. Returns
    ``(step, state, static)`` where ``state = (params, batch_stats,
    opt_state, images, labels)`` and ``step`` is the un-lowered jit callable.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import models
    from horovod_tpu.optim import DistributedOptimizer

    hvd.init()
    n_chips = hvd.num_devices()

    compute_dtype = jnp.float32 if dtype == "fp32" else jnp.bfloat16
    act_store = jnp.float8_e4m3fn if dtype == "fp8" else None
    model_cls = {
        "resnet50": models.ResNet50,
        "resnet101": models.ResNet101,
        "resnet18": models.ResNet18,
        "vgg16": models.VGG16,
        "vgg19": models.VGG19,
        "inception3": models.InceptionV3,
    }[model_name]
    extra = {}
    if model_name.startswith("resnet"):
        extra = {"s2d_stem": s2d_stem, "act_store_dtype": act_store}
    elif dtype == "fp8":
        raise SystemExit("--dtype fp8 is resnet-only (e4m3 act storage)")
    model = model_cls(num_classes=1000, compute_dtype=compute_dtype, **extra)

    rng = jax.random.PRNGKey(0)
    global_batch = batch_size * n_chips
    # Inputs in the compute dtype: halves the first conv's HBM read under
    # bf16 and matches what a real bf16 input pipeline would feed.
    images = np.random.RandomState(0).randn(
        global_batch, image_size, image_size, 3
    ).astype(compute_dtype)
    labels = np.random.RandomState(1).randint(
        0, 1000, size=(global_batch,)
    ).astype(np.int32)

    variables = model.init(rng, jnp.asarray(images[:2]), train=True)
    # VGG has no BN; {} keeps the step signature uniform across models
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    params = hvd.broadcast_parameters(params, root_rank=0)

    def make_loss_fn(batch_stats, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return loss, dict(mutated).get("batch_stats", {})

        return loss_fn

    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops.collectives import shard_map_compat

    mesh = hvd.mesh("flat")
    images = _put(mesh, images, P(hvd.DP_AXIS))
    labels = _put(mesh, labels, P(hvd.DP_AXIS))
    batch_stats = _put(mesh, batch_stats, P())
    if overlap_mode != "off":
        # Backward-overlap plane (--overlap {bucket,bucket+zero1}): one
        # fused collective per gradient bucket, emitted inside the
        # backward; zero1 additionally shards the optimizer update.
        from horovod_tpu.optim.overlap import OverlapPlan

        plan = OverlapPlan(params, optax.sgd(0.01, momentum=0.9),
                           mode=overlap_mode, bucket_mb=grad_bucket_mb,
                           mesh=mesh)
        spec = plan.state_spec()

        def local_step(ostate, batch_stats, images, labels):
            body = plan.local_step(
                make_loss_fn(batch_stats, images, labels), has_aux=True
            )
            ostate, loss, new_stats = body(ostate)
            return ostate, new_stats, loss

        step = jax.jit(
            shard_map_compat(
                local_step,
                mesh=mesh,
                in_specs=(spec, P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
                out_specs=(spec, P(), P()),
            ),
            donate_argnums=(0, 1),
        )
        state = (plan.init(params), batch_stats, images, labels)
        return step, state, {"n_chips": n_chips,
                             "global_batch": global_batch,
                             "carry_len": 2}

    tx = DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=hvd.Compression.none
    )
    params = _put(mesh, params, P())
    opt_state = _put(mesh, tx.init(params), P())

    def local_step(params, batch_stats, opt_state, images, labels):
        loss_fn = make_loss_fn(batch_stats, images, labels)
        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    step = jax.jit(
        shard_map_compat(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
            out_specs=(P(), P(), P(), P()),
        ),
        donate_argnums=(0, 1, 2),
    )
    state = (params, batch_stats, opt_state, images, labels)
    return step, state, {"n_chips": n_chips, "global_batch": global_batch,
                         "carry_len": 3}


def _run_serve_load(args, np_: int, width: int, on_cpu: bool,
                    frontends: int = 1) -> dict:
    """One fleet under one open-loop workload: launch ``np_`` serving
    ranks (``width`` >= 1 turns on the width-sharded fleet — np_//width
    independent serving groups, each rank's paged decode shard_mapped
    over ``width`` local devices; ``frontends`` > 1 shards the front
    door into that many rid-hash-partitioned ingest pumps), submit the
    deterministic mixed-length request schedule, and measure ttft/tpot/
    tokens-per-sec on the client clock.  Returns the raw measurement
    dict the record (or the scaling comparison) embeds."""
    import threading

    from horovod_tpu.serve import ServeJob

    overrides = dict(
        num_layers=2, num_heads=4, emb_dim=64, max_len=256,
        vocab_size=512, attention_impl="reference", dtype=jnp.float32,
    )
    spec = {"size": "nano", "overrides": overrides, "seed": 0,
            "num_slots": args.serve_slots, "idle_secs": 0.005,
            # Stream batching at 8: the first token still publishes
            # immediately (ttft is real), but steady-state streaming
            # costs half the signed puts — on a CPU fleet the store
            # roundtrips are a measurable slice of the step.
            "stream_every": 8,
            "kv_mode": args.serve_kv_mode,
            "page_size": args.serve_page_size,
            "width": width,
            "frontends": max(int(frontends), 1)}
    if args.serve_kv_pages:
        spec["kv_pages"] = args.serve_kv_pages
    env = {"JAX_PLATFORMS": "cpu"} if on_cpu else {}
    if on_cpu:
        # Single-threaded eigen per worker: the serving model is tiny,
        # so the default all-cores threadpool buys nothing per process
        # and makes concurrent fleet members thrash each other —
        # exactly what a scaling comparison must not measure.  Width
        # shards additionally need `width` local devices (faked the
        # same way the test harness does).
        flags = ["--xla_cpu_multi_thread_eigen=false"]
        if width > 1:
            flags.append(
                f"--xla_force_host_platform_device_count={width}"
            )
        env["XLA_FLAGS"] = " ".join(flags)
    n_req = args.serve_requests
    # Mixed-length workload, identical across fleets (and across the
    # two legs of a --serve-scaling comparison): prompt lengths span
    # 2-6 KV pages at the default page size, so the paged pool's
    # partial-last-page waste is measured on realistic traffic, not on
    # single-page stubs.
    rng = np.random.RandomState(42)
    gaps = rng.exponential(1.0 / args.serve_rate, n_req)
    prompts = [rng.randint(0, 512, rng.randint(16, 49)).tolist()
               for _ in range(n_req)]
    budgets = [int(rng.randint(16, 33)) for _ in range(n_req)]

    t_end = time.monotonic() + SERVE_TIMEOUT_SECS
    job = ServeJob(
        spec, np=np_, env=env or None, timeout=SERVE_TIMEOUT_SECS,
    ).start()
    # Warmup OUTSIDE the measured window: one request per prompt-length
    # bucket the workload will hit (16/32/64) drives every rank through
    # its decode-step + per-bucket assign compiles.  Without this the
    # measurement is compile-dominated and a fleet comparison measures
    # XLA, not serving.  A width-sharded fleet partitions the log
    # round-robin across its groups, so each bucket is submitted
    # ``groups`` consecutive times — consecutive log indices land one
    # on every group, whatever the group count — or a group would pay
    # its first bucket-b compile mid-measurement (~500ms observed, a
    # third of the whole window).
    groups = max(np_ // width, 1) if width else 1
    warm = []
    for warm_len in (10, 20, 40):
        for _ in range(groups):
            warm.append(job.client.submit([7] * warm_len,
                                          max_new_tokens=9))
    for rid in warm:
        job.client.result(rid, timeout=max(t_end - time.monotonic(), 1))
    submit_t: dict = {}
    rids: list = []
    fd_stats: dict = {}

    def _submitter():
        t = time.perf_counter()
        for i in range(n_req):
            t += gaps[i]
            now = time.perf_counter()
            if t > now:
                time.sleep(t - now)
            rid = job.client.submit(prompts[i],
                                    max_new_tokens=budgets[i])
            submit_t[rid] = time.perf_counter()
            rids.append(rid)

    try:
        sub = threading.Thread(target=_submitter, daemon=True)
        t_start = time.perf_counter()
        t_start_wall = time.time()
        sub.start()
        first_t: dict = {}
        done: dict = {}
        while len(done) < n_req:
            if time.monotonic() > t_end:
                raise TimeoutError(
                    f"serve bench: {len(done)}/{n_req} requests "
                    f"finished within {SERVE_TIMEOUT_SECS:.0f}s"
                )
            for rid in list(rids):
                if rid in done:
                    continue
                doc = job.client.poll(rid)
                if doc is None:
                    continue
                if doc.get("tokens") and rid not in first_t:
                    first_t[rid] = time.perf_counter()
                if doc.get("done"):
                    done[rid] = (time.perf_counter(),
                                 len(doc.get("tokens", [])),
                                 doc.get("t_done"))
            # A full sweep already costs ~0.5ms of server time per
            # pending rid; sweeping again immediately would make the
            # measuring client the store's biggest tenant and depress
            # exactly the number being measured.
            time.sleep(0.01)
        sub.join(timeout=10)
        total_tokens = sum(n for _, n, _ in done.values())
        # Throughput from SERVER-side completion stamps (the leaders'
        # eviction wall clocks) against the client's submit wall clock
        # — one host in this harness, so the clocks agree.  The
        # client's own polling cadence would otherwise be the largest
        # term in a fleet comparison (poll-granularity error per
        # request exceeded the per-step decode time).
        server_ends = [t for _, _, t in done.values() if t]
        if server_ends:
            elapsed = max(server_ends) - t_start_wall
        else:  # pre-t_done servers: fall back to the client clock
            elapsed = max(t for t, _, _ in done.values()) - t_start
        # SUSTAINED rate: tokens completed in the p20->p80 completion
        # window over that window's duration — the steady-state number
        # with the ramp (first admissions/prefills) and the drain tail
        # (last <slots requests trickling out) excluded.  Makespan
        # throughput stays the headline `value`; the scaling ratio is
        # judged on sustained (both fleets fully busy), which is what
        # "sustains N tokens/sec" means.
        sustained = None
        if len(server_ends) >= 10:
            ends = sorted(
                (t, n) for t, n in
                ((t, n) for _, n, t in done.values() if t)
            )
            lo = ends[int(len(ends) * 0.2)][0]
            hi = ends[int(len(ends) * 0.8)][0]
            mid_tokens = sum(n for t, n in ends if lo < t <= hi)
            if hi > lo:
                sustained = mid_tokens / (hi - lo)
        ttft = [
            (first_t[r] - submit_t[r]) * 1000.0
            for r in rids if r in first_t
        ]
        tpot = [
            (done[r][0] - first_t[r]) / max(done[r][1] - 1, 1) * 1000.0
            for r in rids if r in first_t and done[r][1] > 1
        ]
        results, _ejob = job.stop()
        # Per-shard ingest accounting from the front door itself —
        # counters survive stop(); a lopsided split here means the rid
        # hash is mixing badly, not that a pump is slow.
        fd_stats = job.front_door.stats()
    finally:
        job.shutdown()

    def pct(xs, q):
        return round(float(np.percentile(xs, q)), 2) if xs else None

    throughput = total_tokens / max(elapsed, 1e-9)
    meas = {
        "np": np_,
        "width": width,
        "frontends": max(int(frontends), 1),
        "groups": max(np_ // width, 1) if width else 1,
        "slots": args.serve_slots,
        "requests": n_req,
        "arrival_rate_per_sec": args.serve_rate,
        "total_tokens": total_tokens,
        "tokens_per_sec": round(throughput, 2),
        "sustained_tokens_per_sec": (round(sustained, 2)
                                     if sustained else None),
        "ttft_ms": {"p50": pct(ttft, 50), "p90": pct(ttft, 90),
                    "p99": pct(ttft, 99)},
        "tpot_ms": {"p50": pct(tpot, 50), "p90": pct(tpot, 90),
                    "p99": pct(tpot, 99)},
    }
    if fd_stats:
        meas["frontdoor"] = {
            "frontends": fd_stats.get("frontends"),
            "fd_epoch": fd_stats.get("fd_epoch"),
            "takeovers": fd_stats.get("takeovers"),
            "ingested_by_shard": {
                str(s): n for s, n in sorted(
                    (fd_stats.get("ingested_by_shard") or {}).items())
            },
        }
    ranks = sorted(results or {})
    meas["_results"] = results or {}
    if ranks:
        meas["completed_per_rank"] = {
            str(r): results[r]["completed"] for r in ranks
        }
        # Continuous batching actually happened: admissions that entered
        # while other slots were mid-decode (max across ranks — the
        # counts are identical by the schedule invariant).
        meas["admitted_while_busy"] = max(
            results[r].get("admitted_while_busy", 0) for r in ranks
        )
        # KV-occupancy verdict (worst rank): the paged pool's measured
        # waste and, recomputed on the SAME traffic, what the PR-10
        # contiguous reservation would have wasted (the PR-14 baseline).
        kvs = [results[r]["kv"] for r in ranks if results[r].get("kv")]
        if kvs:
            meas["kv"] = {
                "mode": kvs[0].get("mode"),
                "waste_ratio_mean": round(max(
                    k.get("waste_ratio_mean", 0.0) for k in kvs), 4),
                "contiguous_equiv_waste_mean": round(max(
                    k.get("contiguous_equiv_waste_mean", 0.0)
                    for k in kvs), 4),
                "page_size": kvs[0].get("page_size"),
                "num_pages": kvs[0].get("num_pages"),
                "pool_bytes": kvs[0].get("pool_bytes"),
            }
    return meas


def _serve_bench(args) -> int:
    """``--serve``: open-loop serving benchmark through the
    continuous-batching plane (horovod_tpu/serve/).

    A deterministic Poisson arrival process (seeded exponential gaps at
    ``--serve-rate`` req/s) submits ``--serve-requests`` mixed-length
    prompts AT SCHEDULE — open-loop, so queueing under load is measured
    instead of hidden by back-pressure — while a fine-grained poller
    stamps each request's first token and completion on the client
    clock.  The record lands ttft/tpot percentiles, end-to-end
    tokens/sec, and the paged pool's KV-waste verdict against the
    contiguous-equivalent baseline; ``--serve-scaling`` additionally
    runs the SAME workload at np=w and np=2w (w = --serve-width or 1)
    and embeds the fleet-scaling ratio — the width-sharded fleet's "np
    multiplies tokens/sec" claim measured, not asserted.  With --cpu it
    is a degraded dry run like every other CPU bench number.

    This process never initialises a backend: the chip belongs to the
    serving ranks ``ServeJob`` spawns, and each reports the device it
    ran on in its drain summary — the record's ``device`` comes from
    there, and anything but ``tpu`` without --cpu is an error."""
    on_cpu = args.cpu
    width = int(args.serve_width or 0)
    fd = max(int(getattr(args, "serve_frontends", 0) or 0), 0)
    frontdoor_scaling = None
    if fd > 1 and not args.serve_scaling:
        # Front-door comparison (PR-16): the SAME saturating trace
        # through a single-pump door and through an F-way sharded one.
        # On one host this measures ingest-path structure (per-shard
        # cursors, no cross-shard serialization), not network fan-in —
        # labeled as such below, same honesty rule as --serve-scaling.
        single = _run_serve_load(args, args.serve_np, width, on_cpu,
                                 frontends=1)
        single.pop("_results", None)
        main = _run_serve_load(args, args.serve_np, width, on_cpu,
                               frontends=fd)
        results = main.pop("_results")
        scaling = None
        ratio = (main["tokens_per_sec"]
                 / max(single["tokens_per_sec"], 1e-9))
        frontdoor_scaling = {
            "f1": {k: v for k, v in single.items()
                   if k != "completed_per_rank"},
            f"f{fd}": {k: v for k, v in main.items()
                       if k != "completed_per_rank"},
            "tokens_per_sec_ratio": round(ratio, 3),
            "provenance": ("cpu-mesh structural evidence"
                           if on_cpu else "device measurement"),
        }
    elif args.serve_scaling:
        w = max(width, 1)
        attempts = max(int(args.serve_scaling_attempts), 1)
        # Best-of-N per leg: this host's scheduler sometimes lands two
        # hot worker threads on SMT siblings and the whole run (both
        # groups alike) decodes at half speed — a bimodal environment
        # artifact, observed on single-fleet runs too.  Best-of is the
        # standard mitigation and is labeled in the record.
        def _rate(m):
            return m["sustained_tokens_per_sec"] or m["tokens_per_sec"]

        base = max((_run_serve_load(args, w, w, on_cpu)
                    for _ in range(attempts)), key=_rate)
        doubled = max((_run_serve_load(args, 2 * w, w, on_cpu)
                       for _ in range(attempts)), key=_rate)
        ratio = _rate(doubled) / max(_rate(base), 1e-9)
        # The basis must describe what was ACTUALLY divided: a leg with
        # too few server-side completion stamps falls back to makespan
        # throughput, and a mislabeled record would judge the >=1.7x
        # claim on a basis it misdescribes.
        both_sustained = (base["sustained_tokens_per_sec"] is not None
                          and doubled["sustained_tokens_per_sec"]
                          is not None)
        basis = ("sustained (p20-p80 completion window)"
                 if both_sustained else "makespan tokens_per_sec")
        main, results = doubled, doubled.pop("_results")
        base.pop("_results", None)
        scaling = {
            "np_w": {k: v for k, v in base.items()
                     if k != "completed_per_rank"},
            "np_2w": {k: v for k, v in doubled.items()
                      if k != "completed_per_rank"},
            "tokens_per_sec_ratio": round(ratio, 3),
            "ratio_basis": basis,
            "best_of": attempts,
            # Honest provenance: on the CPU mesh each rank simulates
            # its whole device set, so the ratio is structural evidence
            # of the fleet partition (independent groups over the log),
            # not a hardware throughput claim.
            "provenance": ("cpu-mesh structural evidence"
                           if on_cpu else "device measurement"),
        }
    else:
        main = _run_serve_load(args, args.serve_np, width, on_cpu,
                               frontends=max(fd, 1))
        results = main.pop("_results")
        scaling = None

    ranks = sorted(results or {})
    if not ranks:
        raise RuntimeError("serve bench: no rank returned a summary")
    device = results[ranks[0]]["device"]
    if not on_cpu and device["platform"] != "tpu":
        raise RuntimeError(
            f"serve bench: rank {ranks[0]} served on platform "
            f"{device['platform']!r}, not 'tpu' (pass --cpu for the dry "
            "run)"
        )
    out = {
        "metric": "serve_nano_tokens_per_sec",
        "value": main["tokens_per_sec"],
        "unit": "tokens/sec",
        "device": device["kind"],
        "provenance": backend_provenance(device),
        "serve": {k: v for k, v in main.items()},
    }
    if scaling is not None:
        out["serve"]["scaling"] = scaling
    if frontdoor_scaling is not None:
        out["serve"]["frontdoor_scaling"] = frontdoor_scaling
    # Decode-step MFU from the serving ranks' own cost_analysis()
    # accounting (estimate-flagged on CPU) — the leader's view; the
    # numbers are near-identical across ranks by the identical-
    # schedule invariant.
    perf = results[ranks[0]].get("perf")
    if perf:
        out["perf"] = perf
    # Worker-side memory breakdown (obs/memplane.py): census +
    # per-program compiled bytes + the KV pool's resident
    # footprint — rank 0's view stands in for all.
    mem = results[ranks[0]].get("memory")
    if mem:
        out["memory"] = mem
    # Decode-step anatomy from the leader's perf summary (no training
    # collectives on the serve path, so the split is compute vs host
    # gap) — attached before the degraded-record path, same rule as the
    # training bench.
    try:
        from horovod_tpu.obs.anatomy import attach_anatomy  # noqa: PLC0415

        perf = out.get("perf") or {}
        attach_anatomy(
            out, step_ms=perf.get("step_ms"), mfu=perf.get("mfu"),
            flops_per_step=perf.get("flops_per_step"),
            device_kind=device["kind"],
        )
    except Exception:
        pass
    if on_cpu:
        out["degraded"] = True
    # Sentinel BEFORE the record write, same rule as the training path.
    attach_regression(out)
    if on_cpu:
        _auto_record("cpu dry run: numbers not comparable to TPU "
                     "records", rc=0, phase="serve-cpu-dry-run",
                     parsed=out, device=device)
    print(json.dumps(out), flush=True)
    return 0


def attach_regression(out: dict, record_dir: str = None,
                      threshold_pct: float = 5.0) -> dict:
    """Trend-aware regression sentinel over the ``BENCH_*.json``
    trajectory (obs/trend.py owns the record reading/classification).

    The baseline is the EWMA over the last K non-degraded records
    matching this run's metric AND device (a CPU dev run must never be
    judged against a TPU record) — one lucky round no longer owns the
    bar.  The embedded delta carries ``baseline_records`` provenance
    (which records the EWMA folded), ``stale_records_skipped`` counts
    the newer records with no comparable measurement (a stale
    baseline, self-announcing), ``degraded_records_skipped`` counts
    the fallback records the baseline refused, and ``regression`` flags
    a value drop > ``threshold_pct``% vs the EWMA.  Every record also
    gets the ``trend`` stamp — the degraded-streak verdict ("N
    consecutive records without a real measurement, last real is rX")
    rides in the measurement itself.

    Best-effort by construction: any failure here must never sink the
    measurement it annotates.
    """
    try:
        from horovod_tpu.obs import trend as _trend  # noqa: PLC0415

        d = record_dir or os.path.dirname(os.path.abspath(__file__))
        records = _trend.load_bench_records(d)
        stamp = _trend.trend_stamp(d)
        if stamp is not None:
            out["trend"] = stamp
        key = (out.get("metric"), out.get("device"))
        newest = None  # newest real matching record: (fname, parsed)
        skipped = 0
        degraded_skipped = 0
        for _, fname, doc in reversed(records):
            parsed = _trend.parsed_payload(doc)
            # Degraded records (write_degraded_record) keep the
            # trajectory visible but are never a regression baseline: a
            # failed round must not reset the bar a real measurement is
            # judged against.
            if _trend.classify(doc) == "degraded":
                degraded_skipped += 1
                continue
            if (isinstance(parsed, dict)
                    and _trend.scenario_key(parsed) == key):
                newest = (fname, parsed)
                break
            skipped += 1
        ewma = _trend.ewma_baseline(records, *key)
        if newest is None or ewma is None:
            out["baseline_record"] = {
                "file": None,
                "stale_records_skipped": skipped,
                "degraded_records_skipped": degraded_skipped,
            }
            out["regression"] = None  # nothing comparable to regress from
            return out
        fname, parsed = newest
        deltas = {}
        for key_name in ("value", "mfu"):
            old, new = ewma.get(key_name), out.get(key_name)
            if (isinstance(old, (int, float)) and isinstance(new, (int, float))
                    and old):
                deltas[key_name] = {
                    "baseline": old,
                    "pct": round((new - old) / old * 100.0, 2),
                }
        # Peak device-memory delta, INFORMATIONAL only: memory growth
        # is worth seeing next to the perf number (a +20% throughput
        # that costs 2x HBM changes the item-5 bucket-size choice), but
        # it never flips the regression flag — the flag means "the
        # measurement got worse", and more bytes is not that.
        def _peak(doc):
            dev = ((doc.get("memory") or {}).get("census") or {}
                   ).get("device") or {}
            return dev.get("peak_bytes") or (
                (doc.get("memory") or {}).get("census") or {}
            ).get("total_bytes")

        old_peak, new_peak = _peak(parsed), _peak(out)
        if (isinstance(old_peak, (int, float)) and old_peak
                and isinstance(new_peak, (int, float))):
            deltas["peak_bytes"] = {
                "baseline": old_peak,
                "pct": round((new_peak - old_peak) / old_peak * 100.0, 2),
                "informational": True,
            }
        out["baseline_record"] = {
            "file": fname,
            "baseline_records": ewma["records"],
            "ewma": {"k": ewma["k"], "alpha": ewma["alpha"],
                     "count": ewma["count"]},
            "stale_records_skipped": skipped,
            "degraded_records_skipped": degraded_skipped,
            "stale": skipped > 0,
        }
        out["deltas"] = deltas
        out["regression"] = bool(
            deltas.get("value", {}).get("pct", 0.0) < -threshold_pct
        )
    except Exception:
        out.setdefault("regression", None)
    return out


def collect_engine_gauges() -> dict:
    """Snapshot the autotuner + negotiation-skip gauges out of the
    metrics registry (empty on the world==1 jit path, which never starts
    the engine) — every BENCH record carries what the tuner and the
    replay fast path were doing when the number was taken."""
    try:
        from horovod_tpu.obs import get_registry

        wanted_prefixes = ("autotune.", "overlap.", "perf.", "mem.",
                           "serve.kv.", "health.")
        wanted_names = {
            "engine.negotiation_skip_rate",
            "engine.cache_hit_rate",
            "engine.stats.cycles",
            "engine.stats.negotiated_cycles",
            "engine.stats.replay_cycles",
            "engine.stats.replay_epochs",
            "engine.stats.replay_breaks",
            # Two-fabric counters (multislice): what the DCN actually
            # carried vs ICI, and the DCN wire compression factor.
            "engine.dcn_bytes",
            "engine.ici_bytes",
            "engine.dcn_compression_ratio",
        }
        out = {}
        bucket_bytes = []
        health_alerts = 0.0
        for m in get_registry().snapshot():
            name = m.get("name", "")
            if m.get("tags"):
                # Per-bucket byte gauges are the one tagged family a
                # BENCH record wants whole: the next TPU round needs to
                # attribute an MFU delta to the bucket shape, not just
                # the bucket count.
                if name == "overlap.bucket_bytes":
                    tag = m["tags"].get("bucket")
                    if tag is not None and str(tag).isdigit():
                        bucket_bytes.append((int(tag), m.get("value")))
                elif name == "health.alerts":
                    # Rising-edge alert counters are per-class; the
                    # BENCH record wants the one number "did the
                    # numerics plane object during this measurement".
                    health_alerts += float(m.get("value") or 0)
                continue
            if name == "health.grad_norm_hist":
                # Histogram: the record carries its p50 (the satellite
                # the hardware campaign attaches numerics evidence by).
                if m.get("p50") is not None:
                    out["health.grad_norm_p50"] = m["p50"]
                continue
            if name in wanted_names or name.startswith(wanted_prefixes):
                out[name] = m.get("value")
        if health_alerts:
            out["health.alerts_total"] = health_alerts
        if bucket_bytes:
            out["overlap_bucket_bytes"] = [
                v for _, v in sorted(bucket_bytes)
            ]
        if "overlap.mode" in out:
            try:
                from horovod_tpu.optim.overlap import MODES

                out["overlap_mode"] = MODES[int(out["overlap.mode"])]
            except Exception:
                pass
        return out
    except Exception:
        return {}


def _require_tpu(platform: str) -> None:
    """Without --cpu a measurement needs the chip: any other platform
    is an error before anything is built, never a CPU number under a
    device metric's name."""
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: JAX platform is {platform!r}, not 'tpu' — a "
            "measurement needs the chip.  Pass --cpu for the tiny dry "
            "run (its numbers are marked degraded)."
        )


def _platform_seen_by_child() -> str:
    """The platform a fresh process finds, asked of a child that exits
    before anything else starts — so this process never holds the chip
    its serving ranks need."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise SystemExit(
            "bench.py: no JAX backend could be initialised:\n"
            + proc.stderr[-2000:]
        )
    return proc.stdout.strip().splitlines()[-1]


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet101", "resnet18",
                                 "vgg16", "vgg19", "inception3",
                                 "gpt-small", "gpt-medium", "gpt-large"])
    parser.add_argument("--dtype", default="bf16",
                        choices=["bf16", "fp32", "fp8"],
                        help="compute dtype (params/accumulators stay fp32; "
                        "fp8 = bf16 compute with e4m3 activation storage)")
    parser.add_argument("--batch-size", type=int, default=None,
                        help="per-chip batch (default: 128 resnet, 8 gpt)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=1024,
                        help="sequence length for the gpt models")
    parser.add_argument("--attention", default="flash",
                        choices=["flash", "reference"],
                        help="gpt attention schedule (flash = Pallas kernel)")
    parser.add_argument("--remat", action="store_true",
                        help="remat transformer blocks (dots-saveable "
                        "policy): trades recompute for HBM -> larger batch")
    parser.add_argument("--flash-block-q", type=int, default=512,
                        help="flash attention q tile")
    parser.add_argument("--flash-block-k", type=int, default=256)
    parser.add_argument("--kv-heads", type=int, default=0,
                        help="GQA/MQA kv heads for the gpt models "
                        "(0 = MHA)")
    parser.add_argument("--pos-embedding", default="learned",
                        choices=["learned", "rope"])
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="replace gpt MLPs with this many experts "
                        "(0 = dense); aux loss folded into the objective")
    parser.add_argument("--attention-window", type=int, default=0,
                        help="sliding-window attention (last W keys; "
                        "0 = full causal); flash-only, banded tiles "
                        "skipped in fwd+bwd")
    parser.add_argument("--iters", type=int, default=10,
                        help="timed steps (the medium is +-3% run-to-run; "
                        "more iters buys nothing but window risk)")
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--s2d-stem", action="store_true",
                        help="space-to-depth stem (MLPerf TPU recipe)")
    parser.add_argument("--cpu", action="store_true",
                        help="force CPU (dev mode; numbers not comparable)")
    parser.add_argument("--overlap", default=None,
                        choices=["off", "bucket", "bucket+zero1"],
                        help="backward-overlap gradient plane: bucket = "
                        "in-backward bucketed allreduce, bucket+zero1 "
                        "additionally reduce-scatter-shards the "
                        "optimizer update (default: HVDTPU_OVERLAP or "
                        "off)")
    parser.add_argument("--grad-bucket-mb", type=float, default=None,
                        help="gradient bucket size cap for --overlap "
                        "(default: HVDTPU_GRAD_BUCKET_MB or 16; sweep "
                        "candidates: autotune.grad_bucket_candidates)")
    parser.add_argument("--num-slices", type=int, default=0,
                        help="force a multislice partition "
                        "(HVDTPU_NUM_SLICES) so the record embeds the "
                        "per-fabric byte counters; 0 = discovered "
                        "topology")
    parser.add_argument("--serve", action="store_true",
                        help="serving-plane benchmark: open-loop "
                             "arrivals through the continuous-batching "
                             "scheduler; lands ttft/tpot percentiles "
                             "and tokens/sec instead of a training "
                             "step time")
    parser.add_argument("--serve-np", type=int, default=1,
                        help="serving ranks (elastic fleet size)")
    parser.add_argument("--serve-slots", type=int, default=4,
                        help="decode slot pool size per rank")
    parser.add_argument("--serve-requests", type=int, default=16,
                        help="requests in the open-loop arrival trace")
    parser.add_argument("--serve-rate", type=float, default=4.0,
                        help="mean arrival rate, requests/sec "
                             "(seeded exponential gaps)")
    parser.add_argument("--serve-width", type=int, default=0,
                        help="width-sharded fleet (0 = replicated): "
                             "np//width serving groups, each rank's "
                             "paged decode shard_mapped over width "
                             "devices")
    parser.add_argument("--serve-kv-mode", default="paged",
                        choices=["paged", "contiguous"],
                        help="KV layout (paged = block tables; "
                             "contiguous = PR-10 worst-case rows)")
    parser.add_argument("--serve-page-size", type=int, default=8,
                        help="KV page size in token rows (paged mode)")
    parser.add_argument("--serve-kv-pages", type=int, default=0,
                        help="KV page-pool size (0 = worst case)")
    parser.add_argument("--serve-scaling", action="store_true",
                        help="run the same workload at np=w and np=2w "
                             "(w = --serve-width or 1) and embed the "
                             "fleet-scaling tokens/sec ratio")
    parser.add_argument("--serve-scaling-attempts", type=int, default=2,
                        help="best-of-N runs per scaling leg (host-"
                             "scheduler noise mitigation; labeled in "
                             "the record)")
    parser.add_argument("--frontends", type=int, default=0,
                        dest="serve_frontends",
                        help="sharded front door: run the workload with "
                             "F frontend ingest shards; F>1 also runs "
                             "an F=1 leg on the same trace and embeds "
                             "the ingest comparison + per-shard "
                             "counters in the record")
    parser.add_argument("--campaign", default=None, metavar="SPEC",
                        help="run a resumable benchmark campaign from "
                        "this sweep-spec JSON instead of one "
                        "measurement (delegates to python -m "
                        "horovod_tpu.bench.campaign; see "
                        "docs/performance.md 'Running a campaign')")
    args = parser.parse_args()
    if args.campaign:
        # Campaign mode: this process becomes the sweep driver — each
        # point runs as its own bench.py subprocess, so the driver
        # itself never initialises a backend.
        from horovod_tpu.bench.campaign import main as campaign_main

        return campaign_main(["--spec", args.campaign])

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.num_slices > 0:
        # Before hvd.init(): the slice partition is resolved there.
        os.environ["HVDTPU_NUM_SLICES"] = str(args.num_slices)

    if args.serve:
        # The chip belongs to the serving ranks: this process asks a
        # child that exits what platform a rank will find, and stays
        # off the backend itself.
        if not args.cpu:
            _require_tpu(_platform_seen_by_child())
        try:
            return _serve_bench(args)
        except Exception as exc:
            # A failed serve round still lands a record where a
            # campaign asked for one.
            _auto_record(f"{type(exc).__name__}: {exc}"[:2000], rc=1,
                         phase="serve")
            raise

    if not args.cpu:
        _require_tpu(jax.devices()[0].platform)
    enable_compile_cache()
    if args.overlap is None:
        args.overlap = os.environ.get("HVDTPU_OVERLAP", "off")
        if args.overlap not in ("off", "bucket", "bucket+zero1"):
            raise SystemExit(
                f"HVDTPU_OVERLAP={args.overlap!r}: choices are off, "
                f"bucket, bucket+zero1"
            )
    is_gpt = args.model.startswith("gpt-")
    if args.batch_size is None:
        args.batch_size = 8 if is_gpt else 128
    # Compiled cost analysis of the ACTUAL step: fwd+bwd+optimizer FLOPs as
    # XLA counts them post-fusion — no hand-derived 3x-forward estimates.
    # The AOT executable is also what we run (one compilation, not two);
    # cost_analysis is the post-SPMD-partitioning PER-DEVICE module, so
    # everything downstream is per-chip accounting.
    phase = "build"
    try:
        if is_gpt:
            step, state, static = build_gpt_step(
                args.model[len("gpt-"):], args.dtype, args.batch_size,
                args.seq_len, attention=args.attention, remat=args.remat,
                flash_block_q=args.flash_block_q,
                flash_block_k=args.flash_block_k,
                kv_heads=args.kv_heads, pos_embedding=args.pos_embedding,
                moe_experts=args.moe_experts,
                attention_window=args.attention_window,
                overlap_mode=args.overlap,
                grad_bucket_mb=args.grad_bucket_mb,
            )
        else:
            step, state, static = build_step(
                args.model, args.dtype, args.batch_size, args.image_size,
                s2d_stem=args.s2d_stem, overlap_mode=args.overlap,
                grad_bucket_mb=args.grad_bucket_mb,
            )
        ncarry = static["carry_len"]
        carry, const = state[:ncarry], state[ncarry:]
        n_chips = static["n_chips"]
        global_batch = static["global_batch"]
        phase = "compile"
        compiled = step.lower(*carry, *const).compile()
        phase = "warmup"
        # Memory plane (obs/memplane.py): the train step's artifact-
        # derived breakdown, owner tags over the live state (the
        # closures read the CURRENT carry — it is rebound every
        # iteration), and the census collector so every registry
        # snapshot below carries mem.* gauges.  Best-effort: memory
        # accounting must never sink a measurement.
        try:
            from horovod_tpu.obs import memplane  # noqa: PLC0415

            memplane.register_program(
                f"train_step.{args.overlap}", compiled
            )
            _overlap_on = args.overlap != "off"

            def _params_now():
                c = carry[0]
                return c[0] if _overlap_on else c

            def _opt_now():
                if _overlap_on:
                    return carry[0][1]
                return carry[1] if len(carry) > 1 else None

            memplane.register_owner("params", _params_now)
            memplane.register_owner("optimizer_state", _opt_now)
            memplane.install_census()
        except Exception:
            pass
        # Donation audit: params/opt_state must stay aliased end-to-end
        # through whichever step wrapper built the program (donation
        # silently degrades to a copy on mismatch, so check the
        # artifact).  Best-effort: never sinks the measurement.
        try:
            from horovod_tpu.optim.overlap import audit_donation

            donation_audit = audit_donation(
                compiled, len(jax.tree_util.tree_leaves(carry))
            )
        except Exception:
            donation_audit = None
        from horovod_tpu.obs.profile import (  # noqa: PLC0415
            flops_from_compiled,
        )

        _ca_flops = flops_from_compiled(compiled)
        flops_per_step_per_chip = (
            float(_ca_flops) if _ca_flops is not None else float("nan")
        )
        step = compiled

        loss = None
        for _ in range(args.warmup):
            *carry, loss = step(*carry, *const)
        # The warmup has run on the device (not merely been dispatched)
        # before the timed window opens.
        if loss is not None:
            float(loss)
    except Exception as exc:
        # A failed run still lands a record where a campaign asked for
        # one, naming the phase that failed.
        _auto_record(f"{type(exc).__name__}: {exc}"[:2000], rc=1,
                     phase=phase)
        raise

    t0 = time.perf_counter()
    for _ in range(args.iters):
        *carry, loss = step(*carry, *const)
    final_loss = float(loss)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    items_per_batch = (
        global_batch * args.seq_len if is_gpt else global_batch
    )
    per_chip = items_per_batch * args.iters / elapsed / n_chips
    device = jax.devices()[0]
    peak = peak_flops_per_chip(device, args.dtype)
    achieved_flops_per_chip = flops_per_step_per_chip * args.iters / elapsed
    mfu = achieved_flops_per_chip / peak

    # The live MFU accountant (obs/profile.py): same division, but
    # published as perf.* gauges and embedded estimate-flagged in the
    # record — cost_analysis() FLOPs when the backend exposes them,
    # the analytic per-model formula otherwise, so even a CPU run
    # exercises the full MFU pipeline end-to-end.
    from horovod_tpu.obs.profile import (  # noqa: PLC0415
        MFUProfiler, analytic_step_flops,
    )

    prof_flops = (flops_per_step_per_chip
                  if np.isfinite(flops_per_step_per_chip) else None)
    prof_source = "cost_analysis"
    if prof_flops is None:
        prof_flops = analytic_step_flops(
            args.model, args.batch_size,
            args.seq_len if is_gpt else None, args.image_size,
        )
        prof_source = "analytic"
    profiler = MFUProfiler(prof_flops, device.device_kind,
                           args.dtype, source=prof_source)
    profiler.observe(elapsed / args.iters)
    unit = "tokens/sec/chip" if is_gpt else "images/sec/chip"
    out = {
        "metric": f"{args.model}_{args.dtype}_{unit.replace('/', '_per_')}",
        "value": round(per_chip, 2),
        "unit": unit,
        # the reference publishes no absolute LM throughput; the ratio is
        # only meaningful for the conv-net headline (docs/benchmarks.rst:43)
        "vs_baseline": (
            None if is_gpt
            else round(per_chip / BASELINE_IMG_PER_SEC_PER_ACCEL, 3)
        ),
        "mfu": round(mfu, 4) if np.isfinite(mfu) else None,
        "device": device.device_kind,
        "provenance": backend_provenance(device),
        # Always present, estimate-flagged off-TPU: the record-embedded
        # view of the live perf.* gauges (obs/profile.py).
        "perf": profiler.summary(),
    }
    if not is_gpt and np.isfinite(flops_per_step_per_chip):
        out["flops_per_image"] = round(
            flops_per_step_per_chip / args.batch_size / 1e9, 3
        )
    if args.overlap != "off":
        out["overlap_mode"] = args.overlap
    if donation_audit is not None:
        out["donation"] = donation_audit
    try:
        from horovod_tpu.obs import memplane  # noqa: PLC0415

        out["memory"] = memplane.memory_record()
    except Exception:
        pass
    try:
        # Numerics evidence in every BENCH record (obs/health.py):
        # materialize the headline health gauges from what the timed
        # loop actually measured (its final loss), so every record
        # carries health.loss / health.nonfinite / divergence-check
        # counts even when --health never armed.  Grad-norm series
        # appear only when the measured step itself carried the health
        # bundle — the record does not re-run the step to invent them.
        from horovod_tpu.obs import get_registry  # noqa: PLC0415

        _reg = get_registry()
        _reg.gauge("health.loss").set(final_loss)
        _reg.gauge("health.nonfinite").set(
            0 if np.isfinite(final_loss) else 1)
        # inc(0) materializes the counter at its current value (0 on
        # un-armed runs) without claiming a check happened.
        _reg.counter("health.divergence.checks").inc(0)
        _reg.counter("health.nonfinite_total").inc(0)
    except Exception:
        pass
    gauges = collect_engine_gauges()
    if gauges:
        out["engine_gauges"] = gauges
    try:
        import horovod_tpu as hvd  # noqa: PLC0415

        if hvd.num_slices() > 1:
            out["num_slices"] = hvd.num_slices()
    except Exception:
        pass
    # Step-time anatomy (obs/anatomy.py): compute / collective-wait /
    # host-gap components that tile the measured step time, the top-K
    # HLO op table, and the roofline verdict — attached BEFORE the
    # degraded-record path below so even a CPU fallback record ships
    # its number with the explanation.
    try:
        from horovod_tpu.obs.anatomy import attach_anatomy  # noqa: PLC0415

        attach_anatomy(
            out, step_ms=elapsed / args.iters * 1e3, mfu=out.get("mfu"),
            flops_per_step=prof_flops,
            device_kind=device.device_kind, dtype=args.dtype,
            compiled=compiled, steps_observed=args.warmup + args.iters,
            gauges=gauges,
        )
    except Exception:
        pass
    if args.cpu:
        # A CPU dry run is a trajectory placeholder, not a perf claim:
        # marked degraded in the printed line and in the record a
        # campaign asked for.
        out["degraded"] = True
    # Sentinel BEFORE the record write: the landed record must carry
    # its own trend/regression provenance, not just the stdout line.
    attach_regression(out)
    if args.cpu:
        _auto_record("cpu dry run: numbers not comparable to TPU records",
                     rc=0, phase="cpu-dry-run", parsed=out, device=device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
