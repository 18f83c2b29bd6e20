"""Env knob parsing (reference: horovod/common/utils/env_parser.cc and the
canonical knob list at common.h:62-88).

All runtime configuration converges on environment variables, exactly as in
the reference (SURVEY.md §5.6): the launcher maps CLI flags onto env vars
for every rank; the engine reads them at startup."""

from __future__ import annotations

import os

# Canonical knob names (HVDTPU_* ≙ HOROVOD_* of common.h:62-88).
FUSION_THRESHOLD = "HVDTPU_FUSION_THRESHOLD"
DEFAULT_FUSION_BYTES = 64 * 1024 * 1024  # reference operations.cc:419
CYCLE_TIME = "HVDTPU_CYCLE_TIME"
TIMELINE = "HVDTPU_TIMELINE"
TIMELINE_MARK_CYCLES = "HVDTPU_TIMELINE_MARK_CYCLES"
STALL_CHECK_TIME = "HVDTPU_STALL_CHECK_TIME_SECONDS"
STALL_SHUTDOWN_TIME = "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS"
STALL_CHECK_DISABLE = "HVDTPU_STALL_CHECK_DISABLE"
CACHE_CAPACITY = "HVDTPU_CACHE_CAPACITY"
HIERARCHICAL_ALLREDUCE = "HVDTPU_HIERARCHICAL_ALLREDUCE"
# Multi-slice topology (ICI within a slice, DCN between slices).  The
# slice partition is discovered from the platform when it can be
# (jax Device.slice_index on real multislice deployments) and forced
# otherwise: NUM_SLICES partitions the world into that many contiguous
# equal blocks of processes; SLICE_SIZE is the same knob expressed as
# processes-per-slice (the forced partition that lets every multislice
# code path run on a CPU dev world).  NUM_SLICES wins when both are set.
NUM_SLICES = "HVDTPU_NUM_SLICES"
SLICE_SIZE = "HVDTPU_SLICE_SIZE"
# Wire dtype for the cross-slice (DCN) leg of hierarchical allreduce:
# none (negotiated dtype), bf16, or fp16 (ops/compression.py).  Only the
# 1/local_size shard that crosses DCN is cast; ICI phases stay exact.
DCN_COMPRESSION = "HVDTPU_DCN_COMPRESSION"
AUTOTUNE = "HVDTPU_AUTOTUNE"
AUTOTUNE_LOG = "HVDTPU_AUTOTUNE_LOG"
# Sampling-window knobs (reference common.h:67-69
# HOROVOD_AUTOTUNE_{WARMUP_SAMPLES,STEPS_PER_SAMPLE,BAYES_OPT_MAX_SAMPLES}).
AUTOTUNE_WARMUP_SAMPLES = "HVDTPU_AUTOTUNE_WARMUP_SAMPLES"
AUTOTUNE_STEPS_PER_SAMPLE = "HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE"
AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HVDTPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
AUTOTUNE_GP_NOISE = "HVDTPU_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
# Online-tuner drift detector (no reference analog: the reference tunes
# once and freezes, parameter_manager.cc SetAutoTuning(false); ours keeps
# scoring after convergence and re-opens the GP search when throughput
# regresses by DRIFT_THRESHOLD (fraction) for DRIFT_SAMPLES consecutive
# score windows — elastic world changes and workload phase changes move
# the optimum, and a frozen tuner would hold a stale incumbent forever).
AUTOTUNE_DRIFT_THRESHOLD = "HVDTPU_AUTOTUNE_DRIFT_THRESHOLD"
AUTOTUNE_DRIFT_SAMPLES = "HVDTPU_AUTOTUNE_DRIFT_SAMPLES"
# Backward-overlap gradient plane (optim/overlap.py): gradient-bucket
# size cap in MB for the jit path's in-backward bucketed collectives.
# Unlike fusion_mb, the bucket size is baked into the compiled program
# (moving it forces an XLA recompile), so it is swept offline
# (autotune.grad_bucket_candidates) rather than tuned live.
GRAD_BUCKET_MB = "HVDTPU_GRAD_BUCKET_MB"
DEFAULT_GRAD_BUCKET_MB = 16.0
# Steady-state schedule replay (GSPMD-style static schedule, recreated
# dynamically): after REPLAY_CYCLES consecutive cycles whose executed
# schedule is bitwise-identical on every rank, the engine stops
# exchanging control vectors and replays the memorized fused schedule,
# re-validated by a one-scalar epoch-check lane on the first fused
# buffer of each cycle.  SCHEDULE_REPLAY=0 (--no-schedule-replay) opts
# out; any deviation breaks the epoch back to full negotiation.
SCHEDULE_REPLAY = "HVDTPU_SCHEDULE_REPLAY"
SCHEDULE_REPLAY_CYCLES = "HVDTPU_SCHEDULE_REPLAY_CYCLES"
DEFAULT_REPLAY_CYCLES = 50
LOG_LEVEL = "HVDTPU_LOG_LEVEL"
# Device-resident eager data plane (no reference analog by name: the
# reference's equivalent switch is compile-time HOROVOD_GPU_ALLREDUCE).
EAGER_DEVICE = "HVDTPU_EAGER_DEVICE"
# Per-rank metrics dump target (obs/registry.py); a dir, a {rank}
# template, or a plain path that gets a rank tag inserted.
METRICS_DUMP = "HVDTPU_METRICS_DUMP"
# Live telemetry plane (obs/stream.py + obs/live.py): per-rank metric
# snapshot period in seconds (<= 0 or unset disables streaming) and the
# launcher KV endpoint the snapshots are published to over the
# HMAC-signed PUT path (falls back to HVDTPU_ELASTIC_KV under the
# elastic launcher, which reuses its rendezvous store).
LIVE_STATS = "HVDTPU_LIVE_STATS_SECS"
LIVE_KV = "HVDTPU_LIVE_KV"
# Straggler attribution alert threshold in milliseconds: a collective
# whose first-to-last arrival skew exceeds this warns and counts an
# engine.straggler.alerts event (0/unset = record silently).
ALERT_SKEW = "HVDTPU_ALERT_SKEW_MS"
# Flight recorder (obs/flightrec.py): where each rank dumps its
# in-memory event ring on any death path (same dir/{rank}/plain-path
# forms as METRICS_DUMP; unset = ring records but never dumps), and the
# ring capacity in events (default 512).  The launcher sets the dump
# target itself when the user did not, so crashed jobs always leave a
# black box for obs/postmortem.py.
FLIGHTREC_DUMP = "HVDTPU_FLIGHTREC_DUMP"
FLIGHTREC_CAPACITY = "HVDTPU_FLIGHTREC_CAPACITY"
# Sharded checkpoint + peer-replica recovery tier (ckpt/): CKPT_DIR is
# the sharded-manifest directory the elastic State tier saves to and
# falls back to on restore when no live peer holds a valid replica;
# CKPT_REPLICA turns on the in-memory replica push after every commit
# (each rank mirrors its committed shard to its ring neighbor's key
# over the HMAC-signed KV path, chunked at CKPT_REPLICA_CHUNK_KB);
# CKPT_COMMIT_TIMEOUT bounds the manifest-commit wait on every rank.
CKPT_DIR = "HVDTPU_CKPT_DIR"
CKPT_REPLICA = "HVDTPU_CKPT_REPLICA"
CKPT_REPLICA_CHUNK_KB = "HVDTPU_CKPT_REPLICA_CHUNK_KB"
DEFAULT_REPLICA_CHUNK_KB = 1024
CKPT_COMMIT_TIMEOUT = "HVDTPU_CKPT_COMMIT_TIMEOUT_SECS"
DEFAULT_CKPT_COMMIT_TIMEOUT = 120.0
# Request-level distributed tracing (obs/trace.py): TRACE is the
# per-rank span dump target (same dir/{rank}/plain-path forms as
# METRICS_DUMP, stem "spans"; unset = tracing off, zero hot-path cost).
# TRACE_SAMPLE_RATE is the fraction of requests traced (default 1.0);
# the sampling decision is a pure function of the trace id, so every
# rank and the launcher reach the SAME verdict with no coordination —
# the HVD001 invariant applies to sampling decisions.  TRACE_CAPACITY
# bounds the in-memory span ring per process (default 8192).
TRACE = "HVDTPU_TRACE"
TRACE_SAMPLE_RATE = "HVDTPU_TRACE_SAMPLE_RATE"
TRACE_CAPACITY = "HVDTPU_TRACE_CAPACITY"
# Serving plane (serve/): fleet-wide model geometry the `hvdrun
# --elastic --serve` launcher forwards to every serving rank (the
# python -m horovod_tpu.serve worker reads them as flag fallbacks).
# SERVE_SEED must be identical on every rank — the replicated-params
# determinism the identical-schedule invariant rests on.
SERVE_MODEL = "HVDTPU_SERVE_MODEL"
SERVE_SLOTS = "HVDTPU_SERVE_SLOTS"
SERVE_MAX_LEN = "HVDTPU_SERVE_MAX_LEN"
SERVE_SEED = "HVDTPU_SERVE_SEED"
# Paged KV memory + width-sharded fleets (serve/paged.py, ISSUE 15):
# KV_MODE paged|contiguous, PAGE_SIZE token rows per page, KV_PAGES
# the page-pool size (unset = worst case), WIDTH >= 1 carves the
# world into size//WIDTH serving groups (each independently serving
# its log partition) with each rank's paged decode shard_mapped over
# WIDTH local devices.  All fleet-wide: the block tables and the
# schedule must be identical on every rank of a group.
SERVE_KV_MODE = "HVDTPU_SERVE_KV_MODE"
SERVE_PAGE_SIZE = "HVDTPU_SERVE_PAGE_SIZE"
SERVE_KV_PAGES = "HVDTPU_SERVE_KV_PAGES"
SERVE_WIDTH = "HVDTPU_SERVE_WIDTH"
# Weight hot-swap (serve/hotswap.py): WEIGHTS_DIR is the sharded-
# checkpoint directory a concurrently-training publisher commits
# versions into (unset = hot-swap off); SWAP_POLL_STEPS is the
# leader's manifest-poll cadence in serving steps.  OUT_TTL bounds how
# long the ingest pump retains a FINISHED request's compacted result
# doc for late client polls (request-log compaction, frontend.py).
SERVE_WEIGHTS_DIR = "HVDTPU_SERVE_WEIGHTS_DIR"
SERVE_SWAP_POLL_STEPS = "HVDTPU_SERVE_SWAP_POLL_STEPS"
SERVE_OUT_TTL = "HVDTPU_SERVE_OUT_TTL_SECS"
DEFAULT_SERVE_OUT_TTL = 300.0
# Sharded front door + tenant QoS (ISSUE 16): FRONTENDS is the
# launcher-side shard count F (F ingest pumps, rid-hash routed;
# workers learn it from the serve/frontdoor doc, not this env);
# TENANT_BUDGET arms tenant-aware weighted-fair admission with this
# many tokens per tenant per budget window (fleet-wide — every rank
# must derive the identical admission policy).
SERVE_FRONTENDS = "HVDTPU_SERVE_FRONTENDS"
SERVE_TENANT_BUDGET = "HVDTPU_SERVE_TENANT_BUDGET"
# SLO objectives (obs/slo.py, ISSUE 17): latency targets for one SLO
# class (SLO_CLASS, default "interactive") — TTFT/TPOT ceilings in ms
# and the objective fraction (default 0.99 = 1% error budget).  Fleet-
# wide like the QoS policy: every rank judges the same objectives, so
# they travel the launcher-forwarded env.
SERVE_SLO_CLASS = "HVDTPU_SERVE_SLO_CLASS"
SERVE_SLO_TTFT_MS = "HVDTPU_SERVE_SLO_TTFT_MS"
SERVE_SLO_TPOT_MS = "HVDTPU_SERVE_SLO_TPOT_MS"
SERVE_SLO_OBJECTIVE = "HVDTPU_SERVE_SLO_OBJECTIVE"
# Autoscale (serve/autoscale.py): launcher-local knobs; carried as env
# so config files can set them and operators can see them in ps.  The
# envelope ceiling MAX_WORKERS also sizes the launcher's slot
# allocation (standby ranks need hosts the moment a grow admits them).
SERVE_AUTOSCALE = "HVDTPU_SERVE_AUTOSCALE"
MAX_WORKERS = "HVDTPU_MAX_WORKERS"
SCALE_UP_QUEUE = "HVDTPU_SCALE_UP_QUEUE"
SCALE_DOWN_IDLE_SECS = "HVDTPU_SCALE_DOWN_IDLE_SECS"
SCALE_COOLDOWN_SECS = "HVDTPU_SCALE_COOLDOWN_SECS"
# Training-health plane (obs/health.py, obs/divergence.py, ISSUE 18):
# HEALTH arms the in-graph numerics bundle + anomaly judge ("on"/"off",
# default off — off must leave the compiled step HLO byte-identical);
# HEALTH_CHECK_STEPS is the divergence sentinel's cadence N (digest
# allgather every N steps, default 100); DIVERGENCE_ACTION is what a
# confirmed divergence does: warn | dump | halt.  Fleet-wide: the
# sentinel's exchange is itself a collective, so every rank must derive
# the identical cadence and action (HVD001 applies to the checker too).
HEALTH = "HVDTPU_HEALTH"
HEALTH_CHECK_STEPS = "HVDTPU_HEALTH_CHECK_STEPS"
DIVERGENCE_ACTION = "HVDTPU_DIVERGENCE_ACTION"


def resolve_rank(default=None):
    """This process's rank per the launcher env contract: HVDTPU_RANK
    (static jobs) first, then HVDTPU_ELASTIC_RANK (elastic workers).
    The single definition both the fault injector and the metrics dump
    use — the two must never disagree about which rank a process is."""
    for name in ("HVDTPU_RANK", "HVDTPU_ELASTIC_RANK"):
        value = os.environ.get(name)
        if value not in (None, ""):
            return int(value)
    return default


# The launcher process inherits the job's dump env (METRICS_DUMP,
# FLIGHTREC_DUMP from the user's shell) but has no HVDTPU_RANK, so an
# env-driven artifact dump in the launcher would resolve to rank 0 and
# CLOBBER worker rank 0's evidence.  Launchers self-identify here; their
# artifacts get a distinct "launcher" tag the aggregators ignore.
_is_launcher = False


def mark_launcher() -> None:
    global _is_launcher
    _is_launcher = True


def artifact_rank() -> str:
    """The rank tag per-rank artifact dumps (metrics, flight recorder)
    file under: the resolved rank for workers, ``launcher`` for a
    marked launcher process.  An explicit rank env wins over the
    launcher mark — a process that is both (in-process API tests, or a
    worker driving a sub-job) is a worker first."""
    rank = resolve_rank(None)
    if rank is None and _is_launcher:
        return "launcher"
    return str(rank if rank is not None else 0)


def env_int(name: str, default: int) -> int:
    value = os.environ.get(name)
    return int(value) if value not in (None, "") else default


def env_float(name: str, default: float) -> float:
    value = os.environ.get(name)
    return float(value) if value not in (None, "") else default


def env_bool(name: str, default: bool = False) -> bool:
    value = os.environ.get(name)
    if value in (None, ""):
        return default
    return value.lower() in ("1", "true", "yes", "on")
