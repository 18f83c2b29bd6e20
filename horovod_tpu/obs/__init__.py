"""horovod_tpu.obs — the per-rank observability plane.

One package for the three things a distributed job must be able to tell
you after the fact (PAPER.md §5's debuggability pillars, made
quantitative):

* **metrics** (obs/registry.py) — Counter/Gauge/Histogram instruments
  updated from the engine cycle loop, the stall inspector, checkpoint
  save/restore and every elastic event; dumped per rank as JSON via
  ``HVDTPU_METRICS_DUMP`` and aggregated by the launcher's
  ``--stats-summary`` table (obs/summary.py).
* **progress beat** (obs/progress.py) — a monotonic collectives-
  completed counter piggybacked on the elastic KV heartbeat, plus the
  launcher-side workload-aware staleness policy that kills a rank whose
  beat thread lives but whose training thread is deadlocked.
* **all-rank timeline merge** (obs/timeline_merge.py) — repairs and
  merges the per-rank Chrome traces (runtime/timeline.py) into one
  valid trace with a lane per rank.
* **live telemetry** (obs/stream.py worker side, obs/live.py launcher
  side) — per-rank snapshot deltas streamed over the signed KV path
  while the job runs: console digests, ``live_history.jsonl``, and a
  Prometheus ``GET /metrics`` scrape endpoint on the launcher.
* **straggler attribution** (obs/straggler.py) — which rank arrives
  last at collectives, accumulated as ``engine.straggler.*`` metrics
  from both collective paths, surfaced in the live digest and the
  ``--stats-summary`` straggler section.
* **flight recorder** (obs/flightrec.py) + **post-mortem**
  (obs/postmortem.py) — an always-on bounded per-rank event ring
  flushed on every death path (signals, excepthooks, exit), and the
  launcher-side analyzer that correlates all ranks' rings into a
  root-cause verdict when the job dies.
* **request-level tracing** (obs/trace.py worker+launcher side,
  obs/trace_merge.py consumer) — Dapper-style spans keyed by request
  id (and by step for training), deterministically sampled, dumped per
  rank over the shared pathspec rules and merged into a per-request
  Chrome-trace waterfall plus a ttft/tpot latency-decomposition
  report.
* **MFU profiler** (obs/profile.py) — model-FLOPs accounting
  (the compiled artifact's ``cost_analysis()``) over measured
  step time, published live as ``perf.mfu`` / ``perf.model_tflops`` /
  ``perf.step_ms`` gauges.
* **goodput ledger** (obs/goodput.py) — the wall-clock axis: every
  per-rank second classified (init / compile / productive_step /
  collective_wait / checkpoint / recovery / idle / degraded) off the
  events the flight recorder already emits, published as
  ``goodput.*`` gauges with per-elastic-epoch lost-time attribution
  (rendezvous / respawn / stall), plus the serving-side token-goodput
  variant (``serve.goodput.*``).
* **tenant SLO burn-rate plane** (obs/slo.py) — per-tenant /
  per-SLO-class sliding-window ttft/tpot digests judged against
  ``--slo-ttft-ms``-style targets, with two-window error-budget
  burn-rate alerting (fast window pages on cliffs, slow window warns
  on slow burns), published as ``serve.slo.*``.
* **training-health plane** (obs/health.py + obs/divergence.py) — the
  *numbers* axis: an in-graph per-step numerics bundle (loss, grad
  norms per overlap bucket, update/param ratio, nonfinite counts)
  riding the step's existing host sync, judged by a pure EWMA+MAD
  anomaly table (``health.*`` gauges, rising-edge alerts), plus the
  cross-rank divergence sentinel — periodic bitwise digests of
  params/optimizer state/PRNG key exchanged over the engine, the
  runtime verifier of the HVD001 bitwise-replication invariant, with
  minority-rank + bucket + leaf localization and a serving twin over
  the broadcast schedule doc + KV page tables.
* **memory plane** (obs/memplane.py) — the byte axis: compiled
  per-program breakdowns (``memory_analysis()``, version-tolerant),
  an owner-tagged ``jax.live_arrays()`` census with backend
  ``memory_stats()`` (``mem.*`` gauges, KV-cache occupancy math), and
  the OOM black box (``mem.oom`` flight-recorder events feeding the
  post-mortem's memory verdict).

See docs/observability.md and docs/postmortem.md.
"""

from . import divergence  # noqa: F401
from . import flightrec  # noqa: F401
from . import goodput  # noqa: F401
from . import health  # noqa: F401
from . import memplane  # noqa: F401
from . import slo  # noqa: F401
from . import profile  # noqa: F401
from . import progress  # noqa: F401
from . import straggler  # noqa: F401
from . import stream  # noqa: F401
from . import trace  # noqa: F401
from .registry import (  # noqa: F401
    METRICS_DUMP_ENV,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    dump_metrics,
    get_registry,
    reset_registry,
)

set_phase = progress.set_phase
dump_flight_recorder = flightrec.dump_flight_recorder
install_death_hooks = flightrec.install_death_hooks

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "METRICS_DUMP_ENV",
    "get_registry",
    "reset_registry",
    "dump_metrics",
    "dump_flight_recorder",
    "install_death_hooks",
    "divergence",
    "flightrec",
    "goodput",
    "health",
    "profile",
    "progress",
    "slo",
    "straggler",
    "stream",
    "trace",
    "set_phase",
]
