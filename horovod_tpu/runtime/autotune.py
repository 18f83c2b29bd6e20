"""Runtime parameter autotuning for the eager engine.

Reference: horovod/common/parameter_manager.cc (528 LoC) +
optim/bayesian_optimization.cc + optim/gaussian_process.cc — the reference
tunes {tensor-fusion threshold, cycle time, response-cache enabled,
hierarchical allreduce/allgather} by scoring throughput (bytes/sec) per
sample window and driving Bayesian optimization over a Gaussian process;
rank 0 tunes and broadcasts the winning parameters to all ranks
(controller.cc:33-47 SynchronizeParameters).

TPU redesign: same tunables and the same GP/EI math, but in NumPy instead
of Eigen+lbfgs (hyperparameters are picked by a small marginal-likelihood
grid rather than L-BFGS — the search space is 2-D and tiny).  The
categorical axes (cache on/off, hierarchical on/off) are explored as a
deterministic chain, with the continuous (fusion, cycle) surface tuned by
the GP within each category — mirroring the reference's
CategoricalParameter / BayesianParameter split (parameter_manager.h:59-78).
Parameter sync rides the negotiation: rank 0 attaches tuned params to its
RequestList and every rank applies them on receipt (the descendant of the
reference's param Bcast).

Where this DEPARTS from the reference: the reference calls
``SetAutoTuning(false)`` after one sweep and never moves again; this
tuner is a *continuous controller*.  After the categorical sweep
converges it holds the incumbent but keeps scoring every sample window —
the objective is read from the engine's telemetry plane
(``engine.fusion_bytes``/``engine.cycle_time_ms`` registry instruments:
bytes moved per second of *busy* cycle time, so host idle between steps
cannot convict a good parameter point) — and a drift detector re-opens
the GP search when throughput shows sustained regression (elastic world
change, workload phase change).  Tuner state is published as
``autotune.*`` registry gauges, so ``/metrics`` and the live digest show
what the tuner is doing at any moment.
"""

from __future__ import annotations

import csv
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils import env as envmod

# Continuous search space (log-ish ranges chosen around the reference
# defaults: fusion 64 MB, cycle 5 ms — operations.cc:419,427).
FUSION_BOUNDS_MB = (1.0, 128.0)
CYCLE_BOUNDS_MS = (1.0, 50.0)

# Gradient-bucket size for the jit path's backward-overlap plane
# (optim/overlap.py) — the in-backward analog of fusion_mb.  It is a
# tuning CATEGORY, not a live GP dimension: the bucket boundaries are
# baked into the compiled XLA program, so every move costs a full
# recompile (minutes on TPU) where a fusion_mb move costs one
# negotiation cycle.  The candidate chain below is the offline sweep
# (HVDTPU_GRAD_BUCKET_MB) a deployment walks once per model shape;
# too small → per-collective launch latency dominates, too large → the
# last bucket's wire time has no backward compute left to hide behind
# (docs/performance.md "overlap").
GRAD_BUCKET_BOUNDS_MB = (2.0, 64.0)
DEFAULT_GRAD_BUCKET_MB = envmod.DEFAULT_GRAD_BUCKET_MB


def grad_bucket_candidates() -> List[float]:
    """The geometric bucket-size chain (MB) an offline sweep explores —
    one octave apart inside GRAD_BUCKET_BOUNDS_MB, like the categorical
    chains build_categories() emits for the engine knobs."""
    out, mb = [], GRAD_BUCKET_BOUNDS_MB[0]
    while mb <= GRAD_BUCKET_BOUNDS_MB[1]:
        out.append(mb)
        mb *= 2
    return out


def resolve_grad_bucket_bytes(cli_mb: Optional[float] = None) -> int:
    """The ONE resolution path for the bucket-size knob (CLI flag over
    HVDTPU_GRAD_BUCKET_MB over the 16 MB default) that
    optim/overlap.py resolves its bucket size through."""
    mb = (
        float(cli_mb)
        if cli_mb is not None
        else envmod.env_float(envmod.GRAD_BUCKET_MB,
                              DEFAULT_GRAD_BUCKET_MB)
    )
    if mb <= 0:
        raise ValueError(f"grad bucket size must be positive, got {mb} MB")
    return int(mb * 1024 * 1024)

def build_categories(
    *,
    multislice: bool = False,
    replay_enabled: bool = False,
    hierarchical_capable: bool = True,
) -> List[Dict[str, bool]]:
    """The ONE categorical exploration chain both engines tune over
    (reference explores hierarchical/cache combinations as
    CategoricalParameter values, parameter_manager.h:59-78).

    Topology-derived: each entry costs a full Bayesian sweep, so a knob
    with no consumer on this topology must not appear —

    * ``hierarchical_allreduce: True`` is explored ONLY on multi-slice
      topologies whose data plane can run the two-fabric schedule
      (``multislice and hierarchical_capable``).  On a single slice the
      flat XLA psum is already torus-optimal and the hierarchical path
      would be pure overhead; before this builder each engine hand-rolled
      its own list and a dead always-on entry drifted into the default.
    * ``cache_enabled: False`` is excluded while schedule replay is on:
      disabling the cache forfeits the negotiation-free steady state by
      construction, so a noisy sample window must not be able to freeze
      out the fast path.
    """
    cats: List[Dict[str, bool]] = [
        {"cache_enabled": True, "hierarchical_allreduce": False},
    ]
    if multislice and hierarchical_capable:
        cats.append(
            {"cache_enabled": True, "hierarchical_allreduce": True}
        )
    if not replay_enabled:
        cats.append(
            {"cache_enabled": False, "hierarchical_allreduce": False}
        )
    return cats

DEFAULT_WARMUP_SAMPLES = 3  # discarded while pipelines fill (reference WARMUPS)
DEFAULT_STEPS_PER_SAMPLE = 10  # negotiation cycles per score sample
DEFAULT_BAYES_SAMPLES_PER_CATEGORY = 12
GP_NOISE = 1e-6

# Drift detector defaults: re-open the search when the held incumbent's
# score runs DRIFT_THRESHOLD (fraction) below the post-convergence peak
# for DRIFT_SAMPLES consecutive sample windows.  20% x 3 windows ignores
# ordinary run-to-run jitter while catching a real regime change within
# ~3 windows.
DEFAULT_DRIFT_THRESHOLD = 0.2
DEFAULT_DRIFT_SAMPLES = 3
_HOLD_EWMA_ALPHA = 0.3
_HOLD_LOG_EVERY = 50  # CSV decimation while holding (drift rows always log)

# Tuner lifecycle states, published as the autotune.state gauge.
STATE_WARMUP = 0
STATE_SEARCHING = 1
STATE_CONVERGED = 2
STATE_RETUNING = 3
STATE_NAMES = {
    STATE_WARMUP: "warmup",
    STATE_SEARCHING: "searching",
    STATE_CONVERGED: "converged",
    STATE_RETUNING: "retuning",
}


class GaussianProcess:
    """GP regression with an RBF kernel (reference gaussian_process.cc).

    Inputs are expected normalized to [0, 1]^d.  Hyperparameters
    (signal variance, length scale) are selected by maximizing the log
    marginal likelihood over a small grid — the reference fits them with
    L-BFGS (vendored lbfgs); a grid is adequate for a 2-D tuner and keeps
    this dependency-free.
    """

    def __init__(self, length_scale: float = 0.2, signal_var: float = 1.0,
                 noise: float = 1e-4):
        self.length_scale = length_scale
        self.signal_var = signal_var
        self.noise = noise
        self._x: Optional[np.ndarray] = None
        self._alpha: Optional[np.ndarray] = None
        self._chol: Optional[np.ndarray] = None
        self._y_mean = 0.0
        self._y_std = 1.0

    def _kernel(self, a: np.ndarray, b: np.ndarray,
                length_scale: float, signal_var: float) -> np.ndarray:
        d2 = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
        return signal_var * np.exp(-0.5 * d2 / (length_scale ** 2))

    def _log_marginal(self, x: np.ndarray, y: np.ndarray,
                      length_scale: float, signal_var: float) -> float:
        k = self._kernel(x, x, length_scale, signal_var)
        k[np.diag_indices_from(k)] += self.noise
        try:
            chol = np.linalg.cholesky(k)
        except np.linalg.LinAlgError:
            return -np.inf
        alpha = np.linalg.solve(chol.T, np.linalg.solve(chol, y))
        return float(
            -0.5 * y @ alpha
            - np.log(np.diag(chol)).sum()
            - 0.5 * len(y) * np.log(2 * np.pi)
        )

    def fit(self, x: np.ndarray, y: np.ndarray) -> None:
        x = np.atleast_2d(np.asarray(x, float))
        y = np.asarray(y, float).reshape(-1)
        self._y_mean = float(y.mean())
        self._y_std = float(y.std()) or 1.0
        yn = (y - self._y_mean) / self._y_std
        if len(y) >= 4:
            best = (-np.inf, self.length_scale, self.signal_var)
            for ls in (0.05, 0.1, 0.2, 0.4, 0.8):
                for sv in (0.5, 1.0, 2.0):
                    lm = self._log_marginal(x, yn, ls, sv)
                    if lm > best[0]:
                        best = (lm, ls, sv)
            _, self.length_scale, self.signal_var = best
        k = self._kernel(x, x, self.length_scale, self.signal_var)
        k[np.diag_indices_from(k)] += self.noise
        self._chol = np.linalg.cholesky(k)
        self._alpha = np.linalg.solve(
            self._chol.T, np.linalg.solve(self._chol, yn)
        )
        self._x = x

    def predict(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Posterior mean and stddev at x (denormalized to y's scale)."""
        x = np.atleast_2d(np.asarray(x, float))
        if self._x is None:
            return (np.zeros(len(x)) + self._y_mean,
                    np.ones(len(x)) * self._y_std)
        ks = self._kernel(x, self._x, self.length_scale, self.signal_var)
        mean = ks @ self._alpha
        v = np.linalg.solve(self._chol, ks.T)
        var = np.maximum(
            self.signal_var - (v ** 2).sum(0), GP_NOISE
        )
        return (mean * self._y_std + self._y_mean,
                np.sqrt(var) * self._y_std)


class BayesianOptimization:
    """Expected-improvement Bayesian optimization over [0,1]^d
    (reference bayesian_optimization.cc: NextPoint via EI maximization)."""

    def __init__(self, dims: int, seed: int = 0, xi: float = 0.01,
                 noise: float = 1e-4):
        self.dims = dims
        self.xi = xi
        self._rng = np.random.RandomState(seed)
        self._x: List[np.ndarray] = []
        self._y: List[float] = []
        self.gp = GaussianProcess(noise=noise)

    def add_sample(self, x: np.ndarray, y: float) -> None:
        self._x.append(np.asarray(x, float))
        self._y.append(float(y))
        self.gp.fit(np.stack(self._x), np.asarray(self._y))

    def best(self) -> Tuple[np.ndarray, float]:
        i = int(np.argmax(self._y))
        return self._x[i], self._y[i]

    def next_point(self) -> np.ndarray:
        if len(self._y) < 2:
            return self._rng.uniform(size=self.dims)
        candidates = self._rng.uniform(size=(256, self.dims))
        # seed the candidate pool near the incumbent too
        bx, _ = self.best()
        local = np.clip(
            bx + self._rng.normal(scale=0.08, size=(64, self.dims)), 0, 1
        )
        candidates = np.concatenate([candidates, local])
        mean, std = self.gp.predict(candidates)
        y_best = max(self._y)
        z = (mean - y_best - self.xi) / std
        # EI = (mu - y* - xi) * Phi(z) + sigma * phi(z)
        phi = np.exp(-0.5 * z ** 2) / np.sqrt(2 * np.pi)
        cdf = 0.5 * (1 + _erf(z / np.sqrt(2)))
        ei = (mean - y_best - self.xi) * cdf + std * phi
        return candidates[int(np.argmax(ei))]


def _erf(x: np.ndarray) -> np.ndarray:
    # Abramowitz & Stegun 7.1.26; |err| < 1.5e-7 — plenty for EI ranking.
    sign = np.sign(x)
    x = np.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * x)
    y = 1.0 - (((((1.061405429 * t - 1.453152027) * t) + 1.421413741)
                * t - 0.284496736) * t + 0.254829592) * t * np.exp(-x * x)
    return sign * y


@dataclass
class TunedParams:
    """The parameter struct rank 0 ships to every rank each time the tuner
    moves (reference Params struct, controller.cc:33-47)."""

    fusion_bytes: int
    cycle_s: float
    cache_enabled: bool = True
    hierarchical_allreduce: bool = False

    def as_wire(self) -> tuple:
        return (self.fusion_bytes, self.cycle_s, self.cache_enabled,
                self.hierarchical_allreduce)

    @staticmethod
    def from_wire(t: tuple) -> "TunedParams":
        return TunedParams(int(t[0]), float(t[1]), bool(t[2]), bool(t[3]))


class ParameterManager:
    """Owns the engine tunables and drives the continuous score→tune loop
    (reference parameter_manager.h:59-78,178-220, minus its one-shot
    freeze).

    Usage (engine, rank 0 only):
        pm = ParameterManager(enabled=..., initial=TunedParams(...),
                              metrics_source=...)
        pm.record_bytes(n)                 # legacy scoring feed (no-op
                                           # when metrics_source is set)
        new = pm.cycle()                   # per negotiation cycle;
                                           # returns TunedParams when moved

    ``metrics_source`` is a zero-arg callable returning cumulative
    ``(bytes_moved, busy_seconds)`` — the engine wires it to its
    ``engine.fusion_bytes`` / ``engine.cycle_time_ms`` registry
    instruments, making the telemetry plane the objective function.
    Scoring on *busy* time (sum of measured cycle durations, no
    inter-cycle sleep, no host idle between steps) is what keeps an
    input-bound phase from convicting a good parameter point.  Without a
    source the manager falls back to record_bytes() over wall-clock
    spans (unit tests and the reference behavior).
    """

    def __init__(
        self,
        enabled: bool,
        initial: TunedParams,
        log_path: Optional[str] = None,
        warmup_samples: Optional[int] = None,
        steps_per_sample: Optional[int] = None,
        samples_per_category: Optional[int] = None,
        categories: Optional[List[Dict[str, bool]]] = None,
        metrics_source: Optional[Callable[[], Tuple[float, float]]] = None,
        drift_threshold: Optional[float] = None,
        drift_samples: Optional[int] = None,
    ):
        # Sampling-window knobs resolve through the reference's env names
        # (common.h:67-69 HOROVOD_AUTOTUNE_{WARMUP_SAMPLES,STEPS_PER_SAMPLE,
        # BAYES_OPT_MAX_SAMPLES}) so tests and deployments can trade tuning
        # latency for sample quality deterministically.
        if warmup_samples is None:
            warmup_samples = envmod.env_int(
                envmod.AUTOTUNE_WARMUP_SAMPLES, DEFAULT_WARMUP_SAMPLES
            )
        if steps_per_sample is None:
            steps_per_sample = envmod.env_int(
                envmod.AUTOTUNE_STEPS_PER_SAMPLE, DEFAULT_STEPS_PER_SAMPLE
            )
        if samples_per_category is None:
            samples_per_category = envmod.env_int(
                envmod.AUTOTUNE_BAYES_OPT_MAX_SAMPLES,
                DEFAULT_BAYES_SAMPLES_PER_CATEGORY,
            )
        # GP observation-noise prior (reference common.h:70
        # HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE): raise on noisy shared
        # machines so the tuner discounts sample-to-sample jitter.
        self._gp_noise = envmod.env_float(envmod.AUTOTUNE_GP_NOISE, 1e-4)
        if drift_threshold is None:
            drift_threshold = envmod.env_float(
                envmod.AUTOTUNE_DRIFT_THRESHOLD, DEFAULT_DRIFT_THRESHOLD
            )
        if drift_samples is None:
            drift_samples = envmod.env_int(
                envmod.AUTOTUNE_DRIFT_SAMPLES, DEFAULT_DRIFT_SAMPLES
            )
        # `categories` must list only configurations the owning engine
        # actually consumes — every category costs a full Bayesian sweep,
        # so exploring knobs with no consumer wastes 1/len(categories) of
        # the tuning budget per phantom entry.  Engines pass the
        # topology-derived build_categories() result; the no-argument
        # default is the conservative single-slice chain.
        self.categories = (
            build_categories() if categories is None else categories
        )
        self.enabled = enabled
        self.current = initial
        self.warmup_samples = warmup_samples
        self.steps_per_sample = steps_per_sample
        self.samples_per_category = samples_per_category
        self._bytes = 0
        self._steps = 0
        self._sample_start = time.monotonic()
        self._samples_seen = 0
        self._category_i = 0
        self._bayes = BayesianOptimization(dims=2, seed=0, noise=self._gp_noise)
        self._per_category_samples = 0
        self._best: Tuple[float, TunedParams] = (-1.0, initial)

        # Continuous-controller state.
        self._state = STATE_WARMUP
        self._source = metrics_source
        self._src_bytes0 = 0.0
        self._src_busy0 = 0.0
        if metrics_source is not None:
            self._src_bytes0, self._src_busy0 = metrics_source()
        self.drift_threshold = float(drift_threshold)
        self.drift_samples = int(drift_samples)
        self._hold_ewma: Optional[float] = None
        self._hold_peak = 0.0
        self._drift_count = 0
        self._hold_log_i = 0
        self.reopens = 0
        self._last_score = 0.0

        # Gauges: the tuner's externally visible state (/metrics and the
        # live digest read these; resolved once, updates are lock-free).
        from ..obs import get_registry  # noqa: PLC0415

        metrics = get_registry()
        self._g_state = metrics.gauge("autotune.state")
        self._g_last = metrics.gauge("autotune.last_score")
        self._g_best = metrics.gauge("autotune.best_score")
        self._g_fusion = metrics.gauge("autotune.fusion_mb")
        self._g_cycle = metrics.gauge("autotune.cycle_ms")
        self._g_cache = metrics.gauge("autotune.cache_enabled")
        self._g_category = metrics.gauge("autotune.category")
        self._g_samples = metrics.gauge("autotune.samples")
        self._g_reopens = metrics.gauge("autotune.reopens")
        self._publish()

        # Tuning-history CSV: APPEND, with the header only on a fresh
        # file, and epoch-tagged under the elastic launcher — an elastic
        # respawn re-creates the engine (and this manager), and mode "w"
        # here used to clobber the very tuning history that explains what
        # the dead incarnation had learned.
        self._log_path = None
        if log_path:
            from ..obs import pathspec  # noqa: PLC0415

            log_path = pathspec.epoch_tag(log_path)
            self._log_path = log_path
            if (not os.path.exists(log_path)
                    or os.path.getsize(log_path) == 0):
                with open(log_path, "a", newline="") as f:
                    csv.writer(f).writerow(
                        ["sample", "score_bytes_per_sec", "fusion_mb",
                         "cycle_ms", "cache_enabled",
                         "hierarchical_allreduce", "state"]
                    )

    # -------------------------------------------------------------- scoring

    def record_bytes(self, n: int) -> None:
        self._bytes += n

    def _window_score(self) -> Tuple[float, float]:
        """Close the current sample window; returns (score, bytes_moved).
        Score is bytes per second of busy cycle time when a metrics
        source is wired; bytes per wall-clock second otherwise."""
        if self._source is not None:
            bytes_now, busy_now = self._source()
            d_bytes = bytes_now - self._src_bytes0
            d_busy = busy_now - self._src_busy0
            self._src_bytes0, self._src_busy0 = bytes_now, busy_now
            self._bytes = 0
            return (d_bytes / d_busy if d_busy > 0 else 0.0, d_bytes)
        elapsed = time.monotonic() - self._sample_start
        moved = self._bytes
        score = self._bytes / elapsed if elapsed > 0 else 0.0
        self._bytes = 0
        return score, moved

    def cycle(self) -> Optional[TunedParams]:
        """Advance one negotiation cycle; maybe emit new params to try.

        Unlike the reference (SetAutoTuning(false) after one sweep),
        this keeps running after convergence: held samples feed the
        drift detector, which re-opens the search on sustained
        regression."""
        if not self.enabled:
            return None
        self._steps += 1
        if self._steps < self.steps_per_sample:
            return None
        score, moved = self._window_score()
        self._steps = 0
        self._sample_start = time.monotonic()
        if moved <= 0:
            # Idle window (training paused: eval, checkpoint, input
            # stall) — evidence of NOTHING.  Scoring it as 0 would feed
            # garbage into the GP and, worse, convict a held incumbent
            # of drift after any pause spanning drift_samples windows.
            return None
        self._samples_seen += 1
        self._last_score = score
        if self._samples_seen <= self.warmup_samples:
            return None
        if self._state == STATE_WARMUP:
            self._state = STATE_SEARCHING
        try:
            if self._state == STATE_CONVERGED:
                return self._hold(score)
            return self._tune(score)
        finally:
            self._publish()

    # --------------------------------------------------------------- tuning

    def _norm(self, p: TunedParams) -> np.ndarray:
        # Clamp into bounds before the log: params can start outside the
        # search box (e.g. HVDTPU_FUSION_THRESHOLD=0 disables fusion, and
        # log2(0) would poison the GP kernel with NaNs).
        fmb = float(np.clip(p.fusion_bytes / (1024 * 1024), *FUSION_BOUNDS_MB))
        cms = float(np.clip(p.cycle_s * 1000, *CYCLE_BOUNDS_MS))
        return np.asarray([
            (np.log2(fmb) - np.log2(FUSION_BOUNDS_MB[0]))
            / (np.log2(FUSION_BOUNDS_MB[1]) - np.log2(FUSION_BOUNDS_MB[0])),
            (np.log2(cms) - np.log2(CYCLE_BOUNDS_MS[0]))
            / (np.log2(CYCLE_BOUNDS_MS[1]) - np.log2(CYCLE_BOUNDS_MS[0])),
        ])

    def _denorm(self, x: np.ndarray) -> Tuple[int, float]:
        lf0, lf1 = np.log2(FUSION_BOUNDS_MB)
        lc0, lc1 = np.log2(CYCLE_BOUNDS_MS)
        fmb = 2.0 ** (lf0 + float(np.clip(x[0], 0, 1)) * (lf1 - lf0))
        cms = 2.0 ** (lc0 + float(np.clip(x[1], 0, 1)) * (lc1 - lc0))
        return int(fmb * 1024 * 1024), cms / 1000.0

    def _tune(self, score: float) -> Optional[TunedParams]:
        """One SEARCHING/RETUNING sample: feed the GP, maybe move."""
        if score > self._best[0]:
            self._best = (score, self.current)
        self._log(score)
        self._bayes.add_sample(self._norm(self.current), score)
        self._per_category_samples += 1
        if self._per_category_samples >= self.samples_per_category:
            self._per_category_samples = 0
            if self._state == STATE_RETUNING:
                # a re-opened search stays in the incumbent's category:
                # one GP budget, then settle again
                return self._converge()
            # advance the categorical chain; reset the continuous surface
            self._category_i += 1
            if self._category_i >= len(self.categories):
                return self._converge()
            self._bayes = BayesianOptimization(
                dims=2, seed=self._category_i, noise=self._gp_noise
            )
        fusion_bytes, cycle_s = self._denorm(self._bayes.next_point())
        cat = self._probe_category()
        self.current = TunedParams(
            fusion_bytes=fusion_bytes, cycle_s=cycle_s, **cat
        )
        return self.current

    def _probe_category(self) -> Dict[str, bool]:
        """The categorical config the next continuous probe rides on:
        the chain position while SEARCHING, the INCUMBENT's own config
        while RETUNING — after a full sweep _category_i points past the
        chain's end, and indexing the last entry would silently retune
        in whatever category happened to be swept last (e.g. cache-off)
        rather than the one the incumbent won with."""
        if self._state == STATE_RETUNING:
            return {
                "cache_enabled": self._best[1].cache_enabled,
                "hierarchical_allreduce":
                    self._best[1].hierarchical_allreduce,
            }
        return self.categories[min(self._category_i,
                                   len(self.categories) - 1)]

    def _converge(self) -> Optional[TunedParams]:
        """Settle on the best configuration scored and enter the hold
        state (the reference stops here for good; we keep watching)."""
        self._state = STATE_CONVERGED
        # Seed the smoothed hold signal with the winning search score:
        # it is evidence of the healthy level, but as an EWMA seed its
        # weight decays 0.7^k per window, so a single lucky sample
        # cannot permanently inflate the bar real windows are judged
        # against (the perpetual-retune failure mode).
        self._hold_ewma = self._best[0]
        self._hold_peak = 0.0
        self._drift_count = 0
        # Emit the incumbent even if it equals the last point tried —
        # peers apply params idempotently; returning None here would
        # leave them on the final *probe* point forever.
        self.current = self._best[1]
        return self.current

    def _hold(self, score: float) -> Optional[TunedParams]:
        """One CONVERGED sample: hold the incumbent, watch for drift.
        Drift is judged on the SMOOTHED signal (EWMA vs the peak the
        EWMA itself reached), never on a raw window — one noisy window
        in either direction moves the EWMA by at most alpha."""
        if self._hold_ewma is None:
            self._hold_ewma = score
        else:
            self._hold_ewma = (
                _HOLD_EWMA_ALPHA * score
                + (1 - _HOLD_EWMA_ALPHA) * self._hold_ewma
            )
        self._hold_peak = max(self._hold_peak, self._hold_ewma)
        if self._hold_ewma < self._hold_peak * (1.0 - self.drift_threshold):
            self._drift_count += 1
        else:
            self._drift_count = 0
        # Hold-state logging is decimated: drifting windows are always
        # interesting, otherwise one row per _HOLD_LOG_EVERY windows —
        # the removed one-shot tuner stopped logging at convergence, and
        # an unbounded per-window append would grow the CSV forever on
        # long jobs.
        self._hold_log_i += 1
        if self._drift_count or self._hold_log_i % _HOLD_LOG_EVERY == 0:
            self._log(score)
        if self._drift_count < self.drift_samples:
            return None
        return self._reopen(score)

    def _reopen(self, score: float) -> Optional[TunedParams]:
        """Sustained regression: the world changed under the incumbent.
        Restart the GP in the incumbent's category, seeded with the
        incumbent at its CURRENT (regressed) score — the stale
        pre-drift best would otherwise be unbeatable and the search
        could never move."""
        self._state = STATE_RETUNING
        self.reopens += 1
        self._drift_count = 0
        self._per_category_samples = 0
        self._best = (score, self.current)
        self._bayes = BayesianOptimization(
            dims=2, seed=100 + self.reopens, noise=self._gp_noise
        )
        self._bayes.add_sample(self._norm(self.current), score)
        fusion_bytes, cycle_s = self._denorm(self._bayes.next_point())
        cat = self._probe_category()
        self.current = TunedParams(
            fusion_bytes=fusion_bytes, cycle_s=cycle_s, **cat
        )
        return self.current

    @property
    def converged(self) -> bool:
        return self._state == STATE_CONVERGED

    @property
    def state(self) -> int:
        return self._state

    def best_score(self) -> float:
        return self._best[0]

    def _publish(self) -> None:
        self._g_state.set(self._state)
        self._g_last.set(self._last_score)
        self._g_best.set(self._best[0])
        self._g_fusion.set(self.current.fusion_bytes / 1048576)
        self._g_cycle.set(self.current.cycle_s * 1000)
        self._g_cache.set(int(self.current.cache_enabled))
        self._g_category.set(min(self._category_i,
                                 len(self.categories) - 1))
        self._g_samples.set(self._samples_seen)
        self._g_reopens.set(self.reopens)

    def _log(self, score: float) -> None:
        if not self._log_path:
            return
        p = self.current
        with open(self._log_path, "a", newline="") as f:
            csv.writer(f).writerow([
                self._samples_seen, round(score, 1),
                round(p.fusion_bytes / 1048576, 2),
                round(p.cycle_s * 1000, 3),
                int(p.cache_enabled), int(p.hierarchical_allreduce),
                STATE_NAMES[self._state],
            ])
