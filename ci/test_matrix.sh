#!/usr/bin/env bash
# CI matrix driver (reference: .buildkite/gen-pipeline.sh:10-33 crossing
# {MPI,Gloo,...} x {py} x {framework} images; here the axes that exist in
# the TPU build: eager engine {python,native} x world size {1,2,4}).
#
# Usage: ci/test_matrix.sh            # full matrix
#        ci/test_matrix.sh quick      # unit suite + np=2 cross-engine only
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== build native engine =="
make -C cpp

# Static-analysis gate (ISSUE 5) — runs FIRST in every tier: it is the
# cheapest check and rejects whole bug classes (rank-divergent collective
# schedules, lock-order/signal-safety violations) no test below can see.
analysis_gate() {
    echo "== analysis gate: hvdtpu-lint over the full surface =="
    # ONE full-surface run serves both checks: the committed tree must
    # lint clean against the committed baseline (exit 0 + summary.new
    # asserted below) and the JSON report must be schema-valid.  No
    # explicit paths: the [tool.hvdtpu-lint] config supplies the same
    # surface, AND a config-default run is the one that reports stale
    # baseline entries (fixed findings whose entries should be removed).
    LINT_TMP=$(mktemp -d)
    # --strict-baseline: stale suppressions (entries whose finding no
    # longer fires) fail the gate — dead entries would silently swallow
    # a FUTURE finding at the same (rule, path, context).
    if ! python -m horovod_tpu.analysis \
        --baseline horovod_tpu/analysis/baseline.json \
        --strict-baseline \
        --format json > "$LINT_TMP/report.json"; then
        echo "analysis gate FAILED: new findings on the clean tree" >&2
        python - "$LINT_TMP/report.json" <<'EOF' >&2 || cat "$LINT_TMP/report.json" >&2
import json, sys
for f in json.load(open(sys.argv[1]))["findings"]:
    if f["status"] == "new":
        print(f"{f['path']}:{f['line']}: {f['rule']} {f['message']}")
EOF
        rm -rf "$LINT_TMP"
        exit 1
    fi
    python - "$LINT_TMP/report.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "hvdtpu-lint-v1", doc["schema"]
assert isinstance(doc["rules"], dict) and len(doc["rules"]) >= 20
for rid, r in doc["rules"].items():
    assert {"name", "severity", "summary"} <= set(r), (rid, r)
    assert r["severity"] in ("error", "warning"), (rid, r)
for f in doc["findings"]:
    assert {"rule", "severity", "path", "line", "col", "message",
            "context", "status"} <= set(f), f
    assert f["status"] in ("new", "baselined", "suppressed"), f
    assert isinstance(f["line"], int) and f["line"] >= 1, f
s = doc["summary"]
assert s["new"] == 0, f"clean-tree run reported new findings: {s}"
assert s["total"] == len(doc["findings"])
print(f"analysis gate: schema OK ({len(doc['rules'])} rules, "
      f"{s['baselined']} baselined, {s['suppressed']} suppressed)")
EOF
    # 3) the gate actually GATES: a seeded violation must fail the run
    cat > "$LINT_TMP/seeded_bad.py" <<'EOF'
import horovod_tpu as hvd

def step(x):
    if hvd.rank() == 0:          # rank-guarded collective: deadlock
        return hvd.allreduce(x)
    return x
EOF
    if python -m horovod_tpu.analysis "$LINT_TMP/seeded_bad.py" \
        --baseline horovod_tpu/analysis/baseline.json \
        > "$LINT_TMP/seeded.out" 2>&1; then
        echo "analysis gate FAILED: seeded violation passed the linter" >&2
        cat "$LINT_TMP/seeded.out" >&2
        rm -rf "$LINT_TMP"
        exit 1
    fi
    grep -q "HVD001" "$LINT_TMP/seeded.out" || {
        echo "analysis gate FAILED: seeded violation not attributed to HVD001" >&2
        cat "$LINT_TMP/seeded.out" >&2
        rm -rf "$LINT_TMP"
        exit 1
    }
    # 4) the mesh-aware family gates too (ISSUE 12): a rank-guarded
    # subgroup collective inside a shard_map body must fail as HVD010,
    # including the interprocedural shape where the rank read and the
    # collective live in different functions.
    cat > "$LINT_TMP/seeded_subgroup.py" <<'EOF'
import horovod_tpu as hvd
from jax import lax
from jax.experimental.shard_map import shard_map

def body(x):
    if hvd.rank() == 0:              # world taint, local group: deadlock
        return lax.psum(x, "hvd_local")
    return x

def reduce_part(flag, x):
    if flag == 0:                    # taint arrives through the argument
        return lax.psum(x, "hvd_cross")
    return x

def step(x):
    return reduce_part(hvd.cross_rank(), x)
EOF
    if python -m horovod_tpu.analysis "$LINT_TMP/seeded_subgroup.py" \
        --baseline horovod_tpu/analysis/baseline.json \
        > "$LINT_TMP/seeded_sub.out" 2>&1; then
        echo "analysis gate FAILED: seeded subgroup-divergent collective passed" >&2
        cat "$LINT_TMP/seeded_sub.out" >&2
        rm -rf "$LINT_TMP"
        exit 1
    fi
    # both the direct and the interprocedural hit, attributed to HVD010
    # with the producing call chain named
    [ "$(grep -c "HVD010" "$LINT_TMP/seeded_sub.out")" -ge 2 ] || {
        echo "analysis gate FAILED: seeded subgroup violations not attributed to HVD010" >&2
        cat "$LINT_TMP/seeded_sub.out" >&2
        rm -rf "$LINT_TMP"
        exit 1
    }
    grep -q "step \[.*\] -> reduce_part" "$LINT_TMP/seeded_sub.out" || {
        echo "analysis gate FAILED: HVD010 finding lost its call-chain attribution" >&2
        cat "$LINT_TMP/seeded_sub.out" >&2
        rm -rf "$LINT_TMP"
        exit 1
    }
    rm -rf "$LINT_TMP"
    echo "analysis gate OK"
}

# Race gate (ISSUE 20): the guarded-by data-race family (HVDC108/109/
# 110) specifically.  Two halves: the committed tree restricted to the
# race rules must be clean against the committed baseline (every racy
# access in the serving fleet is either fixed or carries a reasoned
# baseline entry), and a seeded unguarded-write fixture must FAIL the
# run with the class, the field AND the inferred guard named — a gate
# that cannot fail, or that fails without attribution, is decorative.
races_gate() {
    echo "== races gate: HVDC108-110 clean tree vs baseline =="
    RG_TMP=$(mktemp -d)
    # --rules is a partial view, so baseline-staleness policing stays
    # with analysis_gate's full-surface --strict-baseline run; this
    # run asserts the race family's own verdict in isolation.
    if ! python -m horovod_tpu.analysis \
        --rules HVDC108,HVDC109,HVDC110 \
        --baseline horovod_tpu/analysis/baseline.json \
        > "$RG_TMP/clean.out"; then
        echo "races gate FAILED: new race findings on the clean tree" >&2
        cat "$RG_TMP/clean.out" >&2
        rm -rf "$RG_TMP"
        exit 1
    fi
    echo "== races gate: seeded unguarded write must fail, attributed =="
    cat > "$RG_TMP/seeded_race.py" <<'EOF'
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._depth = 0

    def start(self):
        threading.Thread(target=self._run).start()

    def _run(self):
        with self._lock:
            self._depth += 1
        with self._lock:
            self._depth -= 1

    def depth(self):
        with self._lock:
            return self._depth

    def spill(self):
        self._depth = 0     # write outside the inferred guard: HVDC108
EOF
    if python -m horovod_tpu.analysis "$RG_TMP/seeded_race.py" \
        --baseline horovod_tpu/analysis/baseline.json \
        > "$RG_TMP/seeded.out" 2>&1; then
        echo "races gate FAILED: seeded unguarded write passed the linter" >&2
        cat "$RG_TMP/seeded.out" >&2
        rm -rf "$RG_TMP"
        exit 1
    fi
    # the finding must name the class+field and the inferred lock
    for want in "HVDC108" "Pump._depth" "Pump.self._lock"; do
        grep -q "$want" "$RG_TMP/seeded.out" || {
            echo "races gate FAILED: finding lost its attribution ($want)" >&2
            cat "$RG_TMP/seeded.out" >&2
            rm -rf "$RG_TMP"
            exit 1
        }
    done
    rm -rf "$RG_TMP"
    echo "races gate OK"
}

if [ "${1:-full}" = "quick" ]; then
    # Fast lint pre-gate: changed-files-only via the dev-loop wrapper
    # (ISSUE 20 satellite) — on a per-commit diff this is seconds; the
    # FULL-surface analysis_gate + races_gate stay in the full tier,
    # where their cost is amortized against the long pole.
    echo "== quick tier: lint pre-gate over changed files =="
    python scripts/lint.py --changed
    # per-commit tier: everything except the long pole (soak, differential
    # fuzz, fp8 numerics contract, scaling gates) — see pytest.ini markers.
    # The elastic/fault-injection suite runs first and by name: recovery
    # paths only stay honest while the chaos tests that drive them
    # (ISSUE 1 acceptance) are exercised on every commit.
    echo "== quick tier: elastic fault-tolerance + injection paths =="
    python -m pytest tests/test_elastic.py tests/test_ckpt.py \
        "tests/test_checkpoint.py::test_injected_ckpt_failure_raises_on_all_ranks" \
        -x -q
    echo "== quick tier: observability plane =="
    python -m pytest tests/test_obs.py tests/test_obs_live.py \
        tests/test_postmortem.py tests/test_trace.py \
        tests/test_health.py -x -q
    echo "== quick tier: unit + multiprocess suite minus -m full =="
    # test_elastic.py / test_obs*.py and the injection case already ran
    # above — don't pay for the multiprocess chaos cases twice per commit.
    python -m pytest tests/ -x -q -m "not full and not slow" \
        --ignore=tests/test_elastic.py \
        --ignore=tests/test_ckpt.py \
        --ignore=tests/test_obs.py \
        --ignore=tests/test_obs_live.py \
        --ignore=tests/test_postmortem.py \
        --ignore=tests/test_trace.py \
        --ignore=tests/test_health.py \
        --deselect "tests/test_checkpoint.py::test_injected_ckpt_failure_raises_on_all_ranks"
    exit 0
fi

analysis_gate
races_gate

echo "== unit + in-process multiprocess suite (builds cover both engines) =="
# Parallel full tier (VERDICT r4 weak #6: 30 min single-threaded and
# growing).  The suite is sleep/IO-dominated (negotiation cycle sleeps,
# rendezvous polling, worker-process spawns), so oversubscribing even a
# 1-core host with 4 pytest workers cuts wall-clock.  Tests that assert
# wall-clock/throughput bounds carry -m serial and run alone afterwards
# so parallel load can't flake them.  Environments without pytest-xdist
# (it's in the test extra + Dockerfile.test, but a bare `pip install
# pytest` isn't) fall back to the single-process run.
if python -c "import xdist" 2>/dev/null; then
    # slow-marked acceptances are excluded here and run by node id
    # from their own gates (slow_multiproc/serve/paged/autoscale/mem)
    # — without the filter every one of them would execute twice.
    python -m pytest tests/ -x -q -m "not serial and not slow" -n 4 --dist load
else
    echo "pytest-xdist not installed; falling back to serial full tier" >&2
    python -m pytest tests/ -x -q -m "not serial and not slow"
fi
echo "== serial (timing-sensitive) tier =="
python -m pytest tests/ -x -q -m serial

echo "== slow_multiproc gate: tier-1-budget-triaged acceptances by node id =="
# These spawn real worker fleets and together cost ~100s — slow-marked
# out of the driver's tier-1 budget (ISSUE 15 hygiene), run HERE
# explicitly so the coverage never silently lapses.
python -m pytest \
    "tests/test_multiprocess.py::test_stall_shutdown_aborts_instead_of_hanging" \
    "tests/test_multiprocess.py::test_tf_interop_across_processes" \
    "tests/test_multiprocess.py::test_tf_broadcast_hook_in_monitored_session" \
    "tests/test_multiprocess.py::test_tf_adasum_optimizer_matches_numpy_reference" \
    "tests/test_multiprocess.py::test_keras_fit_across_processes" \
    -x -q

# Engine x world-size smoke matrix through the REAL launcher CLI (the
# reference runs examples under both mpirun and horovodrun for every
# image, gen-pipeline.sh:134-232).
for engine in python native; do
    for np in 1 2 4; do
        echo "== smoke: engine=$engine np=$np =="
        HVDTPU_EAGER_ENGINE=$engine \
        JAX_PLATFORMS=cpu \
            python -m horovod_tpu.run -np "$np" -H "localhost:$np" \
            python examples/mnist.py --smoke
    done
done

# Frontend + subsystem examples at np=2 (one engine each is enough: the
# differential fuzz test pins engine equivalence at the op level).
for ex in torch_mnist tf2_mnist keras_mnist adasum_small_model \
          checkpoint_resume estimator_train long_context_zigzag; do
    echo "== example smoke: $ex =="
    JAX_PLATFORMS=cpu \
        python -m horovod_tpu.run -np 2 python "examples/$ex.py"
done

# single-process multi-device examples (in-process mesh, --cpu sets the
# platform inside the process like tests/conftest.py)
for argset in "--smoke --cpu" "--smoke --cpu --circles 2"; do
    echo "== example smoke: pipeline_train $argset =="
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/pipeline_train.py $argset
done

# Observability gate: the obs unit suite plus a 2-process launcher
# smoke — per-rank metrics dumps and the merged all-rank timeline must
# both exist and parse as JSON (ISSUE 2: nothing quantitative survived
# a job before this plane existed).
echo "== obs gate: unit suite =="
python -m pytest tests/test_obs.py -x -q
echo "== obs gate: 2-process metrics dump + merged timeline smoke =="
OBS_TMP=$(mktemp -d)
cat > "$OBS_TMP/worker.py" <<'EOF'
import numpy as np
import horovod_tpu as hvd

hvd.init()
for i in range(4):
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name=f"t{i}")
hvd.shutdown()
EOF
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
HVDTPU_METRICS_DUMP="$OBS_TMP" \
HVDTPU_TIMELINE="$OBS_TMP/trace.json" \
HVDTPU_TIMELINE_MARK_CYCLES=1 \
    python -m horovod_tpu.run -np 2 --stats-summary \
    python "$OBS_TMP/worker.py"
python - "$OBS_TMP" <<'EOF'
import glob, json, sys
d = sys.argv[1]
dumps = glob.glob(f"{d}/metrics.*rank*.json")
assert len(dumps) == 2, f"expected 2 per-rank metrics dumps, got {dumps}"
for p in dumps:
    doc = json.load(open(p))
    assert doc["metrics"], f"empty metrics dump {p}"
merged = json.load(open(f"{d}/trace.json"))
assert merged, "merged timeline is empty"
pids = {e.get("pid") for e in merged if e.get("ph") != "M"}
assert pids == {0, 1}, f"expected a lane per rank, got pids={pids}"
print(f"obs gate OK: {len(dumps)} dumps, {len(merged)} timeline events")
EOF
rm -rf "$OBS_TMP"

# Live telemetry gate (ISSUE 3): a 2-proc job streaming metrics to the
# launcher; an external scraper attaches to GET /metrics MID-RUN and
# must read non-empty, parseable Prometheus exposition with a sample
# per rank, and live_history.jsonl must gain parseable rows.
echo "== obs_live gate: mid-run /metrics scrape + live history =="
LIVE_TMP=$(mktemp -d)
cat > "$LIVE_TMP/worker.py" <<'EOF'
import time

import numpy as np

import horovod_tpu as hvd

hvd.init()
for i in range(16):
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name=f"t{i}")
    time.sleep(0.25)
hvd.shutdown()
EOF
cat > "$LIVE_TMP/scrape.py" <<'EOF'
import json, os, re, subprocess, sys, time, urllib.request

tmp = sys.argv[1]
hist = os.path.join(tmp, "live_history.jsonl")
proc = subprocess.Popen(
    [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
     "--live-stats-secs", "0.3", "--live-history-file", hist,
     sys.executable, os.path.join(tmp, "worker.py")],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    env={**os.environ, "JAX_PLATFORMS": "cpu"},
)
endpoint = None
deadline = time.time() + 90
while time.time() < deadline and endpoint is None:
    line = proc.stdout.readline()
    if not line:
        break
    sys.stdout.write(line)
    m = re.search(r"scrape endpoint (http://\S+/metrics)", line)
    if m:
        endpoint = m.group(1)
assert endpoint, "launcher never announced the scrape endpoint"

# scrape MID-RUN until per-rank samples appear
body = ""
while time.time() < deadline:
    body = urllib.request.urlopen(endpoint, timeout=5).read().decode()
    if 'rank="0"' in body and 'rank="1"' in body:
        break
    time.sleep(0.3)
assert proc.poll() is None, "job finished before the mid-run scrape"
sample = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (NaN|[-+0-9.eE]+)$')
lines = [l for l in body.rstrip().splitlines() if not l.startswith("#")]
assert lines, "empty exposition"
for l in lines:
    assert sample.match(l), f"unparseable exposition line: {l!r}"
assert "hvdtpu_engine_collectives_completed" in body

proc.stdout.read()
assert proc.wait(timeout=120) == 0
rows = [json.loads(l) for l in open(hist)]
assert rows, "live_history.jsonl gained no rows"
assert rows[-1]["ranks_reporting"] >= 1
print(f"obs_live gate OK: {len(lines)} exposition lines, "
      f"{len(rows)} history rows")
EOF
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python "$LIVE_TMP/scrape.py" "$LIVE_TMP"
rm -rf "$LIVE_TMP"

# Goodput gate (ISSUE 17): the per-rank goodput ledger and the tenant
# SLO burn-rate plane.  hvdtpu-lint stays clean over the surface and the
# decision-table suites run (tiling invariant, two-window burn alerting).
echo "== goodput gate: lint + decision-table suites =="
python -m horovod_tpu.analysis horovod_tpu/obs/goodput.py \
    horovod_tpu/obs/slo.py \
    --baseline horovod_tpu/analysis/baseline.json
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest tests/test_goodput.py \
    tests/test_slo.py -x -q
# Post-mortem gate (ISSUE 4): a 2-proc job crashed with action=abort on
# rank 1 must leave per-rank flight-recorder dumps and a launcher-written
# postmortem.json that is schema-valid and blames the injected rank; the
# clean-run path must write NO postmortem.  /healthz is probed instead of
# sleeping before the crash run starts (satellite: KVStoreServer liveness).
echo "== postmortem gate: crashed job leaves a black box + verdict =="
PM_TMP=$(mktemp -d)
cat > "$PM_TMP/worker.py" <<'EOF'
import numpy as np
import horovod_tpu as hvd

hvd.init()
for i in range(8):
    hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name=f"t{i}")
hvd.shutdown()
EOF
python - <<'EOF'
# healthz probe: a fresh KV server must answer before any job leans on it
import json, urllib.request
from horovod_tpu.run.rendezvous import KVStoreServer
s = KVStoreServer(); s.start()
doc = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{s.port}/healthz", timeout=5).read())
assert doc["status"] == "ok", doc
s.stop()
print("healthz OK")
EOF
mkdir -p "$PM_TMP/bb"
if JAX_PLATFORMS=cpu \
   PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
   HVDTPU_FAULT_SPEC="enqueue:rank=1:step=4:action=abort" \
       python -m horovod_tpu.run -np 2 --flightrec-dump "$PM_TMP/bb" \
       python "$PM_TMP/worker.py"; then
    echo "postmortem gate FAILED: crashed job reported success" >&2
    exit 1
fi
python - "$PM_TMP/bb" <<'EOF'
import glob, json, sys
d = sys.argv[1]
dumps = glob.glob(f"{d}/flightrec.*rank*.json")
assert len(dumps) == 2, f"expected 2 per-rank black boxes, got {dumps}"
report = json.load(open(f"{d}/postmortem.json"))
assert report["schema"] == "hvdtpu-postmortem-v1", report["schema"]
ff = report["first_failure"]
assert ff["rank"] == 1, f"verdict blamed {ff['rank']}, injected rank 1"
assert ff["trigger"] == "signal:SIGABRT", ff
assert ff["last_collective"] == "t2", ff
assert "ank 1" in report["verdict"], report["verdict"]
print("postmortem gate OK:", report["verdict"])
EOF
echo "== postmortem gate: clean run writes no postmortem =="
mkdir -p "$PM_TMP/clean"
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m horovod_tpu.run -np 2 --flightrec-dump "$PM_TMP/clean" \
    python "$PM_TMP/worker.py"
if [ -e "$PM_TMP/clean/postmortem.json" ]; then
    echo "postmortem gate FAILED: clean run wrote a postmortem" >&2
    exit 1
fi
rm -rf "$PM_TMP"

# Fastpath gate (ISSUE 6): on a stable 2-proc schedule the replay epoch
# must make ≥95% of steady-state cycles skip negotiation entirely —
# counter-based (engine.stats deltas after warmup), no timing flake —
# and a seeded fault-registry delay mid-replay must break the epoch on
# every rank instead of hanging.
echo "== fastpath gate: steady-state negotiation skip + chaos break =="
FP_TMP=$(mktemp -d)
cat > "$FP_TMP/worker.py" <<'EOF'
import json, os, sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import _engine_registry

hvd.init()
for i in range(30):  # warmup: negotiate, converge, enter replay
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="grad")
eng = _engine_registry.get_engine()
warm = dict(eng.stats)
for i in range(200):  # steady state: must be negotiation-free
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="grad")
steady = dict(eng.stats)
doc = {"warm": warm, "steady": steady, "rank": hvd.rank()}
with open(os.path.join(sys.argv[1], f"stats.rank{hvd.rank()}.json"), "w") as f:
    json.dump(doc, f)
hvd.shutdown()
EOF
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
HVDTPU_EAGER_ENGINE=python \
HVDTPU_EAGER_DEVICE=0 \
HVDTPU_SCHEDULE_REPLAY_CYCLES=5 \
HVDTPU_CYCLE_TIME=2 \
    timeout 180 python -m horovod_tpu.run -np 2 python "$FP_TMP/worker.py" "$FP_TMP"
python - "$FP_TMP" <<'EOF'
import glob, json, sys

dumps = sorted(glob.glob(f"{sys.argv[1]}/stats.rank*.json"))
assert len(dumps) == 2, dumps
for p in dumps:
    doc = json.load(open(p))
    warm, steady = doc["warm"], doc["steady"]
    assert steady["replay_epochs"] >= 1, steady
    d_cycles = steady["cycles"] - warm["cycles"]
    d_neg = steady["negotiated_cycles"] - warm["negotiated_cycles"]
    assert d_cycles > 0, (warm, steady)
    ratio = d_neg / d_cycles
    assert ratio <= 0.05, (
        f"rank {doc['rank']}: {d_neg}/{d_cycles} steady-state cycles "
        f"negotiated ({ratio:.1%} > 5%)")
    print(f"fastpath gate rank {doc['rank']}: {d_neg}/{d_cycles} "
          f"steady-state cycles negotiated ({ratio:.1%})")
EOF
echo "== fastpath gate: seeded delay breaks the epoch on every rank =="
cat > "$FP_TMP/chaos.py" <<'EOF'
import json, os, sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import _engine_registry

hvd.init()
for i in range(60):
    out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="grad")
    assert float(out[0]) == 2.0
eng = _engine_registry.get_engine()
doc = {"stats": dict(eng.stats), "rank": hvd.rank()}
with open(os.path.join(sys.argv[1], f"chaos.rank{hvd.rank()}.json"), "w") as f:
    json.dump(doc, f)
hvd.shutdown()
EOF
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
HVDTPU_EAGER_ENGINE=python \
HVDTPU_EAGER_DEVICE=0 \
HVDTPU_SCHEDULE_REPLAY_CYCLES=5 \
HVDTPU_CYCLE_TIME=2 \
HVDTPU_STALL_CHECK_TIME_SECONDS=1 \
HVDTPU_FAULT_SPEC="enqueue:rank=1:step=30:action=delay:2500" \
    timeout 120 python -m horovod_tpu.run -np 2 python "$FP_TMP/chaos.py" "$FP_TMP"
python - "$FP_TMP" <<'EOF'
import glob, json, sys

dumps = sorted(glob.glob(f"{sys.argv[1]}/chaos.rank*.json"))
assert len(dumps) == 2, dumps
for p in dumps:
    doc = json.load(open(p))
    s = doc["stats"]
    assert s["replay_epochs"] >= 1, s
    assert s["replay_breaks"] >= 1, (
        f"rank {doc['rank']} never broke its replay epoch: {s}")
    print(f"fastpath chaos rank {doc['rank']}: {s['replay_breaks']} "
          f"break(s), {s['replay_cycles']} replay cycles — no hang")
EOF
rm -rf "$FP_TMP"

# Checkpoint/recovery gate (ISSUE 7): the ckpt unit suite, hvdtpu-lint
# clean over the new subsystem specifically, and a 2-proc elastic chaos
# run — a seeded mid-epoch kill must be recovered by the respawned
# incarnation restoring from its peer's IN-MEMORY replica (provenance
# says peer, the replica specifically, never disk) inside the recovery
# budget, the job must finish with the right state, and the sharded
# manifest written along the way must be schema-valid.
echo "== ckpt gate: unit suite + lint over the subsystem =="
python -m pytest tests/test_ckpt.py -x -q
python -m horovod_tpu.analysis horovod_tpu/ckpt \
    --baseline horovod_tpu/analysis/baseline.json
echo "== ckpt gate: chaos — peer-sourced restore within budget =="
CK_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 180 python - "$CK_TMP" <<'EOF'
import sys

import numpy as np

import horovod_tpu.elastic as elastic
from horovod_tpu import ckpt

tmp = sys.argv[1]
ckpt_dir = f"{tmp}/shards"


def train(total_steps=8, directory=ckpt_dir):
    import numpy as np  # noqa: PLC0415

    import horovod_tpu.elastic as elastic  # noqa: PLC0415

    ctx = elastic.context()
    state = elastic.State(w=np.zeros(4, dtype=np.float64), step=0)

    @elastic.run
    def loop(state):
        while state.step < total_steps:
            grad = np.full(4, float(state.step + 1) * (ctx.rank + 1))
            state.w = state.w - 0.1 * ctx.allreduce(
                grad, name=f"g{state.step}")
            state.step += 1
            state.commit()
            if state.step == 2:
                # disk tier: every rank writes only its own shard,
                # rank 0 commits the manifest last
                state.save_sharded(directory).wait()
        return state.step, state.last_restore

    return loop(state)


env = {"JAX_PLATFORMS": "cpu", "HVDTPU_CKPT_REPLICA": "1",
       "HVDTPU_CKPT_DIR": ckpt_dir,
       "HVDTPU_FAULT_SPEC": "worker_exit:step=5:rank=1"}
results, job = elastic.launch(train, np=2, env=env, max_retries=2,
                              timeout=120)

assert sorted(results) == [0, 1], results
assert all(results[r][0] == 8 for r in results), results
assert [e[0] for e in job.trace].count("respawn") == 1, job.trace

prov = results[1][1]
assert prov and prov["source"] == "peer", (
    f"respawned rank restored from {prov}, expected the peer tier")
assert prov["replica_adopted"] is True, (
    f"restore did not come from the in-memory replica: {prov}")
assert prov["ms"] < 10_000, f"recovery took {prov['ms']:.0f} ms"

manifest = ckpt.load_manifest(ckpt_dir, 2)
assert manifest is not None, "no committed manifest at step 2"
assert manifest["schema"] == "hvdtpu-sharded-ckpt-v1", manifest["schema"]
assert manifest["world_size"] == 2, manifest
assert len(manifest["shards"]) == 2, manifest
for s in manifest["shards"]:
    assert len(s["checksum"]) == 64, s
owned = sorted(i for s in manifest["shards"] for i in s["leaves"])
assert owned == list(range(manifest["num_leaves"])), manifest
state = ckpt.restore_sharded(ckpt_dir, step=2)
print(f"ckpt gate OK: rank 1 restored from its peer replica in "
      f"{prov['ms']:.0f} ms; manifest valid "
      f"({manifest['num_leaves']} leaves over 2 shards)")
EOF
rm -rf "$CK_TMP"

# Multislice gate (ISSUE 8): a forced 2-slice world's engine allreduce
# must (a) actually run the hierarchical two-fabric path — per-fabric
# byte counters nonzero with dcn_bytes == ici_bytes / slice_procs,
# (b) produce results identical to a flat run of the same payloads
# (integer-valued floats sum exactly in any association order), and
# (c) turn a seeded slice-local delay into a slice-level straggler
# verdict through the shared blame merger.
echo "== multislice gate: hierarchical two-fabric collectives =="
MS_TMP=$(mktemp -d)
cat > "$MS_TMP/worker.py" <<'EOF'
import json, os, sys

import numpy as np

import horovod_tpu as hvd
from horovod_tpu._engine_registry import peek_engine
from horovod_tpu.obs import get_registry

hvd.init()
r = hvd.rank()
outs = []
for i in range(8):
    out = hvd.allreduce(np.arange(16, dtype=np.float32) * (i + 1) + r,
                        op=hvd.Sum, name=f"g{i}")
    outs.append(np.asarray(out).tolist())
eng = peek_engine()
counters = {m["name"]: m.get("value") for m in get_registry().snapshot()
            if not m.get("tags")}
doc = {
    "rank": r, "slice": hvd.slice_id(), "num_slices": hvd.num_slices(),
    "hier": bool(eng and eng.hierarchical), "outs": outs,
    "dcn": counters.get("engine.dcn_bytes", 0),
    "ici": counters.get("engine.ici_bytes", 0),
    "metrics": get_registry().snapshot(),
}
with open(os.path.join(sys.argv[2], f"{sys.argv[1]}.rank{r}.json"), "w") as f:
    json.dump(doc, f)
hvd.shutdown()
EOF
MS_COMMON_ENV="JAX_PLATFORMS=cpu HVDTPU_EAGER_ENGINE=python HVDTPU_CYCLE_TIME=2"
env $MS_COMMON_ENV \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    XLA_FLAGS="--xla_force_host_platform_device_count=1" \
    HVDTPU_SLICE_SIZE=2 HVDTPU_HIERARCHICAL_ALLREDUCE=1 \
    timeout 180 python -m horovod_tpu.run -np 4 \
    python "$MS_TMP/worker.py" hier "$MS_TMP"
# same forced partition, flat schedule: the multislice world the
# hierarchical run is judged against (and the full-tensor DCN cost)
env $MS_COMMON_ENV \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    XLA_FLAGS="--xla_force_host_platform_device_count=1" \
    HVDTPU_SLICE_SIZE=2 \
    timeout 180 python -m horovod_tpu.run -np 4 \
    python "$MS_TMP/worker.py" flat "$MS_TMP"
echo "== multislice gate: seeded slice-local delay -> slice verdict =="
env $MS_COMMON_ENV \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    XLA_FLAGS="--xla_force_host_platform_device_count=1" \
    HVDTPU_SLICE_SIZE=2 HVDTPU_HIERARCHICAL_ALLREDUCE=1 \
    HVDTPU_FAULT_SPEC="enqueue:rank=2:count=6:action=delay:400" \
    timeout 180 python -m horovod_tpu.run -np 4 \
    python "$MS_TMP/worker.py" chaos "$MS_TMP"
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python - "$MS_TMP" <<'EOF'
import glob, json, sys

from horovod_tpu.obs import straggler as obs_straggler

tmp = sys.argv[1]


def load(tag):
    docs = [json.load(open(p))
            for p in sorted(glob.glob(f"{tmp}/{tag}.rank*.json"))]
    assert len(docs) == 4, (tag, docs)
    return sorted(docs, key=lambda d: d["rank"])


hier, flat, chaos = load("hier"), load("flat"), load("chaos")
for r in range(4):
    h = hier[r]
    assert h["num_slices"] == 2 and h["slice"] == r // 2, h
    assert h["hier"], "hierarchical path not selected"
    # (a) the two-fabric path executed, with the 1/slice_procs DCN story
    assert h["dcn"] > 0 and h["ici"] > 0, (h["dcn"], h["ici"])
    assert h["dcn"] * 2 == h["ici"], (h["dcn"], h["ici"])
    # (b) bitwise-identical to the flat run
    assert h["outs"] == flat[r]["outs"], f"rank {r}: hier != flat"
    # flat multislice pays full-tensor cost on the slow fabric
    assert flat[r]["dcn"] > 0 and flat[r]["ici"] == 0, flat[r]["dcn"]
# (c) slice-level straggler verdict from the seeded slice-1 delay
verdict = obs_straggler.merge_blames([d["metrics"] for d in chaos])
assert verdict is not None, "no straggler attribution recorded"
assert verdict["rank"] == 2, verdict
assert verdict.get("slice") == 1, verdict
print(f"multislice gate OK: dcn/ici = {hier[0]['dcn']}/{hier[0]['ici']} "
      f"(= 1/slice_procs), hier == flat bitwise, "
      f"slice verdict: slice {verdict['slice']} "
      f"({verdict['slice_blames']})")
EOF
rm -rf "$MS_TMP"

# Overlap gate (ISSUE 9): the backward-overlap gradient plane on a
# 4-device CPU mesh must (a) schedule per-bucket collectives INSIDE the
# backward — inspector-verified >=2 gradient collectives before the
# last backward compute op, while the off-mode module reads as one
# monolithic end-of-backward psum — and (b) produce training
# bitwise-equal to off for both bucket and bucket+zero1.
echo "== overlap gate: in-backward bucketed collectives =="
JAX_PLATFORMS=cpu \
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 300 python - <<'EOF'
import jax, jax.numpy as jnp, numpy as np, optax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.optim import overlap
from horovod_tpu.ops.collectives import shard_map_compat

mesh = Mesh(np.asarray(jax.devices(), dtype=object).reshape(4),
            (hvd.DP_AXIS,))

def init_params(key):
    sizes = [32, 64, 37, 64, 10]
    params = []
    for i in range(4):
        k, key = jax.random.split(key)
        params.append({"w": jax.random.normal(k, (sizes[i], sizes[i+1])) * .1,
                       "b": jnp.zeros(sizes[i+1])})
    return params

def loss_fn(params, x, y):
    h = x
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"]
        if i < 3:
            h = jax.nn.relu(h)
    return jnp.mean((h - y) ** 2)

params = init_params(jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
y = jax.random.normal(jax.random.PRNGKey(2), (16, 10))
tx = optax.sgd(0.05, momentum=0.9)

results, reports = {}, {}
for mode in overlap.MODES:
    plan = overlap.OverlapPlan(params, tx, mode=mode, mesh=mesh,
                               bucket_mb=8 / 1024.0)
    spec = plan.state_spec()
    step = jax.jit(shard_map_compat(
        plan.local_step(loss_fn), mesh=mesh,
        in_specs=(spec, P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
        out_specs=(spec, P()),
    ), donate_argnums=(0,))
    state = plan.init(params)
    reports[mode] = overlap.inspect_schedule(step.lower(state, x, y))
    for _ in range(4):
        state, loss = step(state, x, y)
    results[mode] = jax.tree_util.tree_leaves(plan.materialize(state))

# (a) per-bucket collectives inside the backward, not one monolithic psum
rep, rep_off = reports["bucket"], reports["off"]
assert rep.gradient_collectives >= 3, rep.as_dict()
assert rep.in_backward >= 2, rep.as_dict()
assert rep_off.gradient_collectives == 1 and rep_off.monolithic, \
    rep_off.as_dict()
# (b) bitwise-equal training
for mode in ("bucket", "bucket+zero1"):
    for a, b in zip(results["off"], results[mode]):
        assert bool(jnp.all(a == b)), f"{mode} diverged from off"
print(f"overlap gate OK: bucket={rep.as_dict()} off={rep_off.as_dict()}, "
      f"bucket/bucket+zero1 bitwise == off over 4 steps")
EOF

# HLO schedule-diff gate (ISSUE 12): every rank must COMPILE the same
# collective sequence for the engine fused-allreduce, the overlap
# bucket train step, and the serve sequence-sharded decode step — the
# artifact-level form of the HVD001/HVD010 invariant.  Each simulated
# rank compiles in its own process with rank-specific env; the checker
# diffs op kinds, order, replica groups, and operand bytes.  The
# --seed-divergence self-test plants a rank-guarded collective and
# requires the gate to reject it, so "gate passed" can never mean
# "checker was blind".
echo "== hlo gate: cross-rank collective-schedule diff =="
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 580 python scripts/hlo_gate.py
echo "== hlo gate: seeded divergence self-test =="
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 580 python scripts/hlo_gate.py --seed-divergence

# Serve gate (ISSUE 10): the continuous-batching serving plane.  The
# unit suite + hvdtpu-lint over the new subsystem, then one 2-proc
# acceptance run: staggered mixed-length requests through a live fleet
# with live telemetry armed — continuous admission must be observable
# (a request admitted after step 0 completes), the serve gauges must
# appear in a mid-run /metrics scrape, a deterministically killed
# serving rank must respawn and replay its in-flight requests (zero
# dropped, tokens bitwise-equal to single-stream generate).
echo "== serve gate: unit suite + lint over the subsystem =="
# slow-marked multi-proc acceptances are excluded from tier-1's budget
# (-m 'not slow') and run HERE by node id — the gate is their home.
python -m pytest tests/test_serve.py -x -q -m "not slow"
python -m pytest \
    "tests/test_serve.py::test_serve_job_staggered_requests_and_rejection" \
    "tests/test_serve.py::test_serve_chaos_kill_leader_respawn_zero_dropped" \
    -x -q
python -m horovod_tpu.analysis horovod_tpu/serve \
    --baseline horovod_tpu/analysis/baseline.json
echo "== serve gate: 2-proc continuous batching + chaos respawn + scrape =="
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 300 python - <<'EOF'
import time
import urllib.request

import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.decode import generate
from horovod_tpu.models.transformer import gpt
from horovod_tpu.serve import ServeJob

overrides = dict(num_layers=1, num_heads=2, emb_dim=32, max_len=64,
                 vocab_size=64, dtype=jnp.float32,
                 attention_impl="reference")
spec = {"size": "nano", "overrides": overrides, "seed": 3,
        "num_slots": 2, "idle_secs": 0.005}
model = gpt("nano", **overrides)
import jax
params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))

rs = np.random.RandomState(7)
prompts = [rs.randint(0, 64, rs.randint(3, 9)).tolist() for _ in range(8)]
steps = [3, 4, 5, 6, 3, 4, 5, 6]
oracle = [np.asarray(generate(model.cfg, params,
                              jnp.asarray([p], jnp.int32), s))[0].tolist()
          for p, s in zip(prompts, steps)]

# Kill the LEADER mid-stream: rank 0 is the only rank that reads the
# ingest log and writes result streams, and its step 6 is
# deterministically mid-stream (8 requests x >=3 tokens through 2
# slots need far more busy steps than 6).
job = ServeJob(
    spec, np=2,
    env={"JAX_PLATFORMS": "cpu",
         "HVDTPU_FAULT_SPEC": "worker_exit:step=6:rank=0"},
    max_retries=2, live_stats_secs=0.2, timeout=240,
).start()
rids = []
for p, s in zip(prompts, steps):
    rids.append(job.client.submit(p, max_new_tokens=s))
    time.sleep(0.05)  # staggered arrivals -> admissions mid-stream

# mid-run /metrics scrape: serve gauges must be present while slots
# are still churning (they stream as deltas, so poll until all four
# series have landed)
WANT = ("hvdtpu_serve_queue_depth", "hvdtpu_serve_active_slots",
        "hvdtpu_serve_admitted", "hvdtpu_serve_tokens_per_sec",
        # Memory plane (ISSUE 14): KV occupancy must stream live —
        # the paged-attention baseline is read off a running fleet.
        "hvdtpu_serve_kv_waste_ratio",
        # Paged KV (ISSUE 15): the page pool the admission gate judges
        # capacity in must be observable mid-run.
        "hvdtpu_serve_kv_page_size", "hvdtpu_serve_kv_page_free",
        "hvdtpu_serve_kv_page_used")
deadline = time.monotonic() + 120
serve_series = []
while time.monotonic() < deadline:
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{job.port}/metrics", timeout=5
    ).read().decode()
    serve_series = [l for l in body.splitlines()
                    if l.startswith("hvdtpu_serve_")]
    if all(any(l.startswith(w) for l in serve_series) for w in WANT):
        break
    time.sleep(0.3)
for want in WANT:
    assert any(l.startswith(want) for l in serve_series), (
        f"{want} missing from the mid-run /metrics scrape")

docs = [job.client.result(r, timeout=180) for r in rids]
results, ejob = job.stop()

# zero dropped, bitwise-equal tokens per request
for i, d in enumerate(docs):
    assert d["tokens"] == oracle[i], (
        f"request {i} tokens {d['tokens']} != oracle {oracle[i]}")
# continuous admission: some request entered after serving had begun
assert max(d["admitted_step"] for d in docs) > 1, docs
# the injected kill was recovered by respawn, and work finished in the
# post-recovery epoch
events = [e[0] for e in ejob.trace]
assert events.count("failure") == 1 and events.count("respawn") == 1, \
    ejob.trace
assert max(d["epoch"] for d in docs) >= 1, docs
assert sorted(results) == [0, 1], results
print(f"serve gate OK: 8/8 requests exact through the chaos run, "
      f"{len(serve_series)} serve series scraped, trace {ejob.trace}")
EOF

# Paged KV + width-sharded fleet gate (ISSUE 15): unit suite for the
# allocator/paged-decode/width/sampling planes, the slow-marked fleet
# acceptance by node id (np=2 width=1 -> two serving groups over the
# log partition, leader of group 1 killed mid-stream, greedy AND
# sampled streams 8/8 bitwise vs the single-engine oracle), and the
# compiled-HLO schedule diff across simulated ranks for the width-
# sharded paged decode program (scripts/hlo_gate.py runs in the full
# tier's hlo gate; the width program rides it).
echo "== paged gate: allocator + paged decode + width + sampling =="
python -m pytest tests/test_paged.py -x -q
echo "== paged gate: width-fleet chaos acceptance (by node id) =="
python -m pytest \
    "tests/test_serve.py::test_serve_width_fleet_partition_chaos_and_sampling" \
    -x -q

# Autoscale + hot-swap gate (ISSUE 13): the train→serve loop closed
# without a restart.  hvdtpu-lint clean over the new serve files (the
# poll-and-flip decision must derive from shared data only —
# HVD001/HVD010-013), the pure decision-table suite, then the two
# chaos acceptances: (1) load-driven grow through a re-minted epoch
# with in-flight requests bitwise-equal to an uninterrupted run,
# followed by a drain-driven release (cooldown respected in the
# decision trace, zero drops, no flapping); (2) a rank killed between
# shard prefetch and version flip (swap_commit/action=swap_abort) —
# the fleet converges on exactly ONE weight version (the durable flip
# record), 8/8 requests complete with oracle-exact tokens.
echo "== autoscale_swap gate: lint + decision-table suite =="
python -m horovod_tpu.analysis \
    horovod_tpu/serve/autoscale.py horovod_tpu/serve/hotswap.py \
    horovod_tpu/serve/service.py horovod_tpu/serve/frontend.py \
    --baseline horovod_tpu/analysis/baseline.json
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest tests/test_autoscale_swap.py \
    -x -q -m "not multiprocess"
echo "== autoscale_swap gate: grow-under-load + drain-release =="
JAX_PLATFORMS=cpu \
    timeout 400 python -m pytest \
    "tests/test_autoscale_swap.py::test_autoscale_grow_under_load_then_drain_release" \
    -x -q
echo "== autoscale_swap gate: mid-swap kill -> one version, 8/8 =="
JAX_PLATFORMS=cpu \
    timeout 400 python -m pytest \
    "tests/test_autoscale_swap.py::test_chaos_kill_mid_swap_converges_on_one_version" \
    "tests/test_autoscale_swap.py::test_log_compaction_bounds_store_and_replay" \
    -x -q

# Front-door gate (ISSUE 16): the sharded, supervised request plane +
# tenant-aware QoS.  hvdtpu-lint stays clean over the scheduler (the
# tenant pick must be a pure fold over the ordered log — HVD001/012),
# the fast decision-table suite (QoS table incl. the FCFS-degenerate
# byte-identity, machine-readable rejection codes, FrontDoor takeover
# on a bare KV store with no drop and no double-ingest, multi-shard
# recovery interleave, client poll backoff), then the two chaos
# acceptances by node id: (1) F=2 mixed-tenant fleet, frontend 0
# killed abruptly mid-stream — the survivor adopts its shard, the
# elastic monitor re-mints the epoch, and 8/8 requests complete
# bitwise-equal to the single-stream oracle; (2) a flooding batch
# tenant is budget-throttled (throttle counter lands in the drain
# summary) while its interactive victims all complete promptly with
# oracle tokens.
echo "== frontdoor gate: lint + decision-table suite =="
python -m horovod_tpu.analysis \
    horovod_tpu/serve/scheduler.py horovod_tpu/serve/frontend.py \
    horovod_tpu/serve/service.py \
    --baseline horovod_tpu/analysis/baseline.json
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest tests/test_frontdoor.py \
    -x -q -m "not slow"
echo "== frontdoor gate: kill-a-frontend chaos -> zero drops, bitwise =="
JAX_PLATFORMS=cpu \
    timeout 400 python -m pytest \
    "tests/test_frontdoor.py::test_frontdoor_kill_frontend_mid_stream_zero_drops_bitwise" \
    -x -q
echo "== frontdoor gate: noisy tenant throttled, victims complete =="
JAX_PLATFORMS=cpu \
    timeout 400 python -m pytest \
    "tests/test_frontdoor.py::test_frontdoor_noisy_tenant_throttled_victims_complete" \
    -x -q

# Trace gate (ISSUE 11): request-level tracing + the live MFU
# profiler.  The unit suite + hvdtpu-lint over the new obs files, a
# 2-proc training smoke through the real launcher CLI with --trace
# (engine negotiate/execute spans from BOTH ranks must land on the
# merged waterfall, and the launcher's end-of-job merge must write a
# schema-valid decomposition report), and the 2-proc serve chaos
# acceptance: leader killed mid-stream, the replayed request's spans
# from both incarnations appear stitched by epoch, every decomposed
# ttft's components sum to the histogram's sample within 5%, and the
# per-rank record embeds a cost_analysis()-derived perf.mfu
# (estimate-flagged on CPU).
echo "== trace gate: unit suite + lint over the tracing/profiler surface =="
python -m pytest tests/test_trace.py -x -q -m "not multiprocess"
python -m horovod_tpu.analysis horovod_tpu/obs/trace.py \
    horovod_tpu/obs/trace_merge.py horovod_tpu/obs/profile.py \
    --baseline horovod_tpu/analysis/baseline.json
echo "== trace gate: 2-proc launcher smoke with --trace -> engine lanes =="
TR_TMP=$(mktemp -d)
cat > "$TR_TMP/worker.py" <<'EOF'
import numpy as np
import horovod_tpu as hvd

hvd.init()
for i in range(4):
    hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name=f"t{i}")
hvd.shutdown()
EOF
# both engines must land engine-lane spans: the python engine records
# the negotiate/execute split, the native engine per-op
# enqueue->completion spans (its negotiation runs inside the C++ lib)
for ENGINE in python auto; do
    rm -f "$TR_TMP"/spans.*.json "$TR_TMP"/trace_*.json
    JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    HVDTPU_EAGER_ENGINE="$ENGINE" \
        timeout 300 python -m horovod_tpu.run -np 2 --trace "$TR_TMP/" \
        python "$TR_TMP/worker.py"
    python - "$TR_TMP" "$ENGINE" <<'EOF'
import glob, json, sys

d, engine = sys.argv[1], sys.argv[2]
rank_files = glob.glob(f"{d}/spans.*rank*.json")
assert len(rank_files) >= 2, f"expected 2 per-rank span files: {rank_files}"
wf = json.load(open(f"{d}/trace_waterfall.json"))
xs = [e for e in wf if e.get("ph") == "X"]
assert {e["args"]["rank"] for e in xs} >= {"0", "1"}, (
    "waterfall is missing a rank's spans")
lanes = {m["args"]["name"] for m in wf
         if m.get("ph") == "M" and m["name"] == "process_name"}
assert "engine" in lanes, f"no engine step lane, lanes={lanes}"
want = ("negotiate", "execute") if engine == "python" else \
    ("negotiate", "execute", "collective")
assert any(e["name"] in want for e in xs), "no engine-lane spans"
rep = json.load(open(f"{d}/trace_report.json"))
assert rep["schema"] == "hvdtpu-trace-report-v1", rep["schema"]
assert rep["missing_ranks"] == [], rep["missing_ranks"]
print(f"trace gate OK ({engine} engine): {len(xs)} spans across "
      f"lanes {sorted(lanes)}")
EOF
done
rm -rf "$TR_TMP"
echo "== trace gate: 2-proc serve chaos -> stitched waterfall + ttft decomposition + mfu =="
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 420 python -m pytest \
    "tests/test_trace.py::test_trace_acceptance_leader_kill_waterfall_and_mfu" \
    -x -q

# Memory gate (ISSUE 14): the HBM memory plane.  hvdtpu-lint clean
# over the new surface, the unit suite, then the two artifact gates:
# every collective-bearing program's per-device footprint must stay
# under the committed memory_budget.json ceiling (and a seeded 64x
# oversized program must be rejected — a budget that cannot fail is
# decorative), the PR-9 ZeRO-1 claim is asserted from the compiled
# programs' input buffers (optimizer-state bytes under bucket+zero1
# <= 1/world + eps of bucket mode on the 8-device mesh), and the OOM
# chaos acceptance: a seeded backend-shaped RESOURCE_EXHAUSTED on one
# rank must leave a postmortem whose verdict names the dying rank AND
# its dominant memory owner.
echo "== mem gate: lint + unit suite =="
python -m horovod_tpu.analysis horovod_tpu/obs/memplane.py \
    scripts/mem_gate.py \
    --baseline horovod_tpu/analysis/baseline.json
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest tests/test_memplane.py -q \
    -m "not multiprocess and not slow"
echo "== mem gate: compile-heavy coverage (slot-engine kv + 8-dev zero1) =="
JAX_PLATFORMS=cpu \
    timeout 400 python -m pytest \
    "tests/test_memplane.py::test_slot_engine_kv_stats_match_hand_computed" \
    "tests/test_memplane.py::test_zero1_budget_math_on_8_device_mesh" \
    -x -q
echo "== mem gate: per-program budget + zero1 ratio from the artifact =="
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 580 python scripts/mem_gate.py
echo "== mem gate: seeded budget violation must fail =="
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 580 python scripts/mem_gate.py --seed-violation
echo "== mem gate: OOM chaos -> postmortem names rank + dominant owner =="
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    timeout 400 python -m pytest \
    "tests/test_memplane.py::test_oom_chaos_postmortem_names_rank_and_owner" \
    -x -q

# Health gate (ISSUE 18): the training-health plane must (a) pass its
# unit suite, (b) leave the compiled step HLO byte-identical when
# --health is off, and (c) survive the SDC chaos proof — a seeded
# single-bit exponent flip on rank 1's copy of the 6th reduced gradient
# (training step 2, leaf w2 — bucket 0 in the reverse-topological
# layout) must be localized by the divergence
# sentinel to that exact rank + bucket + leaf within one check interval,
# halt every rank, and be named in the postmortem verdict.  A clean run
# of the same worker must alert nothing and write no postmortem.
echo "== health gate: unit suite =="
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest tests/test_health.py -x -q
echo "== health gate: --health off leaves compiled HLO unchanged =="
JAX_PLATFORMS=cpu \
    timeout 300 python -m pytest \
    "tests/test_health.py::test_health_off_leaves_compiled_hlo_byte_identical" \
    -x -q
echo "== health gate: SDC chaos -> sentinel names rank 1 + leaf w2 =="
HL_TMP=$(mktemp -d)
cat > "$HL_TMP/worker.py" <<'EOF'
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.obs import divergence
from horovod_tpu.obs.health import HealthConfig
from horovod_tpu.optim.overlap import build_layout

hvd.init()
params = {"w0": np.zeros(4, np.float32),
          "w1": np.zeros(4, np.float32),
          "w2": np.zeros(4, np.float32)}
names = sorted(params)                 # tree_flatten order: w0, w1, w2
leaves = [params[n] for n in names]
layout = build_layout(params, 16)      # 16B buckets: one leaf per bucket
cfg = HealthConfig.from_env()
sentinel = divergence.DivergenceSentinel(
    layout, rank=hvd.rank(), check_steps=cfg.check_steps,
    action=cfg.divergence_action, leaf_names=names)
for step in range(1, 9):
    # grad_ready collective seq: step 1 -> 1,2,3; step 2 -> 4,5,6, so
    # the seeded seq-6 flip lands on rank 1's copy of w2 — bucket 0,
    # since build_layout packs in reverse flatten order.
    for i, leaf in enumerate(leaves):
        leaf += np.asarray(
            hvd.allreduce(np.full(4, 0.1, np.float32), op=hvd.Sum,
                          name=f"g{i}"))
    sentinel.maybe_check(step, leaves)
hvd.shutdown()
EOF
mkdir -p "$HL_TMP/bb"
if JAX_PLATFORMS=cpu \
   PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
   HVDTPU_HEALTH=on HVDTPU_HEALTH_CHECK_STEPS=4 \
   HVDTPU_DIVERGENCE_ACTION=halt \
   HVDTPU_FAULT_SPEC="grad_ready:rank=1:step=6:action=flip_bits" \
       timeout 300 python -m horovod_tpu.run -np 2 \
       --flightrec-dump "$HL_TMP/bb" python "$HL_TMP/worker.py"; then
    echo "health gate FAILED: corrupted job reported success" >&2
    exit 1
fi
python - "$HL_TMP/bb" <<'EOF'
import glob, json, sys
d = sys.argv[1]
dumps = glob.glob(f"{d}/flightrec.*rank*.json")
assert len(dumps) == 2, f"expected 2 per-rank black boxes, got {dumps}"
events = [e for p in dumps for e in json.load(open(p))["events"]
          if e["kind"] == "health.divergence"]
assert events, "no health.divergence flightrec event recorded"
for ev in events:
    fields = dict(kv.split("=", 1) for kv in ev["detail"].split())
    assert fields["minority"] == "1", ev
    assert fields["bucket"] == "0", ev
    assert fields["leaf"] == "w2", ev
    assert ev["cycle"] == 4, ev  # first check interval after the flip
report = json.load(open(f"{d}/postmortem.json"))
v = report["verdict"]
assert "TRAINING-STATE DIVERGENCE" in v, v
assert "rank(s) 1" in v and "bucket0" in v and "w2" in v, v
print("health gate OK:", v.splitlines()[0])
EOF
echo "== health gate: clean run alerts nothing =="
mkdir -p "$HL_TMP/clean"
JAX_PLATFORMS=cpu \
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
HVDTPU_HEALTH=on HVDTPU_HEALTH_CHECK_STEPS=4 \
HVDTPU_DIVERGENCE_ACTION=halt \
    timeout 300 python -m horovod_tpu.run -np 2 \
    --flightrec-dump "$HL_TMP/clean" python "$HL_TMP/worker.py"
if [ -e "$HL_TMP/clean/postmortem.json" ]; then
    echo "health gate FAILED: clean run wrote a postmortem" >&2
    exit 1
fi
if grep -l "health.divergence\|health.alert" \
        "$HL_TMP"/clean/flightrec.*rank*.json 2>/dev/null; then
    echo "health gate FAILED: clean run recorded a health alert" >&2
    exit 1
fi
rm -rf "$HL_TMP"

# Elastic chaos smoke through the real launcher: a rank is killed
# deterministically mid-training (HVDTPU_FAULT_SPEC), the job must
# recover via rollback + respawn (the example asserts it did).
echo "== elastic chaos smoke: recovery after injected worker death =="
JAX_PLATFORMS=cpu python examples/elastic_train.py \
    --np 3 --fault worker_exit:step=4:rank=1
echo "== elastic chaos smoke: shrink when the respawn budget is spent =="
JAX_PLATFORMS=cpu python examples/elastic_train.py \
    --np 3 --fault worker_exit:step=4:rank=1 \
    --max-retries 0 --min-workers 2
echo "== elastic chaos smoke: deadlocked training thread caught by beat =="
JAX_PLATFORMS=cpu python examples/elastic_train.py \
    --np 3 --fault worker_exit:step=4:rank=1:action=hang \
    --progress-timeout 2
echo "matrix OK"
