"""hvdtpu-lint test suite (ISSUE 5).

Coverage contract (acceptance criteria):

* every rule ID has at least one FIRING fixture and one NON-FIRING
  fixture (parametrized below from ``FIXTURES`` — a new rule without
  fixtures fails ``test_every_rule_has_fixtures``);
* CLI behavior: exit codes, ``--format json`` schema, baseline
  matching (reasoned entries only), inline suppression comments,
  ``--rules`` filtering;
* a regression case reproducing the PR-4 reentrant-flush deadlock
  shape (SIGTERM-inside-SIGUSR1: a non-reentrant lock on the
  signal-flush path), which HVDC103 must catch.

Fixture sources live as string literals so the analyzer never sees
them when linting tests/ itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.analysis import all_rules, analyze_paths
from horovod_tpu.analysis.baseline import (
    BASELINE_SCHEMA,
    BaselineError,
    load_baseline,
)
from horovod_tpu.analysis.config import load_config

# ---------------------------------------------------------------------------
# fixtures: rule id -> (firing source, clean source)
# ---------------------------------------------------------------------------

FIXTURES = {
    "HVD001": (
        """
        import horovod_tpu as hvd

        def step(x):
            if hvd.rank() == 0:
                return hvd.allreduce(x)
            return x
        """,
        """
        import horovod_tpu as hvd

        def step(x):
            total = hvd.allreduce(x)
            if hvd.rank() == 0:
                print(total)
            return total
        """,
    ),
    "HVD002": (
        """
        import horovod_tpu as hvd

        def reduce_all(grads):
            for k in {"w", "b"}:
                grads[k] = hvd.allreduce(grads[k])
        """,
        """
        import horovod_tpu as hvd

        def reduce_all(grads):
            for k in sorted({"w", "b"}):
                grads[k] = hvd.allreduce(grads[k])
        """,
    ),
    "HVD003": (
        """
        import horovod_tpu as hvd

        def step(x, spiked):
            if spiked:
                x = hvd.allreduce(x)
            return x
        """,
        """
        import horovod_tpu as hvd

        def step(x, spiked):
            if spiked:
                x = hvd.allreduce(x, name="spike_fix")
            return x
        """,
    ),
    "HVD004": (
        """
        import optax
        import horovod_tpu as hvd

        def main(params):
            hvd.init()
            tx = hvd.DistributedOptimizer(optax.adam(1e-3))
            return tx.init(params)

        if __name__ == "__main__":
            main({})
        """,
        """
        import optax
        import horovod_tpu as hvd

        def main(params):
            hvd.init()
            params = hvd.broadcast_parameters(params, root_rank=0)
            tx = hvd.DistributedOptimizer(optax.adam(1e-3))
            return tx.init(params)

        if __name__ == "__main__":
            main({})
        """,
    ),
    "HVD005": (
        """
        import horovod_tpu as hvd

        IS_CHIEF = hvd.rank() == 0
        """,
        """
        import horovod_tpu as hvd

        hvd.init()
        IS_CHIEF = hvd.rank() == 0
        """,
    ),
    "HVD006": (
        """
        import horovod_tpu as hvd

        def step(x):
            try:
                return hvd.allreduce(x, name="g")
            except Exception:
                return hvd.allreduce(x, name="retry")
        """,
        """
        import horovod_tpu as hvd

        def step(x):
            try:
                return hvd.allreduce(x, name="g")
            finally:
                hvd.barrier()
        """,
    ),
    "HVD007": (
        """
        import horovod_tpu as hvd

        def step(x):
            return hvd.allreduce(x, name=f"grad_{hvd.rank()}")
        """,
        """
        import horovod_tpu as hvd

        def step(x):
            return hvd.allreduce(x, name="grad_w0")
        """,
    ),
    "HVD008": (
        """
        from jax.experimental import multihost_utils

        def checkpoint_barrier():
            multihost_utils.sync_global_devices("ckpt")
        """,
        """
        import horovod_tpu as hvd

        def checkpoint_barrier():
            hvd.barrier()
        """,
    ),
    "HVD009": (
        """
        import jax

        def local_step(params, opt_state, batch):
            return params, opt_state

        step = jax.jit(local_step)
        """,
        """
        import jax

        def local_step(params, opt_state, batch):
            return params, opt_state

        step = jax.jit(local_step, donate_argnums=(0, 1))
        """,
    ),
    "HVD010": (
        """
        import horovod_tpu as hvd
        from jax import lax

        def reduce_part(flag, x):
            if flag == 0:
                return lax.psum(x, "hvd_local")
            return x

        def step(x):
            return reduce_part(hvd.local_rank(), x)
        """,
        """
        import horovod_tpu as hvd
        from jax import lax

        def reduce_part(flag, x):
            y = lax.psum(x, "hvd_local")
            if flag == 0:
                return y
            return y * 0

        def step(x):
            return reduce_part(hvd.local_rank(), x)
        """,
    ),
    "HVD011": (
        """
        from jax import lax

        def step(x, fast_path):
            axis = "hvd_local" if fast_path else "hvd_cross"
            return lax.psum(x, axis)
        """,
        """
        from jax import lax

        def step(x):
            return lax.psum(x, ("hvd_local", "hvd_cross"))
        """,
    ),
    "HVD012": (
        """
        import random

        # hvdtpu: deterministic
        def pick_slot(queue, slots):
            return random.choice(slots)
        """,
        """
        # hvdtpu: deterministic
        def pick_slot(queue, slots):
            return min(slots)
        """,
    ),
    "HVD013": (
        """
        import horovod_tpu as hvd

        def record(trace, tid, t0, t1):
            if hvd.rank() == 0:
                trace.add_span(tid, "decode", t0, t1)
        """,
        """
        def record(trace, tid, t0, t1, enabled):
            if enabled:
                trace.add_span(tid, "decode", t0, t1)
        """,
    ),
    "HVDC101": (
        """
        import threading

        _table_lock = threading.Lock()
        _stats_lock = threading.Lock()

        def update_table():
            with _table_lock:
                with _stats_lock:
                    pass

        def update_stats():
            with _stats_lock:
                with _table_lock:
                    pass
        """,
        """
        import threading

        _table_lock = threading.Lock()
        _stats_lock = threading.Lock()

        def update_table():
            with _table_lock:
                with _stats_lock:
                    pass

        def update_stats():
            with _table_lock:
                with _stats_lock:
                    pass
        """,
    ),
    "HVDC102": (
        """
        import threading
        import time

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()

            def cycle(self):
                with self._lock:
                    time.sleep(1.0)
        """,
        """
        import threading
        import time

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()

            def cycle(self):
                with self._lock:
                    pending = 1
                time.sleep(1.0)
                return pending
        """,
    ),
    "HVDC103": (
        """
        import signal
        import threading

        _lock = threading.Lock()

        def _flush():
            with _lock:
                pass

        def _handler(signum, frame):
            _flush()

        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
        """
        import signal
        import threading

        _lock = threading.RLock()

        def _flush():
            with _lock:
                pass

        def _handler(signum, frame):
            _flush()

        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
    ),
    "HVDC104": (
        """
        import logging
        import signal

        LOG = logging.getLogger("x")

        def _handler(signum, frame):
            LOG.warning("dying")

        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
        """
        import logging
        import signal

        LOG = logging.getLogger("x")

        def _handler(signum, frame):
            pass

        def install():
            signal.signal(signal.SIGTERM, _handler)
            LOG.info("hooks installed")  # outside signal context
        """,
    ),
    "HVDC105": (
        """
        import horovod_tpu as hvd

        def step(g):
            try:
                return hvd.allreduce(g, name="g")
            except Exception:
                return g
        """,
        """
        import horovod_tpu as hvd
        from horovod_tpu.exceptions import HorovodShutdownError

        def step(g):
            try:
                return hvd.allreduce(g, name="g")
            except HorovodShutdownError:
                raise
            except Exception:
                return g
        """,
    ),
    "HVDC106": (
        """
        import time

        from horovod_tpu.obs.flightrec import on_death

        def _flush():
            time.sleep(1.0)

        def arm():
            on_death(_flush)
        """,
        """
        from horovod_tpu.obs.flightrec import on_death

        def _flush():
            pass

        def arm():
            on_death(_flush)
        """,
    ),
    "HVDC107": (
        """
        import signal

        def _handler(signum, frame):
            events = []
            while True:
                events.append(frame)

        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
        """
        import signal

        def _handler(signum, frame):
            events = []
            while True:
                events.append(frame)
                if len(events) > 8:
                    break

        def install():
            signal.signal(signal.SIGTERM, _handler)
        """,
    ),
    "HVDC108": (
        """
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
                self._depth = 0  # write outside the inferred guard
        """,
        """
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
                with self._lock:
                    self._depth = 0
        """,
    ),
    "HVDC109": (
        """
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._value += 1
                with self._lock:
                    self._value = 0

            def peek(self):
                return self._value  # read outside the write guard
        """,
        """
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self._value = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._value += 1
                with self._lock:
                    self._value = 0

            def peek(self):
                with self._lock:
                    return self._value
        """,
    ),
    "HVDC110": (
        """
        import threading

        class Once:
            def __init__(self):
                self._lock = threading.Lock()
                self._started = False

            def launch(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                with self._lock:
                    self._started = False

            def start(self):
                if not self._started:  # test outside the lock
                    with self._lock:
                        self._started = True  # act under it
        """,
        """
        import threading

        class Once:
            def __init__(self):
                self._lock = threading.Lock()
                self._started = False

            def launch(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                with self._lock:
                    self._started = False

            def start(self):
                with self._lock:
                    if not self._started:
                        self._started = True
        """,
    ),
}


def _lint_source(tmp_path, source, name="snippet.py", rules=None):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return analyze_paths([str(path)], root=str(tmp_path), rules=rules)


def _new(findings, rule=None):
    return [
        f for f in findings
        if f.status == "new" and (rule is None or f.rule == rule)
    ]


# ---------------------------------------------------------------------------
# per-rule firing / non-firing
# ---------------------------------------------------------------------------


def test_every_rule_has_fixtures():
    missing = set(all_rules()) - set(FIXTURES)
    assert not missing, f"rules without fixtures: {sorted(missing)}"
    assert len(all_rules()) >= 12  # acceptance criterion


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_fires_on_bad_fixture(tmp_path, rule_id):
    bad, _ = FIXTURES[rule_id]
    findings = _lint_source(tmp_path, bad)
    assert _new(findings, rule_id), (
        f"{rule_id} did not fire; findings: "
        f"{[(f.rule, f.message) for f in findings]}"
    )


@pytest.mark.parametrize("rule_id", sorted(FIXTURES))
def test_rule_quiet_on_clean_fixture(tmp_path, rule_id):
    _, good = FIXTURES[rule_id]
    findings = _lint_source(tmp_path, good)
    hits = _new(findings, rule_id)
    assert not hits, (
        f"{rule_id} fired on the clean fixture: "
        f"{[f.message for f in hits]}"
    )


# ---------------------------------------------------------------------------
# rule-specific edge cases
# ---------------------------------------------------------------------------


def test_hvd001_early_exit_guard(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def save(x):
            if hvd.rank() != 0:
                return None
            return hvd.allreduce(x)
    """)
    assert _new(findings, "HVD001")


def test_hvd001_uniform_size_guard_ok(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def maybe(x):
            if hvd.size() > 1:
                return hvd.allreduce(x)
            return x
    """)
    assert not _new(findings, "HVD001")


def test_hvd002_dict_items_fires_and_sorted_ok(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def reduce_all(grads):
            for k, v in grads.items():
                grads[k] = hvd.allreduce(v)
    """)
    assert _new(findings, "HVD002")
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def reduce_all(grads):
            for k in sorted(grads.keys()):
                grads[k] = hvd.allreduce(grads[k])
    """)
    assert not _new(findings, "HVD002")


def test_hvd003_main_guard_exempt(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        if __name__ == "__main__":
            hvd.init()
            hvd.allreduce([1.0])
    """)
    assert not _new(findings, "HVD003")


def test_hvd005_function_scope_ok(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def who_am_i():
            return hvd.rank()
    """)
    assert not _new(findings, "HVD005")


def test_hvd009_resolves_through_shard_map_wrapper(tmp_path):
    findings = _lint_source(tmp_path, """
        import jax
        from jax import shard_map

        def local_step(params, opt_state, xb):
            return params, opt_state

        step = jax.jit(shard_map(local_step, mesh=None,
                                 in_specs=(), out_specs=()))
    """)
    assert _new(findings, "HVD009")


def test_hvd009_quiet_on_stateless_apply_and_donate_argnames(tmp_path):
    findings = _lint_source(tmp_path, """
        import jax

        apply = jax.jit(lambda p, xb: p @ xb)

        def local_step(params, opt_state, xb):
            return params, opt_state

        step = jax.jit(local_step, donate_argnames=("params",))
    """)
    assert not _new(findings, "HVD009")


def test_hvd009_resolution_is_scope_first(tmp_path):
    # Two builders bind the same name to different callables: each jit
    # call must be judged against ITS OWN function's binding — the
    # stateless apply stays quiet, the train step fires.
    findings = _lint_source(tmp_path, """
        import jax
        from jax import shard_map

        def build_eval():
            step = shard_map(lambda p_, xb: p_, mesh=None,
                             in_specs=(), out_specs=())
            return jax.jit(step)

        def build_train():
            def local(params, opt_state, xb):
                return params, opt_state
            step = shard_map(local, mesh=None, in_specs=(), out_specs=())
            return jax.jit(step)
    """)
    hits = _new(findings, "HVD009")
    assert len(hits) == 1, [f.message for f in hits]
    assert "params" in hits[0].message


def test_hvd009_name_does_not_resolve_to_same_named_method(tmp_path):
    # Regression: `init = shard_map(lambda bufs: ..., ...)` then
    # jax.jit(init) must not resolve `init` to an unrelated class's
    # `init(self, params)` method and convict the lambda.
    findings = _lint_source(tmp_path, """
        import jax
        from jax import shard_map

        class Plan:
            def init(self, params):
                return params

        def build():
            init = shard_map(lambda bufs: bufs, mesh=None,
                             in_specs=(), out_specs=())
            return jax.jit(init)
    """)
    assert not _new(findings, "HVD009")


def test_hvdc105_stored_exception_ok(tmp_path):
    # checkpoint.py's deferred-error pattern: the handler KEEPS the
    # exception (re-raised later) — not a swallow.
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        class Save:
            def wait(self, g):
                try:
                    return hvd.allreduce(g, name="g")
                except Exception as exc:
                    self._error = exc
                    return None
    """)
    assert not _new(findings, "HVDC105")


def test_hvdc102_via_callee(tmp_path):
    # The blocking call hides one call level down, same module.
    findings = _lint_source(tmp_path, """
        import threading

        class Pub:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=lambda: None)

            def _stop_worker(self):
                self._thread.join(timeout=2)

            def stop(self):
                with self._lock:
                    self._stop_worker()
    """)
    hits = _new(findings, "HVDC102")
    assert hits and "join" in hits[0].message


def test_thread_target_closure_not_signal_reachable(tmp_path):
    # exec.py's mitigation pattern: the handler only SPAWNS a thread;
    # the closure doing lock work runs outside signal context.
    findings = _lint_source(tmp_path, """
        import signal
        import threading

        _lock = threading.Lock()

        def _handler(signum, frame):
            def _work():
                with _lock:
                    pass
            threading.Thread(target=_work, daemon=True).start()

        def install():
            signal.signal(signal.SIGTERM, _handler)
    """)
    assert not _new(findings, "HVDC103")


# ---------------------------------------------------------------------------
# interprocedural taint edge cases (ISSUE 12)
# ---------------------------------------------------------------------------


def test_hvd010_taint_through_kwarg(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def reduce_part(x, flag=0):
            if flag == 0:
                return lax.psum(x, "hvd_local")
            return x

        def step(x):
            return reduce_part(x, flag=hvd.local_rank())
    """)
    hits = _new(findings, "HVD010")
    assert hits and "flag" in hits[0].message, \
        [f.message for f in findings]


def test_hvd010_taint_through_returned_tuple(tmp_path):
    # A rank carried inside a returned tuple must not launder through
    # unpacking.
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def who_and_size():
            return hvd.rank(), hvd.size()

        def step(x):
            r, n = who_and_size()
            if r == 0:
                return lax.psum(x, "hvd_local")
            return x
    """)
    hits = _new(findings, "HVD010")
    assert hits and "who_and_size" in hits[0].message, \
        [f.message for f in findings]


def test_hvd010_sanitized_by_uniform_broadcast(tmp_path):
    # An allreduce result is identical on every rank: branching on it
    # is safe even though a rank value flowed in.
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def step(x):
            chief = hvd.allreduce(hvd.rank(), name="who")
            if chief == 0:
                return lax.psum(x, "hvd_local")
            return x
    """)
    assert not _new(findings, "HVD010"), \
        [f.message for f in _new(findings, "HVD010")]


def test_hvd010_three_frame_chain_attribution(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def helper(x, r):
            if r > 0:
                return x
            return lax.psum(x, "hvd_cross")

        def mid(x, rr):
            return helper(x, rr)

        def top(x):
            return mid(x, hvd.cross_rank())
    """)
    hits = _new(findings, "HVD010")
    assert hits, [f.message for f in findings]
    # call-chain attribution: every frame named, caller-first
    msg = hits[0].message
    assert "top" in msg and "mid" in msg and "helper" in msg


def test_hvd010_scoped_taint_is_uniform_off_axis(tmp_path):
    # local_rank() differs WITHIN a local group but is uniform within a
    # cross group (the group fixes every other mesh coordinate): a
    # local-scoped guard around a CROSS collective must stay quiet.
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def reduce_cross(x, lr):
            if lr == 0:
                return lax.psum(x, "hvd_cross")
            return x

        def step(x):
            return reduce_cross(x, hvd.local_rank())
    """)
    assert not _new(findings, "HVD010"), \
        [f.message for f in _new(findings, "HVD010")]


def test_hvd010_param_laundered_in_callee_stays_quiet(tmp_path):
    # The callee itself launders the tainted parameter along the
    # collective's axis before branching on it: uniform by the time it
    # reaches the guard, whatever the caller passed in.  Two distinct
    # regressions hid here — ValueTaint.merge wiped the sanitized set
    # when merging into a fresh value, and the parameter-hazard path
    # never consulted it.
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def reduce_part(flag, x):
            flag = lax.psum(flag, "hvd_local")
            if flag == 0:
                return lax.psum(x, "hvd_local")
            return x

        def step(x):
            return reduce_part(hvd.local_rank(), x)
    """)
    assert not _new(findings, "HVD010"), \
        [f.message for f in _new(findings, "HVD010")]


def test_hvd011_same_name_in_unrelated_functions_stays_quiet(tmp_path):
    # Two helpers each binding their own constant `axis` are two
    # single-axis call sites — the assignment map is scoped per
    # enclosing function, not per file.
    findings = _lint_source(tmp_path, """
        from jax import lax

        def local_reduce(x):
            axis = "hvd_local"
            return lax.psum(x, axis)

        def cross_reduce(x):
            axis = "hvd_cross"
            return lax.psum(x, axis)
    """)
    assert not _new(findings, "HVD011"), \
        [f.message for f in _new(findings, "HVD011")]


def test_hvd011_reassigned_selector_in_one_function_fires(tmp_path):
    findings = _lint_source(tmp_path, """
        from jax import lax

        def pick(x, fast):
            axis = "hvd_local"
            if fast:
                axis = "hvd_cross"
            return lax.psum(x, axis)
    """)
    assert _new(findings, "HVD011")


def test_hvd010_world_taint_diverges_in_every_subgroup(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from jax import lax

        def step(x):
            if hvd.rank() == 0:
                return lax.psum(x, "hvd_local")
            return x
    """)
    assert _new(findings, "HVD010")


def test_hvd012_impure_helper_via_call_tree(tmp_path):
    findings = _lint_source(tmp_path, """
        import time

        def now_ms():
            return time.time() * 1000

        # hvdtpu: deterministic
        def pick_slot(queue, slots):
            t = now_ms()
            return slots[int(t) % len(slots)]
    """)
    hits = _new(findings, "HVD012")
    assert hits and "now_ms" in " ".join(f.message for f in hits), \
        [f.message for f in findings]


def test_hvd012_impure_arg_into_contract_function(tmp_path):
    findings = _lint_source(tmp_path, """
        import random

        # hvdtpu: deterministic
        def pick_slot(queue, seed):
            return queue[seed % len(queue)]

        def caller(queue):
            return pick_slot(queue, random.randint(0, 7))
    """)
    hits = _new(findings, "HVD012")
    assert hits and any("flows into" in f.message for f in hits), \
        [f.message for f in findings]


def test_hvd013_rank_in_sampled_args(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd
        from horovod_tpu.obs.trace import sampled

        def should_trace(tid):
            return sampled(f"{tid}-{hvd.rank()}")
    """)
    assert _new(findings, "HVD013")


# ---------------------------------------------------------------------------
# PR-4 regression: the reentrant-flush deadlock shape
# ---------------------------------------------------------------------------


def test_pr4_reentrant_flush_deadlock_shape(tmp_path):
    """The bug PR 4 fixed by hand: SIGUSR1's flush holds a module lock
    when SIGTERM lands on the same thread; the SIGTERM handler re-enters
    flush() and deadlocks on a non-reentrant Lock.  The signal pass must
    flag the Lock (HVDC103) — and must go quiet once it is an RLock,
    which is exactly the shipped fix in obs/flightrec.py."""
    bad = """
        import signal
        import threading

        _death_lock = threading.Lock()
        _callbacks = []

        def flush(trigger):
            with _death_lock:
                cbs = list(_callbacks)
            for fn in cbs:
                fn()

        def _signal_handler(signum, frame):
            flush(f"signal:{signum}")

        def install_death_hooks():
            for sig in (signal.SIGTERM, signal.SIGUSR1):
                signal.signal(sig, _signal_handler)
    """
    findings = _lint_source(tmp_path, bad, name="flightrec_shape.py")
    hits = _new(findings, "HVDC103")
    assert hits, "the PR-4 deadlock shape must be rejected"
    assert "_death_lock" in hits[0].message
    fixed = bad.replace("threading.Lock()", "threading.RLock()")
    findings = _lint_source(tmp_path, fixed, name="flightrec_shape.py")
    assert not _new(findings, "HVDC103")


# ---------------------------------------------------------------------------
# race rules (HVDC108-110): guarded-by inference edge cases
# ---------------------------------------------------------------------------


def test_racer_init_writes_exempt(tmp_path):
    """Construction-time writes (in __init__ and init-only callees,
    before the first escape) are exempt from guard coverage: they
    happen before any other thread can hold a reference."""
    src = """
        import threading

        class Table:
            def __init__(self):
                self._lock = threading.Lock()
                self._rows = []       # unguarded, but pre-escape
                self._fill()

            def _fill(self):
                self._rows.append(0)  # init-only callee: same exemption

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._rows.append(1)
                with self._lock:
                    self._rows.append(2)
                with self._lock:
                    self._rows.pop()
                with self._lock:
                    self._rows.clear()

            def snap(self):
                with self._lock:
                    return list(self._rows)
    """
    findings = _lint_source(tmp_path, src)
    assert not _new(findings, "HVDC108"), \
        [f.message for f in _new(findings, "HVDC108")]
    assert not _new(findings, "HVDC109")


def test_racer_unescaped_class_never_reported(tmp_path):
    """The RacerD ownership rule: a lock-owning class whose instances
    never escape to another thread (no spawn, no registry handoff, no
    module global) is single-threaded as far as the analysis can see —
    even a field with a broken guard protocol stays quiet."""
    src = """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._depth = 0

            def _run(self):
                with self._lock:
                    self._depth += 1
                with self._lock:
                    self._depth -= 1

            def depth(self):
                with self._lock:
                    return self._depth

            def spill(self):
                self._depth = 0  # would be HVDC108 if Pump escaped
    """
    findings = _lint_source(tmp_path, src)
    for rid in ("HVDC108", "HVDC109", "HVDC110"):
        assert not _new(findings, rid), rid


def test_racer_callee_held_lock_counts_as_guarded(tmp_path):
    """Interprocedural held-lock closure: a write in a helper with no
    visible ``with`` is guarded when EVERY call path into the helper
    holds the lock (the HVDC101-style fixpoint) — and becomes a finding
    the moment one lockless call site appears."""
    quiet = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._bump()
                with self._lock:
                    self._n = 0

            def get(self):
                with self._lock:
                    return self._n

            def peek(self):
                with self._lock:
                    return self._n

            def _bump(self):
                self._n += 1  # every caller holds self._lock
    """
    findings = _lint_source(tmp_path, quiet)
    assert not _new(findings, "HVDC108"), \
        [f.message for f in _new(findings, "HVDC108")]
    racy = quiet + """
            def poke(self):
                self._bump()  # lockless path into the helper
    """
    findings = _lint_source(tmp_path, racy)
    hits = _new(findings, "HVDC108")
    assert hits, "lockless call path into _bump must fire"
    assert "Counter" in hits[0].message
    assert "_n" in hits[0].message
    assert "_lock" in hits[0].message


def test_racer_no_dominant_guard_stays_quiet(tmp_path):
    """Threshold edge: with one guarded write, one unguarded write and
    an unguarded read, no lock reaches the guard fraction on either the
    all-access or the write-side criterion — no discernible discipline
    means nothing to enforce (reporting here would be noise)."""
    src = """
        import threading

        class Mixed:
            def __init__(self):
                self._lock = threading.Lock()
                self._x = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                with self._lock:
                    self._x = 1

            def a(self):
                self._x = 2

            def b(self):
                return self._x
    """
    findings = _lint_source(tmp_path, src)
    assert not _new(findings, "HVDC108")
    assert not _new(findings, "HVDC109")


# ---------------------------------------------------------------------------
# PR-20 self-application regressions: the races the rules found & fixed
# ---------------------------------------------------------------------------


def test_race_fix_engine_pending_params_shape(tmp_path):
    """Reduced shape of the EagerEngine._pending_params race: the
    negotiation loop drains the field under the engine lock and the
    replay path writes it under the lock, but the post-negotiation
    store skipped it.  HVDC108 must fire on the lockless store and go
    quiet once it is inside the lock — the shipped fix in
    runtime/engine.py."""
    bad = """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._pending = None

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    with self._lock:
                        req = self._pending
                        self._pending = None
                    self._negotiate(req)

            def _negotiate(self, req):
                self._pending = req  # the bug: lockless store

            def replay(self, req):
                with self._lock:
                    self._pending = req
    """
    findings = _lint_source(tmp_path, bad, name="engine_shape.py")
    hits = _new(findings, "HVDC108")
    assert hits, "the pending-params shape must be rejected"
    assert "_pending" in hits[0].message
    fixed = bad.replace(
        "self._pending = req  # the bug: lockless store",
        "with self._lock:\n"
        "                    self._pending = req",
    )
    findings = _lint_source(tmp_path, fixed, name="engine_shape.py")
    assert not _new(findings, "HVDC108"), \
        [f.message for f in _new(findings, "HVDC108")]


def test_race_fix_frontend_stats_snapshot_shape(tmp_path):
    """Reduced shape of the FrontDoor.stats() race: the supervisor
    thread mutates owners/epoch under the lock while stats() reads them
    bare (the one-guarded-writer-many-lockless-readers shape that the
    write-side guard criterion exists for).  HVDC109 must fire on both
    fields; the snapshot-under-lock fix must be quiet."""
    bad = """
        import threading

        class Door:
            def __init__(self):
                self._lock = threading.Lock()
                self.owners = {}
                self.epoch = 0

            def start(self):
                threading.Thread(target=self._watch).start()

            def _watch(self):
                while True:
                    with self._lock:
                        self.owners = {"s0": "fe1"}
                        self.epoch += 1

            def stats(self):
                return {"owners": dict(self.owners),
                        "epoch": self.epoch}
    """
    findings = _lint_source(tmp_path, bad, name="door_shape.py")
    hits = _new(findings, "HVDC109")
    assert {m for f in hits for m in ("owners", "epoch")
            if m in f.message} == {"owners", "epoch"}, \
        [f.message for f in hits]
    fixed = bad.replace(
        'return {"owners": dict(self.owners),\n'
        '                        "epoch": self.epoch}',
        'with self._lock:\n'
        '                    return {"owners": dict(self.owners),\n'
        '                            "epoch": self.epoch}',
    )
    assert fixed != bad
    findings = _lint_source(tmp_path, fixed, name="door_shape.py")
    assert not _new(findings, "HVDC109"), \
        [f.message for f in _new(findings, "HVDC109")]


def test_race_fix_frontend_publish_doc_shape(tmp_path):
    """Reduced shape of the FrontDoor._publish_doc race: building the
    discovery document read owners/epoch with no lock before handing it
    to the KV store.  The fix snapshots under the lock and publishes
    outside it (publishing INSIDE would trade the race for an HVDC102
    blocking-call-under-lock finding)."""
    bad = """
        import threading

        class Door:
            def __init__(self, kv):
                self._lock = threading.Lock()
                self._kv = kv
                self.owners = {}
                self.epoch = 0

            def start(self):
                threading.Thread(target=self._watch).start()

            def _watch(self):
                while True:
                    with self._lock:
                        self.owners = {"s0": "fe1"}
                        self.epoch += 1
                    self.publish()

            def publish(self):
                doc = {"owners": dict(self.owners),
                       "epoch": self.epoch}
                self._kv.put("frontends", doc)
    """
    findings = _lint_source(tmp_path, bad, name="publish_shape.py")
    assert _new(findings, "HVDC109"), "lockless doc build must fire"
    fixed = bad.replace(
        'doc = {"owners": dict(self.owners),\n'
        '                       "epoch": self.epoch}\n',
        'with self._lock:\n'
        '                    doc = {"owners": dict(self.owners),\n'
        '                           "epoch": self.epoch}\n',
    )
    assert fixed != bad
    findings = _lint_source(tmp_path, fixed, name="publish_shape.py")
    assert not _new(findings, "HVDC109"), \
        [f.message for f in _new(findings, "HVDC109")]


def test_race_fix_frontend_takeover_log_read_shape(tmp_path):
    """Reduced shape of the FrontDoor._takeover race: the epoch bump
    happens under the lock but the log line after the block re-reads
    the field bare — a second takeover can bump it in between, logging
    the wrong epoch.  The fix captures a local inside the block."""
    bad = """
        import threading

        class Door:
            def __init__(self):
                self._lock = threading.Lock()
                self.epoch = 0

            def start(self):
                threading.Thread(target=self._watch).start()

            def _watch(self):
                with self._lock:
                    self.epoch += 1
                print("took over at epoch", self.epoch)
    """
    findings = _lint_source(tmp_path, bad, name="takeover_shape.py")
    hits = _new(findings, "HVDC109")
    assert hits and "epoch" in hits[0].message
    fixed = bad.replace(
        "self.epoch += 1\n"
        '                print("took over at epoch", self.epoch)',
        "self.epoch += 1\n"
        "                    epoch = self.epoch\n"
        '                print("took over at epoch", epoch)',
    )
    assert fixed != bad
    findings = _lint_source(tmp_path, fixed, name="takeover_shape.py")
    assert not _new(findings, "HVDC109"), \
        [f.message for f in _new(findings, "HVDC109")]


def test_self_application_is_clean_against_baseline():
    """The shipped tree lints clean: no new findings over horovod_tpu/
    + examples/ + scripts/ once the committed baseline (reasoned false
    positives only) is applied.  This is the acceptance criterion run
    in-process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(root)
    findings = analyze_paths(cfg.paths, root=root, exclude=cfg.exclude)
    baseline = load_baseline(os.path.join(root, cfg.baseline))
    for f in findings:
        if f.status == "new" and f.key() in baseline:
            f.status = "baselined"
    new = [f for f in findings if f.status == "new"]
    assert not new, [
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in new
    ]
    # and the baseline itself carries a real reason per entry
    for entry in baseline.values():
        assert len(entry["reason"]) > 20


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


def test_inline_suppression_same_line_and_line_above(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def a(x, cond):
            if cond:
                return hvd.allreduce(x)  # hvdtpu: disable=HVD003
            return x

        def b(x, cond):
            if cond:
                # hvdtpu: disable=HVD003
                return hvd.allreduce(x)
            return x
    """)
    assert not _new(findings, "HVD003")
    assert sum(1 for f in findings if f.status == "suppressed") == 2


def test_suppression_is_per_rule(tmp_path):
    findings = _lint_source(tmp_path, """
        import horovod_tpu as hvd

        def a(x, cond):
            if cond:
                # hvdtpu: disable=HVD007
                return hvd.allreduce(x)
            return x
    """)
    assert _new(findings, "HVD003")  # wrong id: still fires


def test_suppression_inside_string_literal_ignored(tmp_path):
    findings = _lint_source(tmp_path, '''
        import horovod_tpu as hvd

        DOC = """example: # hvdtpu: disable=HVD003"""

        def a(x, cond):
            if cond:
                return hvd.allreduce(x)
            return x
    ''')
    assert _new(findings, "HVD003")


# ---------------------------------------------------------------------------
# CLI: exit codes, formats, baseline
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ,
             "PYTHONPATH": _REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
    )


@pytest.fixture(scope="module")
def cli_tmp(tmp_path_factory):
    d = tmp_path_factory.mktemp("lint_cli")
    (d / "bad.py").write_text(textwrap.dedent(FIXTURES["HVD001"][0]))
    (d / "good.py").write_text(textwrap.dedent(FIXTURES["HVD001"][1]))
    return d


@pytest.mark.serial
def test_cli_exit_codes(cli_tmp):
    r = _run_cli(["good.py"], cwd=cli_tmp)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli(["bad.py"], cwd=cli_tmp)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "HVD001" in r.stdout


@pytest.mark.serial
def test_cli_json_schema(cli_tmp):
    r = _run_cli(["bad.py", "--format", "json"], cwd=cli_tmp)
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["schema"] == "hvdtpu-lint-v1"
    assert set(doc) >= {"schema", "rules", "findings", "summary"}
    assert doc["summary"]["new"] >= 1
    f = doc["findings"][0]
    assert set(f) >= {"rule", "severity", "path", "line", "col",
                      "message", "context", "status"}
    assert doc["rules"]["HVD001"]["severity"] == "error"


@pytest.mark.serial
def test_cli_baseline_roundtrip(cli_tmp):
    # findings baselined with a reason -> exit 0; reasonless -> exit 2
    r = _run_cli(["bad.py", "--format", "json"], cwd=cli_tmp)
    doc = json.loads(r.stdout)
    entries = [
        {"rule": f["rule"], "path": f["path"], "context": f["context"],
         "reason": "test fixture: acknowledged on purpose"}
        for f in doc["findings"]
    ]
    bl = cli_tmp / "bl.json"
    bl.write_text(json.dumps(
        {"schema": BASELINE_SCHEMA, "entries": entries}
    ))
    r = _run_cli(["bad.py", "--baseline", "bl.json"], cwd=cli_tmp)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "baselined" in r.stdout
    # empty reason must be rejected (the "no unreasoned baseline" rule)
    for e in entries:
        e["reason"] = ""
    bl.write_text(json.dumps(
        {"schema": BASELINE_SCHEMA, "entries": entries}
    ))
    r = _run_cli(["bad.py", "--baseline", "bl.json"], cwd=cli_tmp)
    assert r.returncode == 2
    assert "reason" in r.stderr


@pytest.mark.serial
def test_cli_rules_filter_and_list(cli_tmp):
    r = _run_cli(["bad.py", "--rules", "HVD005"], cwd=cli_tmp)
    assert r.returncode == 0  # HVD001 finding filtered out
    r = _run_cli(["--list-rules"], cwd=cli_tmp)
    assert r.returncode == 0
    for rid in FIXTURES:
        assert rid in r.stdout
    r = _run_cli(["bad.py", "--rules", "NOPE001"], cwd=cli_tmp)
    assert r.returncode == 2


def test_baseline_loader_rejects_missing_reason(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({
        "schema": BASELINE_SCHEMA,
        "entries": [{"rule": "HVD001", "path": "x.py",
                     "context": "f"}],
    }))
    with pytest.raises(BaselineError):
        load_baseline(str(p))


def test_baseline_loader_rejects_wrong_schema(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"schema": "nope", "entries": []}))
    with pytest.raises(BaselineError):
        load_baseline(str(p))


def test_parse_error_is_a_finding(tmp_path):
    findings = _lint_source(tmp_path, "def broken(:\n")
    assert any(f.rule == "PARSE" for f in findings)


def test_pyproject_config_is_read():
    cfg = load_config(_REPO)
    assert cfg.paths == ["horovod_tpu", "examples", "scripts"]
    assert cfg.baseline == "horovod_tpu/analysis/baseline.json"


def test_config_fallback_parser(tmp_path):
    # the 3.10 path: no tomllib — the subset parser must read our block
    from horovod_tpu.analysis.config import _read_table_fallback

    p = tmp_path / "pyproject.toml"
    p.write_text(textwrap.dedent("""
        [project]
        name = "x"

        [tool.hvdtpu-lint]
        paths = ["a", "b"]  # trailing comments are legal TOML
        baseline = "bl.json"
        exclude = [
            "a/skip",  # and on list continuation lines too
        ]
    """))
    table = _read_table_fallback(str(p), "tool.hvdtpu-lint")
    assert table == {
        "paths": ["a", "b"], "baseline": "bl.json",
        "exclude": ["a/skip"],
    }


@pytest.mark.serial
def test_cli_config_error_is_exit_2(tmp_path):
    # A broken [tool.hvdtpu-lint] block must exit 2 (usage error), not
    # crash with a traceback that exits 1 and reads as "findings".
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.hvdtpu-lint]
        paths = [unquoted]
    """))
    (tmp_path / "ok.py").write_text("x = 1\n")
    r = _run_cli(["--root", str(tmp_path)], cwd=tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "config" in r.stderr.lower()


def test_suppression_scanner_survives_tokenize_divergence(tmp_path):
    # ast.parse accepts some inputs the pure-Python tokenizer rejects
    # with TokenError (e.g. an unterminated trailing line continuation);
    # parse_suppressions must degrade to "no suppressions", not raise.
    from horovod_tpu.analysis.core import parse_suppressions

    assert parse_suppressions("x = 1\\") == {}


@pytest.mark.serial
def test_cli_rules_filter_does_not_report_stale_baseline(cli_tmp):
    # A --rules run sees a rule subset; baseline entries for other
    # rules must not be reported as stale ("fixed? remove it").
    r = _run_cli(["bad.py", "--format", "json"], cwd=cli_tmp)
    doc = json.loads(r.stdout)
    entries = [
        {"rule": f["rule"], "path": f["path"], "context": f["context"],
         "reason": "test fixture: acknowledged on purpose"}
        for f in doc["findings"]
    ]
    bl = cli_tmp / "bl_rules.json"
    bl.write_text(json.dumps(
        {"schema": BASELINE_SCHEMA, "entries": entries}
    ))
    r = _run_cli(
        ["--rules", "HVD005", "--baseline", "bl_rules.json", "bad.py"],
        cwd=cli_tmp,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no longer matches" not in r.stderr


@pytest.mark.serial
def test_cli_changed_without_git_is_exit_2(tmp_path):
    (tmp_path / "x.py").write_text("x = 1\n")
    r = _run_cli(["--changed", "--root", str(tmp_path)], cwd=tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "git" in r.stderr


# ---------------------------------------------------------------------------
# per-file cache + baseline pruning + --changed robustness (ISSUE 12)
# ---------------------------------------------------------------------------


def test_cache_roundtrip_same_findings(tmp_path):
    from horovod_tpu.analysis import cache as cache_mod

    (tmp_path / "bad.py").write_text(
        textwrap.dedent(FIXTURES["HVD001"][0]))
    cp = str(tmp_path / "cache.json")
    cold = analyze_paths([str(tmp_path / "bad.py")],
                         root=str(tmp_path), cache_path=cp)
    assert os.path.isfile(cp)
    assert cache_mod.load_cache(cp)  # entries landed
    warm = analyze_paths([str(tmp_path / "bad.py")],
                         root=str(tmp_path), cache_path=cp)
    assert [(f.rule, f.path, f.line, f.message) for f in cold] == \
        [(f.rule, f.path, f.line, f.message) for f in warm]


def test_cache_hit_skips_module_rules(tmp_path, monkeypatch):
    from horovod_tpu.analysis import registry

    (tmp_path / "bad.py").write_text(
        textwrap.dedent(FIXTURES["HVD001"][0]))
    cp = str(tmp_path / "cache.json")
    analyze_paths([str(tmp_path / "bad.py")], root=str(tmp_path),
                  cache_path=cp)
    calls = []
    orig = registry.run_module_rules
    monkeypatch.setattr(
        registry, "run_module_rules",
        lambda model: calls.append(model.relpath) or orig(model))
    warm = analyze_paths([str(tmp_path / "bad.py")],
                         root=str(tmp_path), cache_path=cp)
    assert not calls, f"cache hit still ran module rules on {calls}"
    assert _new(warm, "HVD001")


def test_cache_invalidated_by_edit(tmp_path):
    p = tmp_path / "f.py"
    p.write_text(textwrap.dedent(FIXTURES["HVD001"][0]))
    cp = str(tmp_path / "cache.json")
    first = analyze_paths([str(p)], root=str(tmp_path), cache_path=cp)
    assert _new(first, "HVD001")
    p.write_text(textwrap.dedent(FIXTURES["HVD001"][1]))
    second = analyze_paths([str(p)], root=str(tmp_path), cache_path=cp)
    assert not _new(second, "HVD001")


def test_cache_subset_run_merges_instead_of_clobbering(tmp_path):
    # a --changed-style run over ONE file must not evict the other
    # files' entries from the cache
    from horovod_tpu.analysis import cache as cache_mod

    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("x = 1\n")
    b.write_text("y = 2\n")
    cp = str(tmp_path / "cache.json")
    analyze_paths([str(a), str(b)], root=str(tmp_path), cache_path=cp)
    assert set(cache_mod.load_cache(cp)) == {"a.py", "b.py"}
    a.write_text("x = 3\n")  # dirty, so the subset run rewrites
    analyze_paths([str(a)], root=str(tmp_path), cache_path=cp)
    assert set(cache_mod.load_cache(cp)) == {"a.py", "b.py"}


def test_cache_corruption_is_recomputed(tmp_path):
    p = tmp_path / "f.py"
    p.write_text(textwrap.dedent(FIXTURES["HVD001"][0]))
    cp = tmp_path / "cache.json"
    analyze_paths([str(p)], root=str(tmp_path), cache_path=str(cp))
    cp.write_text("{ not json")
    findings = analyze_paths([str(p)], root=str(tmp_path),
                             cache_path=str(cp))
    assert _new(findings, "HVD001")


def test_cache_rejected_on_rule_set_change(tmp_path):
    from horovod_tpu.analysis import cache as cache_mod

    p = tmp_path / "f.py"
    p.write_text("x = 1\n")
    cp = tmp_path / "cache.json"
    analyze_paths([str(p)], root=str(tmp_path), cache_path=str(cp))
    doc = json.loads(cp.read_text())
    doc["rules"] = "HVD999"  # a different analyzer wrote this
    cp.write_text(json.dumps(doc))
    assert cache_mod.load_cache(str(cp)) == {}


@pytest.mark.serial
def test_prune_baseline_removes_stale_entries(tmp_path):
    # a baseline with one live and one stale entry; --prune-baseline
    # must drop exactly the stale one and keep the live entry's reason.
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.hvdtpu-lint]
        paths = ["bad.py"]
        baseline = "bl.json"
    """))
    (tmp_path / "bad.py").write_text(
        textwrap.dedent(FIXTURES["HVD001"][0]))
    r = _run_cli(["--no-baseline", "--format", "json"], cwd=tmp_path)
    doc = json.loads(r.stdout)
    entries = [
        {"rule": f["rule"], "path": f["path"], "context": f["context"],
         "reason": "live entry, still fires"}
        for f in doc["findings"]
    ]
    entries.append({
        "rule": "HVD007", "path": "gone.py", "context": "nope",
        "reason": "stale: the finding this acknowledged was fixed",
    })
    (tmp_path / "bl.json").write_text(json.dumps(
        {"schema": BASELINE_SCHEMA, "entries": entries}))
    r = _run_cli(["--prune-baseline"], cwd=tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pruned stale baseline entry" in r.stderr
    kept = json.loads((tmp_path / "bl.json").read_text())["entries"]
    assert all(e["path"] != "gone.py" for e in kept)
    assert any(e["reason"] == "live entry, still fires" for e in kept)


@pytest.mark.serial
def test_strict_baseline_exits_1_on_stale(tmp_path):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.hvdtpu-lint]
        paths = ["ok.py"]
        baseline = "bl.json"
    """))
    (tmp_path / "ok.py").write_text("x = 1\n")
    (tmp_path / "bl.json").write_text(json.dumps({
        "schema": BASELINE_SCHEMA,
        "entries": [{"rule": "HVD001", "path": "gone.py",
                     "context": "f", "reason": "stale on purpose"}],
    }))
    r = _run_cli(["--strict-baseline"], cwd=tmp_path)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "stale" in r.stderr
    # without the flag the same run is exit 0 (note only)
    r = _run_cli([], cwd=tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no longer matches" in r.stderr


@pytest.mark.serial
def test_prune_and_strict_rejected_on_partial_view(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    for extra in (["--rules", "HVD001"], ["ok.py"], ["--changed"]):
        for flag in ("--prune-baseline", "--strict-baseline"):
            r = _run_cli([flag, *extra], cwd=tmp_path)
            assert r.returncode == 2, (flag, extra, r.stderr)
            assert "full-surface" in r.stderr


@pytest.mark.serial
def test_changed_survives_deleted_and_renamed_files(tmp_path):
    # a deleted tracked file and a rename must not crash --changed (the
    # old names no longer exist on disk).
    def git(*a):
        subprocess.run(
            ["git", *a], cwd=tmp_path, check=True, capture_output=True,
            env={**os.environ,
                 "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.hvdtpu-lint]
        paths = ["src"]
        baseline = ""
    """))
    src = tmp_path / "src"
    src.mkdir()
    (src / "doomed.py").write_text("x = 1\n")
    (src / "old_name.py").write_text("y = 2\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (src / "doomed.py").unlink()
    (src / "old_name.py").rename(src / "new_name.py")
    (src / "fresh.py").write_text(
        textwrap.dedent(FIXTURES["HVD001"][0]))
    r = _run_cli(["--changed"], cwd=tmp_path)
    # no traceback/exit-2 from the missing paths; the surviving files
    # are linted and the bad one still fails the run
    assert r.returncode == 1, r.stdout + r.stderr
    assert "HVD001" in r.stdout
    assert "Traceback" not in r.stderr


def test_write_baseline_preserves_curated_reasons(tmp_path):
    from horovod_tpu.analysis.baseline import (
        load_baseline, write_baseline,
    )
    from horovod_tpu.analysis.core import Finding

    f1 = Finding(rule="HVD001", severity="error", path="a.py", line=3,
                 col=0, message="m1", context="f")
    f2 = Finding(rule="HVD002", severity="warning", path="b.py", line=7,
                 col=0, message="m2", context="g")
    existing = {
        f1.key(): {"rule": "HVD001", "path": "a.py", "context": "f",
                   "reason": "curated justification, hand-written"},
    }
    out = tmp_path / "bl.json"
    write_baseline(str(out), [f1, f2], reason="", existing=existing)
    doc = json.loads(out.read_text())
    by_rule = {e["rule"]: e for e in doc["entries"]}
    # the pre-existing entry keeps its human reason...
    assert by_rule["HVD001"]["reason"] == \
        "curated justification, hand-written"
    # ...and the new entry's empty reason still fails the loader
    assert by_rule["HVD002"]["reason"] == ""
    with pytest.raises(Exception):
        load_baseline(str(out))


def test_lint_script_flag_values_not_paths():
    # "--format json" must NOT read 'json' as an explicit path (which
    # would silently disable the default --changed fast mode).
    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    try:
        import lint as lint_script
    finally:
        sys.path.pop(0)
    assert not lint_script._has_explicit_paths(["--format", "json"])
    assert not lint_script._has_explicit_paths(
        ["--rules", "HVD001", "--format=json"])
    assert not lint_script._has_explicit_paths(["--jobs", "4"])
    assert not lint_script._has_explicit_paths(["-j", "4"])
    assert lint_script._has_explicit_paths(["horovod_tpu"])
    assert lint_script._has_explicit_paths(["--format", "json", "a.py"])


# ---------------------------------------------------------------------------
# --jobs: parallel per-file analysis
# ---------------------------------------------------------------------------


def test_jobs_parallel_matches_serial(tmp_path):
    """A --jobs run must be bit-identical to a serial run: same
    findings (rule/path/line/status) over a mixed dirty tree, including
    project-scope race findings whose closure runs in-process."""
    (tmp_path / "a.py").write_text(textwrap.dedent(FIXTURES["HVD001"][0]))
    (tmp_path / "b.py").write_text(textwrap.dedent(FIXTURES["HVDC108"][0]))
    (tmp_path / "c.py").write_text(textwrap.dedent(FIXTURES["HVD002"][1]))
    (tmp_path / "d.py").write_text(textwrap.dedent(FIXTURES["HVDC109"][0]))
    key = lambda fs: [(f.rule, f.path, f.line, f.status) for f in fs]  # noqa: E731
    serial = analyze_paths([str(tmp_path)], root=str(tmp_path))
    par = analyze_paths([str(tmp_path)], root=str(tmp_path), jobs=3)
    assert key(par) == key(serial)
    assert any(f.rule == "HVDC108" for f in par)


def test_jobs_cache_written_by_workers_is_coherent(tmp_path, monkeypatch):
    """The cache a parallel run persists must satisfy a later serial
    run as a plain content-hash hit — worker results travel in cache-
    entry shape, so an incoherent merge would show up here as a module-
    rule recompute (or wrong findings)."""
    from horovod_tpu.analysis import registry

    (tmp_path / "a.py").write_text(textwrap.dedent(FIXTURES["HVD001"][0]))
    (tmp_path / "b.py").write_text(textwrap.dedent(FIXTURES["HVDC108"][0]))
    cache = tmp_path / "cache.json"
    first = analyze_paths([str(tmp_path)], root=str(tmp_path),
                          cache_path=str(cache), jobs=2)
    assert cache.is_file()

    def boom(model):
        raise AssertionError(f"module rules re-ran for {model.relpath}")

    monkeypatch.setattr(registry, "run_module_rules", boom)
    warm = analyze_paths([str(tmp_path)], root=str(tmp_path),
                         cache_path=str(cache))
    key = lambda fs: [(f.rule, f.path, f.line) for f in fs]  # noqa: E731
    assert key(warm) == key(first)


@pytest.mark.serial
def test_cli_jobs_flag(cli_tmp):
    r = _run_cli(["bad.py", "--jobs", "2", "--no-cache"], cwd=cli_tmp)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "HVD001" in r.stdout
    r = _run_cli(["bad.py", "--jobs", "-3"], cwd=cli_tmp)
    assert r.returncode == 2
    assert "--jobs" in r.stderr


# ---------------------------------------------------------------------------
# --changed hardening + wrapper-level coverage
# ---------------------------------------------------------------------------


@pytest.mark.serial
def test_changed_handles_non_ascii_paths(tmp_path):
    """Text-mode ``git diff`` C-quotes non-ASCII paths (core.quotePath
    default), which the isfile() filter then silently drops — the file
    escapes the lint. ``-z`` keeps the bytes verbatim."""
    def git(*a):
        subprocess.run(
            ["git", *a], cwd=tmp_path, check=True, capture_output=True,
            env={**os.environ,
                 "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    from horovod_tpu.analysis.cli import _changed_files

    git("init", "-q")
    (tmp_path / "sürface.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (tmp_path / "sürface.py").write_text("x = 2\n")
    assert _changed_files(str(tmp_path)) == ["sürface.py"]


@pytest.mark.serial
def test_lint_script_survives_deleted_and_renamed_files(tmp_path):
    """Wrapper-level regression for the reported dev-loop crash: the
    `python scripts/lint.py` entry (which defaults to --changed) must
    ride out a working tree with deletions and renames."""
    def git(*a):
        subprocess.run(
            ["git", *a], cwd=tmp_path, check=True, capture_output=True,
            env={**os.environ,
                 "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.hvdtpu-lint]
        paths = ["src"]
        baseline = ""
    """))
    src = tmp_path / "src"
    src.mkdir()
    (src / "doomed.py").write_text("x = 1\n")
    (src / "old_name.py").write_text("y = 2\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    (src / "doomed.py").unlink()
    (src / "old_name.py").rename(src / "new_name.py")
    (src / "fresh.py").write_text(
        textwrap.dedent(FIXTURES["HVD001"][0]))
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "lint.py"),
         "--root", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ,
             "PYTHONPATH": _REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "HVD001" in r.stdout
    assert "Traceback" not in r.stderr


# ---------------------------------------------------------------------------
# configured surface audit
# ---------------------------------------------------------------------------


def test_configured_surface_covers_package():
    """[tool.hvdtpu-lint] paths must cover EVERY python file under
    horovod_tpu/ except explicit excludes: a subpackage added without
    updating the config would otherwise silently escape the CI gate."""
    from horovod_tpu.analysis.cli import _iter_py_files

    cfg = load_config(_REPO)
    surface = set(_iter_py_files(cfg.paths, cfg.exclude, _REPO))
    excl = [os.path.normpath(os.path.join(_REPO, e))
            for e in cfg.exclude]

    def excluded(p):
        np_ = os.path.normpath(p)
        return any(np_ == e or np_.startswith(e + os.sep) for e in excl)

    missing = []
    pkg = os.path.join(_REPO, "horovod_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            fp = os.path.join(dirpath, fn)
            if fn.endswith(".py") and not excluded(fp) \
                    and fp not in surface:
                missing.append(os.path.relpath(fp, _REPO))
    assert not missing, \
        f"python files outside the configured lint surface: {missing}"
