"""Training-health plane (obs/health.py + obs/divergence.py): digest
algebra (digest equality ⟺ bitwise equality on adversarial float pairs
— ±0.0, NaN payloads, denormals — and host/in-graph parity), the
anomaly judge as a pure decision table (spike/ramp/plateau/nonfinite,
rising-edge counting, min-sample guard), the divergence sentinel's
localization with an injected exchange, the HLO-unchanged-when-off
artifact check on ``OverlapPlan.local_step``, the ``grad_ready`` fault
actions, and the postmortem folding of health events."""

from __future__ import annotations

import json
import re
import time

import numpy as np
import pytest

import horovod_tpu.obs as obs
from horovod_tpu.obs import divergence, flightrec, health, postmortem
from horovod_tpu.testing import faults


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("HVDTPU_FAULT_SPEC", raising=False)
    faults.reset()
    obs.reset_registry()
    flightrec.reset_recorder()
    yield
    faults.reset()
    obs.reset_registry()
    flightrec.reset_recorder()


# ---------------------------------------------------------------------------
# digest algebra
# ---------------------------------------------------------------------------


def test_digest_bitwise_equality_on_adversarial_float_pairs():
    """Value-equal but bit-different pairs MUST digest differently;
    bit-identical arrays MUST digest identically."""
    pos_zero = np.array([0.0], np.float32)
    neg_zero = np.array([-0.0], np.float32)
    assert pos_zero[0] == neg_zero[0]  # value comparison waves it through
    assert not np.array_equal(divergence.digest_array(pos_zero),
                              divergence.digest_array(neg_zero))

    nan_a = np.uint32(0x7FC00000).reshape(1).view(np.float32)
    nan_b = np.uint32(0x7FC00001).reshape(1).view(np.float32)
    assert not np.array_equal(divergence.digest_array(nan_a),
                              divergence.digest_array(nan_b))

    denorm = np.array([1e-42], np.float32)
    zero = np.array([0.0], np.float32)
    assert not np.array_equal(divergence.digest_array(denorm),
                              divergence.digest_array(zero))

    x = np.linspace(-3, 3, 97).astype(np.float32)
    assert np.array_equal(divergence.digest_array(x),
                          divergence.digest_array(x.copy()))


def test_digest_single_bit_flip_always_detected():
    """M odd ⟹ the per-word mix is bijective: any single-element bit
    flip, at any position, changes the digest."""
    base = np.arange(64, dtype=np.float32)
    ref = divergence.digest_array(base)
    for pos in (0, 1, 31, 63):
        for bit in (0, 7, 22, 31):
            mutated = base.copy()
            raw = mutated.view(np.uint32)
            raw[pos] ^= np.uint32(1) << np.uint32(bit)
            assert not np.array_equal(divergence.digest_array(mutated),
                                      ref), (pos, bit)


def test_digest_dtype_coverage_and_length_mixing():
    for dt in (np.float16, np.float32, np.float64, np.int8, np.uint8,
               np.int32, np.int64):
        arr = np.arange(7).astype(dt)
        d = divergence.digest_array(arr)
        assert d.shape == (divergence.DIGEST_WIDTH,)
        assert d.dtype == np.uint32
    # zero padding is not invisible: [x] vs [x, 0] differ
    a = np.array([1.5], np.float32)
    b = np.array([1.5, 0.0], np.float32)
    assert not np.array_equal(divergence.digest_array(a),
                              divergence.digest_array(b))
    # empty arrays digest deterministically
    assert np.array_equal(
        divergence.digest_array(np.empty(0, np.float32)),
        divergence.digest_array(np.empty(0, np.float32)))


def test_digest_concat_order_sensitivity():
    a = np.array([1.0, 2.0], np.float32)
    b = np.array([3.0], np.float32)
    assert not np.array_equal(divergence.digest_leaves([a, b]),
                              divergence.digest_leaves([b, a]))


def test_jit_digest_matches_host_digest():
    """The in-graph digest is byte-for-byte the host digest — the
    device and host halves of the sentinel can be mixed freely."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.optim.overlap import build_layout

    params = {"w1": np.linspace(-2, 2, 32).astype(np.float32)
              .reshape(8, 4),
              "b": np.array([0.0, -0.0, 1e-42, np.inf], np.float32)}
    leaves, _ = jax.tree_util.tree_flatten(params)
    layout = build_layout(params, 64)
    vec, names = divergence.tree_digest_vector(leaves, layout)
    host = vec.reshape(len(layout.buckets), divergence.DIGEST_WIDTH)
    dev = np.asarray(
        divergence.jit_digest(layout)(*[jnp.asarray(l) for l in leaves])
    )
    assert np.array_equal(dev, host)


def test_blob_and_page_state_digest():
    assert np.array_equal(divergence.blob_digest(b"abc"),
                          divergence.blob_digest(b"abc"))
    assert not np.array_equal(divergence.blob_digest(b"abc"),
                              divergence.blob_digest(b"abd"))
    assert divergence.page_state_digest(None).shape == (
        divergence.DIGEST_WIDTH,)


# ---------------------------------------------------------------------------
# anomaly judge: pure decision table
# ---------------------------------------------------------------------------


def _warm(judge, n=10, loss=1.0, grad=1.0):
    for _ in range(n):
        assert judge.observe(loss=loss, grad_norm=grad) == []


def test_judge_loss_spike_fires_and_is_rising_edge():
    j = health.AnomalyJudge(min_samples=4)
    _warm(j)
    alerts = j.observe(loss=500.0, grad_norm=1.0)
    assert [a.cls for a in alerts] == ["loss-spike"]
    assert alerts[0].rising
    # persists: still firing, but NOT another rising edge
    alerts = j.observe(loss=500.0, grad_norm=1.0)
    assert alerts and not alerts[0].rising
    assert j.alerts_total["loss-spike"] == 1
    # recovers, then spikes again: a second episode counts again
    for _ in range(12):
        j.observe(loss=1.0, grad_norm=1.0)
    assert j.observe(loss=500.0, grad_norm=1.0)[0].rising
    assert j.alerts_total["loss-spike"] == 2


def test_judge_downward_loss_move_is_not_a_spike():
    j = health.AnomalyJudge(min_samples=4)
    _warm(j, loss=100.0)
    assert j.observe(loss=0.01, grad_norm=1.0) == []


def test_judge_gradual_ramp_does_not_fire():
    """The EWMA tracks a steady ramp; only a step change is a spike."""
    j = health.AnomalyJudge(min_samples=4)
    loss = 1.0
    for _ in range(200):
        loss *= 1.01
        assert j.observe(loss=loss, grad_norm=1.0) == []


def test_judge_plateau_stays_silent():
    j = health.AnomalyJudge(min_samples=4)
    for _ in range(100):
        assert j.observe(loss=3.14, grad_norm=0.5) == []


def test_judge_grad_explode_and_vanish():
    j = health.AnomalyJudge(min_samples=4)
    _warm(j)
    assert [a.cls for a in j.observe(loss=1.0, grad_norm=1e6)] == \
        ["grad-explode"]
    j2 = health.AnomalyJudge(min_samples=4, vanish_frac=1e-3)
    _warm(j2)
    assert [a.cls for a in j2.observe(loss=1.0, grad_norm=1e-7)] == \
        ["grad-vanish"]


def test_judge_min_sample_guard_blocks_cold_relative_rules():
    """A spike on observation 2 is warmup noise, not an anomaly."""
    j = health.AnomalyJudge(min_samples=8)
    j.observe(loss=1.0, grad_norm=1.0)
    assert j.observe(loss=1e9, grad_norm=1e9) == []


def test_judge_nonfinite_is_absolute_and_skips_baseline():
    """Nonfinite fires even before min_samples, and a NaN loss must
    not poison the EWMA baseline."""
    j = health.AnomalyJudge(min_samples=8)
    alerts = j.observe(loss=float("nan"), grad_norm=1.0)
    assert [a.cls for a in alerts] == ["nonfinite"]
    assert alerts[0].rising
    assert j.loss.n == 0  # baseline untouched
    _warm(j)
    assert [a.cls for a in j.observe(loss=1.0, grad_norm=1.0,
                                     nonfinite=3)] == ["nonfinite"]


def test_judge_dead_gradient_needs_a_streak():
    j = health.AnomalyJudge(min_samples=4, dead_steps=5)
    _warm(j)
    for i in range(4):
        assert j.observe(loss=1.0, grad_norm=1.0,
                         bucket_norms=[1.0, 0.0]) == [], i
    alerts = j.observe(loss=1.0, grad_norm=1.0, bucket_norms=[1.0, 0.0])
    assert [a.cls for a in alerts] == ["dead-gradient"]
    assert "bucket=1" in alerts[0].detail
    # one live step resets the streak
    j.observe(loss=1.0, grad_norm=1.0, bucket_norms=[1.0, 0.5])
    assert j.observe(loss=1.0, grad_norm=1.0,
                     bucket_norms=[1.0, 0.0]) == []


# ---------------------------------------------------------------------------
# monitor publishing
# ---------------------------------------------------------------------------


def _metric(name, **tags):
    for m in obs.get_registry().snapshot():
        if m["name"] == name and (not tags or m.get("tags") == tags):
            return m
    return None


def test_monitor_publishes_bundle_and_rising_edges():
    mon = health.HealthMonitor(n_buckets=2)
    bundle = np.array([2.5, 3.0, 0.01, 0.0, 1.0, 2.0])
    for step in range(10):
        mon.observe_bundle(step, bundle)
    assert _metric("health.loss")["value"] == 2.5
    assert _metric("health.grad_norm")["value"] == 3.0
    assert _metric("health.bucket_grad_norm", bucket="1")["value"] == 2.0
    spike = bundle.copy()
    spike[0] = 900.0
    mon.observe_bundle(10, spike)
    mon.observe_bundle(11, spike)
    assert _metric("health.alert", **{"class": "loss-spike"})["value"] \
        == 1
    assert _metric("health.alerts", **{"class": "loss-spike"})["value"] \
        == 1  # rising edge counted once
    kinds = [(e["kind"], e["name"]) for e in
             flightrec.get_recorder().snapshot()]
    assert ("health.alert", "loss-spike") in kinds


def test_monitor_first_nonfinite_provenance_names_the_leaf():
    import jax

    from horovod_tpu.optim.overlap import build_layout

    params = {"a": np.ones(4, np.float32), "b": np.ones(4, np.float32)}
    layout = build_layout(params, 8)  # one bucket per leaf
    leaves, _ = jax.tree_util.tree_flatten(params)
    grads = [l.copy() for l in leaves]
    grads[1][2] = np.nan
    names = [f"leaf{i}" for i in range(len(leaves))]
    mon = health.HealthMonitor(n_buckets=len(layout.buckets), rank=3,
                               leaf_names=names)
    mon.observe(7, loss=1.0, grad_norm=1.0, nonfinite=1,
                grads_flat=grads, layout=layout)
    assert mon.first_nonfinite["step"] == 7
    assert mon.first_nonfinite["rank"] == 3
    assert mon.first_nonfinite["leaf"] == "leaf1"
    # second nonfinite does not overwrite the FIRST story
    mon.observe(9, loss=1.0, grad_norm=1.0, nonfinite=5,
                grads_flat=grads, layout=layout)
    assert mon.first_nonfinite["step"] == 7
    evs = [e for e in flightrec.get_recorder().snapshot()
           if e["kind"] == "health.nonfinite"]
    assert len(evs) == 1 and "leaf=leaf1" in evs[0]["detail"]


# ---------------------------------------------------------------------------
# divergence sentinel with an injected exchange
# ---------------------------------------------------------------------------


class _FakeExchange:
    """World-of-N allgather: rank r's vector is ``mutate(r, vec)``."""

    def __init__(self, world, mutate):
        self.world = world
        self.mutate = mutate
        self.calls = []

    def __call__(self, vec, name):
        self.calls.append(name)
        rows = [np.asarray(self.mutate(r, vec.copy()), dtype=np.uint32)
                for r in range(self.world)]
        return np.concatenate(rows)


def _layout_and_leaves():
    import jax

    from horovod_tpu.optim.overlap import build_layout

    params = {"w1": np.ones((4, 4), np.float32),
              "w2": np.full((4, 4), 2.0, np.float32),
              "w3": np.full((4, 4), 3.0, np.float32)}
    layout = build_layout(params, 64)  # 64B buckets: one leaf each
    leaves, _ = jax.tree_util.tree_flatten(params)
    return layout, [np.asarray(l) for l in leaves]


def test_sentinel_clean_run_alerts_nothing():
    layout, leaves = _layout_and_leaves()
    ex = _FakeExchange(4, lambda r, v: v)
    s = divergence.DivergenceSentinel(layout, rank=0, check_steps=10,
                                      exchange=ex)
    assert s.maybe_check(5, leaves) is None   # off-cadence: no exchange
    assert ex.calls == []
    assert s.maybe_check(10, leaves) is None  # on-cadence: clean
    assert ex.calls and s.checks == 1 and s.detections == 0
    assert _metric("health.divergence.checks")["value"] == 1
    assert _metric("health.divergence.alert")["value"] == 0


def test_sentinel_localizes_minority_rank_bucket_and_leaf():
    layout, leaves = _layout_and_leaves()
    names = ["w1", "w2", "w3"]
    # rank 1's copy of bucket 2's leaf took a bit flip
    bad_leaf = layout.buckets[2].leaf_indices[0]

    def mutate(r, vec):
        if r != 1:
            return vec
        mutated = [l.copy() for l in leaves]
        raw = mutated[bad_leaf].view(np.uint32)
        raw.reshape(-1)[5] ^= np.uint32(1) << np.uint32(30)
        if vec.size == len(layout.buckets) * divergence.DIGEST_WIDTH:
            # phase 1: full per-bucket vector
            v, _ = divergence.tree_digest_vector(mutated, layout)
        else:
            # phase 2: per-leaf descent inside the named bucket
            v = divergence.leaf_digest_matrix(
                mutated, layout.buckets[2]).ravel()
        return v

    ex = _FakeExchange(4, mutate)
    s = divergence.DivergenceSentinel(layout, rank=0, check_steps=10,
                                      exchange=ex, leaf_names=names,
                                      action="warn")
    report = s.maybe_check(20, leaves)
    assert report is not None
    assert report.minority_ranks == (1,)
    assert report.bucket == 2
    assert report.leaf_name == names[bad_leaf]
    assert len(ex.calls) == 2  # bucket phase + leaf descent
    assert "minority=1" in report.detail and "bucket=2" in report.detail
    ev = [e for e in flightrec.get_recorder().snapshot()
          if e["kind"] == "health.divergence"]
    assert len(ev) == 1 and ev[0]["cycle"] == 20
    det = _metric("health.divergence.detected",
                  component="bucket2", leaf=names[bad_leaf])
    assert det is not None and det["value"] == 1


def test_sentinel_extras_localize_opt_state_and_prng():
    layout, leaves = _layout_and_leaves()
    opt = [np.zeros(4, np.float32)]
    key = np.array([7, 9], np.uint32)

    def mutate(r, vec):
        if r != 2:
            return vec
        v, _ = divergence.tree_digest_vector(
            leaves, layout,
            extras=[("opt_state", opt),
                    ("prng", [np.array([7, 10], np.uint32)])])
        return v

    s = divergence.DivergenceSentinel(layout, rank=0, check_steps=1,
                                      exchange=_FakeExchange(3, mutate))
    report = s.check(1, leaves, opt_leaves=opt, prng_key=key)
    assert report.component == "prng"
    assert report.minority_ranks == (2,)
    assert report.bucket is None


def test_sentinel_halt_raises_on_every_rank():
    layout, leaves = _layout_and_leaves()

    def mutate(r, vec):
        if r == 1:
            v = vec.copy()
            v[0] ^= np.uint32(1)
            return v
        return vec

    for rank in (0, 1):  # culprit and bystander reach the same verdict
        obs.reset_registry()
        s = divergence.DivergenceSentinel(
            layout, rank=rank, check_steps=1, action="halt",
            exchange=_FakeExchange(2, mutate))
        with pytest.raises(divergence.DivergenceHalt, match="halt"):
            s.check(1, leaves)


def test_sentinel_rejects_bad_knobs():
    layout, _ = _layout_and_leaves()
    with pytest.raises(ValueError, match="action"):
        divergence.DivergenceSentinel(layout, rank=0, action="explode")
    with pytest.raises(ValueError, match="check_steps"):
        divergence.DivergenceSentinel(layout, rank=0, check_steps=0)


def test_partition_majority_tie_breaks_deterministically():
    # 2-rank tie: lowest rank's pattern is the "majority" everywhere
    mat = np.array([[1, 2], [3, 4]], dtype=np.uint32)
    minority, majority = divergence._partition(mat)
    assert majority == [0] and minority == [1]


# ---------------------------------------------------------------------------
# HLO-unchanged-when-off (the artifact check CI re-runs)
# ---------------------------------------------------------------------------


def _compiled_text(step):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    params = {"w": jnp.ones((4, 4), jnp.float32)}
    import optax

    tx = optax.sgd(0.1)
    state = (params, tx.init(params))
    x = jnp.ones((2, 4))
    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P()),
                           out_specs=P(), check_vma=False))
    text = fn.lower(state, x).compile().as_text()
    # The module name and the caller's source position (the two calls
    # compared below sit on different lines) are not the program.
    text = re.sub(r"line=\d+ end_line=\d+ column=\d+ end_column=\d+",
                  "", text)
    return re.sub(r"HloModule [^,]*", "HloModule M", text)


def test_health_off_leaves_compiled_hlo_byte_identical():
    import jax.numpy as jnp
    import optax

    from horovod_tpu.optim.overlap import OverlapPlan

    params = {"w": jnp.ones((4, 4), jnp.float32)}
    plan = OverlapPlan(params, optax.sgd(0.1), mode="off")

    def loss_fn(p, x):
        return jnp.mean((x @ p["w"]) ** 2)

    baseline = _compiled_text(plan.local_step(loss_fn))
    off = _compiled_text(plan.local_step(loss_fn, health=False))
    on = _compiled_text(plan.local_step(loss_fn, health=True))
    assert off == baseline          # --health off: byte-identical
    assert on != baseline           # and the flag is not a no-op


def test_health_bundle_values_in_graph():
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.optim.overlap import OverlapPlan
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    params = {"w": jnp.ones((4, 4), jnp.float32)}
    plan = OverlapPlan(params, optax.sgd(0.1), mode="off")

    def loss_fn(p, x):
        return jnp.sum(x @ p["w"])

    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    tx_state = plan.tx.init(params)
    step = jax.jit(shard_map(plan.local_step(loss_fn, health=True),
                             mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False))
    x = jnp.ones((2, 4))
    (_, loss, bundle) = step((params, tx_state), x)
    bundle = np.asarray(bundle)
    assert bundle[0] == float(loss)
    grads = jax.grad(loss_fn)(params, x)
    expect = float(np.sqrt(np.sum(np.asarray(grads["w"]) ** 2)))
    assert abs(bundle[1] - expect) < 1e-4
    assert bundle[3] == 0.0  # no nonfinites
    assert len(bundle) == 4 + len(plan.layout.buckets)


def test_zero1_bundle_matches_replicated_bundle():
    """The ZeRO-1 path computes the bundle from gradient shards +
    psum; loss, global grad norm, and nonfinite count must agree with
    the replicated path on the same batch."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.optim.overlap import OverlapPlan
    from horovod_tpu.ops.collectives import shard_map_compat
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:8], dtype=object).reshape(8),
                (hvd.DP_AXIS,))
    params = {"w": jnp.ones((8, 8), jnp.float32) * 0.1,
              "b": jnp.zeros(8, jnp.float32)}

    def loss_fn(p, x):
        return jnp.mean((x @ p["w"] + p["b"]) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    bundles = {}
    for mode in ("off", "bucket+zero1"):
        plan = OverlapPlan(params, optax.sgd(0.1), mode=mode, mesh=mesh,
                           bucket_mb=1e-4)
        spec = plan.state_spec()
        step = jax.jit(shard_map_compat(
            plan.local_step(loss_fn, health=True), mesh=mesh,
            in_specs=(spec, P(hvd.DP_AXIS)),
            out_specs=(spec, P(), P())))
        _, _, bundle = step(plan.init(params), x)
        bundles[mode] = np.asarray(bundle)
    off, z1 = bundles["off"], bundles["bucket+zero1"]
    assert abs(off[0] - z1[0]) < 1e-6       # loss
    assert abs(off[1] - z1[1]) < 1e-4       # global grad norm
    assert off[3] == z1[3] == 0.0           # nonfinite count


# ---------------------------------------------------------------------------
# grad_ready fault actions
# ---------------------------------------------------------------------------


def test_fault_spec_flip_bits_only_valid_at_grad_ready():
    specs = faults.parse_spec("grad_ready:rank=1:step=6:action=flip_bits")
    assert specs[0].action == "flip_bits"
    with pytest.raises(ValueError, match="grad_ready"):
        faults.parse_spec("ckpt_write:action=flip_bits")
    with pytest.raises(ValueError, match="grad_ready"):
        faults.parse_spec("enqueue:action=nan_inject")


def test_corrupt_grad_flip_bits_is_deterministic_single_element():
    a = np.linspace(0.1, 1.0, 16).astype(np.float32)
    out1 = faults.corrupt_grad(a, "flip_bits", rank=1, step=6, name="g")
    out2 = faults.corrupt_grad(a, "flip_bits", rank=1, step=6, name="g")
    assert np.array_equal(out1, out2)                    # deterministic
    assert not np.array_equal(out1, a)
    assert int((out1 != a).sum()) == 1                   # one element
    assert np.isfinite(out1).all()                       # finite SDC
    assert out1.dtype == a.dtype
    assert np.array_equal(a, np.linspace(0.1, 1.0, 16)
                          .astype(np.float32))           # input intact
    # the hit position is keyed by (rank, step, name): across a handful
    # of ranks at least one must land elsewhere (mod-16 collisions are
    # fine for any single pair)
    others = [faults.corrupt_grad(a, "flip_bits", rank=r, step=6, name="g")
              for r in range(8)]
    assert any(not np.array_equal(out1, o) for o in others)


def test_corrupt_grad_nan_inject():
    a = np.ones(8, np.float32)
    out = faults.corrupt_grad(a, "nan_inject", rank=0, step=3, name="x")
    assert int(np.isnan(out).sum()) == 1
    # integer arrays fall back to the bit flip (NaN has no int encoding)
    ints = np.arange(8, dtype=np.int32)
    iout = faults.corrupt_grad(ints, "nan_inject", rank=0, step=3,
                               name="x")
    assert int((iout != ints).sum()) == 1


def test_maybe_fail_grad_ready_returns_advisory_action(monkeypatch):
    monkeypatch.setenv("HVDTPU_FAULT_SPEC",
                       "grad_ready:rank=1:step=2:action=flip_bits")
    faults.reset()
    assert faults.maybe_fail("grad_ready", step=1, rank=1) is None
    assert faults.maybe_fail("grad_ready", step=2, rank=0) is None
    assert faults.maybe_fail("grad_ready", step=2, rank=1) == "flip_bits"
    # count=1 default: fires once
    assert faults.maybe_fail("grad_ready", step=2, rank=1) is None


# ---------------------------------------------------------------------------
# postmortem folding
# ---------------------------------------------------------------------------


def _flightrec_dump(tmp_path, rank, events, trigger="atexit",
                    last_exception=None):
    doc = {
        "schema": flightrec.SCHEMA, "rank": rank, "pid": 1000 + rank,
        "wall_time": time.time() + rank, "trigger": trigger, "epoch": 0,
        "capacity": 64, "recorded": len(events), "overwritten": 0,
        "last_exception": last_exception,
        "events": [
            {"seq": i, "t": time.time(), "kind": k, "name": n,
             "cycle": c, "detail": d}
            for i, (k, n, c, d) in enumerate(events)
        ],
    }
    path = tmp_path / f"flightrec.rank{rank}.json"
    path.write_text(json.dumps(doc))
    return doc


def test_postmortem_carries_divergence_and_nonfinite(tmp_path):
    _flightrec_dump(
        tmp_path, 0,
        [("complete", "g0", 1, ""),
         ("health.divergence", "bucket2", 8,
          "step=8 minority=1 component=bucket2 bucket=2 leaf=w1")],
        trigger="exception",
        last_exception={"type": "DivergenceHalt", "message": "", "where": "",
                        "traceback": ""},
    )
    _flightrec_dump(
        tmp_path, 1,
        [("complete", "g0", 1, ""),
         ("health.nonfinite", "first", 6,
          "step=6 rank=1 count=2 bucket=2 leaf_index=1 leaf=w1"),
         ("health.alert", "nonfinite", 6, "step=6 count=2"),
         ("health.divergence", "bucket2", 8,
          "step=8 minority=1 component=bucket2 bucket=2 leaf=w1")],
        trigger="exception",
        last_exception={"type": "DivergenceHalt", "message": "", "where": "",
                        "traceback": ""},
    )
    report = postmortem.analyze(postmortem.load_dumps(str(tmp_path)),
                                expected_ranks=2)
    h = report["health"]
    assert h["0"]["divergence"]["leaf"] == "w1"
    assert h["0"]["divergence"]["minority"] == "1"
    assert h["1"]["first_nonfinite"]["step"] == 6
    assert "nonfinite" in h["1"]["alerts"]
    v = postmortem.verdict(report)
    assert "TRAINING-STATE DIVERGENCE" in v
    assert "bucket2 (leaf w1)" in v
    assert "step 8" in v
    assert "NONFINITE GRADIENTS" in v
    assert "step 6" in v and "'w1'" in v


def test_postmortem_clean_run_has_no_health_section(tmp_path):
    _flightrec_dump(tmp_path, 0, [("complete", "g0", 1, "")])
    report = postmortem.analyze(postmortem.load_dumps(str(tmp_path)))
    assert report["health"] == {}
    assert "DIVERGENCE" not in postmortem.verdict(report)


# ---------------------------------------------------------------------------
# summary + live surfaces
# ---------------------------------------------------------------------------


def test_health_section_aggregates_dumps():
    from horovod_tpu.obs import summary

    dumps = {
        "0": {"metrics": [
            {"name": "health.alerts", "tags": {"class": "loss-spike"},
             "value": 2},
            # histograms have quantiles, not "value" — must be skipped
            {"name": "health.grad_norm_hist", "tags": {},
             "count": 12, "p50": 1.0, "p99": 1.0},
            {"name": "health.grad_norm_z", "tags": {}, "value": 1.5},
            {"name": "health.divergence.checks", "tags": {}, "value": 4},
            {"name": "health.divergence.last_check_step", "tags": {},
             "value": 400},
        ]},
        "1": {"metrics": [
            {"name": "health.grad_norm_z", "tags": {}, "value": 7.2},
            {"name": "health.divergence.detected",
             "tags": {"component": "bucket2", "leaf": "w1"}, "value": 1},
        ]},
    }
    text = summary.health_section(dumps)
    assert "loss-spike x2" in text
    assert "worst grad-norm z-score: 7.20" in text
    assert "divergence checks: 4 (last at step 400)" in text
    assert "DIVERGENCE DETECTED x1 in bucket2/w1" in text
    assert summary.health_section({"0": {"metrics": []}}) is None


def test_live_digest_health_token():
    from horovod_tpu.obs.live import LiveAggregator

    class _View:
        def __init__(self, metrics):
            self.metrics = {i: m for i, m in enumerate(metrics)}

    ok = {0: _View([{"name": "health.alert",
                     "tags": {"class": "loss-spike"}, "value": 0}])}
    firing = {0: _View([
        {"name": "health.alert", "tags": {"class": "loss-spike"},
         "value": 1},
        {"name": "health.divergence.alert", "tags": {}, "value": 1},
    ])}
    assert LiveAggregator._health_part(ok) == "health OK"
    assert LiveAggregator._health_part(firing) == \
        "health ALERT(divergence, loss-spike)"
    assert LiveAggregator._health_part({}) is None


def test_health_config_from_env(monkeypatch):
    monkeypatch.delenv("HVDTPU_HEALTH", raising=False)
    assert not health.HealthConfig.from_env().enabled
    monkeypatch.setenv("HVDTPU_HEALTH", "on")
    monkeypatch.setenv("HVDTPU_HEALTH_CHECK_STEPS", "25")
    monkeypatch.setenv("HVDTPU_DIVERGENCE_ACTION", "halt")
    cfg = health.HealthConfig.from_env()
    assert cfg.enabled and cfg.check_steps == 25
    assert cfg.divergence_action == "halt"
