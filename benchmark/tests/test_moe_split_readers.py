"""The ten readers that split the expert layer's three scopes, and their
entries in ``BENCHMARK.json``, pinned by name: eight scope readers
(``moe_logits_ms`` ... ``moe_gate_ms``) on a hand-made traced run, and
the two that read the program's gauges in this process
(``moe_live_row_share``, ``moe_gmm_tile_fill``).  A program without the
scope, the gauges or the counters (the parent of the PR that added them)
reads None, so that its line leaves the metric out."""

import os

import pytest

from helpers import ROOT

CELLS = ["glm47f_train_s8192", "trinitym_train_s8192",
         "smallthinker_train_s16384", "lfm2_train_s32768",
         "kimilin_train_s16384"]
# reader: (scope, the scope it lies in, layer in BENCHMARK.json)
SCOPES = {
    "moe_logits_ms": ("moe_logits", "moe_route", "Models"),
    "moe_topk_ms": ("moe_topk", "moe_route", "Models"),
    "moe_sort_ms": ("moe_sort", "moe_route", "Models"),
    "moe_unsort_ms": ("moe_unsort", "moe_route", "Models"),
    "moe_rows_in_ms": ("moe_rows_in", "moe_dispatch", "Models"),
    "moe_rows_out_ms": ("moe_rows_out", "moe_dispatch", "Models"),
    "moe_cast_ms": ("moe_cast", "moe_experts", "Kernels"),
    "moe_gate_ms": ("moe_gate", "moe_experts", "Kernels"),
}
COUNTERS = {"moe_live_row_share": "Models", "moe_gmm_tile_fill": "Kernels"}


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def _traced(parent, scope=None, steps=2):
    """Two steps of one block on one device: 3 ms forward and 5 ms
    backward under ``parent`` itself and, where asked, 4 and 8 ms under
    ``scope`` inside it."""
    stack = f"block1/mlp/{parent}/"
    ops = [["fusion.1", 0, 3e6, f"jit(step)/jvp(GPT)/{stack}mul:"],
           ["fusion.2", 4e6, 5e6,
            f"jit(step)/transpose(jvp(GPT))/{stack}mul:"]]
    if scope:
        ops += [["fusion.3", 10e6, 4e6,
                 f"jit(step)/jvp(GPT)/{stack}{scope}/mul:"],
                ["fusion.4", 15e6, 8e6,
                 f"jit(step)/transpose(jvp(GPT))/block1/mlp/"
                 f"jit(_backward)/{parent}/{scope}/mul:"]]
    return {"trace": {"ops": {0: ops}, "steps": steps}}


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_a_scope_reader_sums_its_scope_forward_and_backward(name):
    scope, parent, _ = SCOPES[name]
    reader = _reader(name)
    assert reader.SCOPE == scope
    run = _traced(parent, scope)
    assert reader.read(run) == pytest.approx((4 + 8) / 2)
    # the part lies inside its parent: the older reader reads as before
    assert _reader(parent + "_ms").read(run) == pytest.approx(
        (3 + 5 + 4 + 8) / 2)
    # another part of the same parent is not this one's
    other = next(s for s, p, _ in SCOPES.values()
                 if p == parent and s != scope)
    assert reader.read(_traced(parent, other)) is None


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_a_scope_reader_finds_nothing_without_its_scope(name):
    parent = SCOPES[name][1]
    assert _reader(name).read(_traced(parent)) is None    # the parent's tree
    assert _reader(name).read({"trace": None}) is None    # an untraced run


def test_the_breakdown_files_the_parts_under_the_outermost_known_scope():
    """Every reader's ``SCOPE`` joins ``device_scopes``' known names; an
    event goes under the OUTERMOST of them, so the new names take
    nothing out of ``mlp``, nor out of ``moe_route`` at a block's top."""
    from benchmark.harness import registry
    from benchmark.harness import trace as tr

    known = registry.reader_scopes(ROOT)
    assert {scope for scope, _, _ in SCOPES.values()} <= set(known)
    inside = _traced("moe_dispatch", "moe_rows_out")["trace"]["ops"][0]
    assert tr.time_by_scope(inside, known) == {"mlp": 20e6}
    on_top = [["fusion.5", 0, 7e6,
               "jit(step)/jvp(GPT)/block1/moe_route/moe_sort/sort:"]]
    assert tr.time_by_scope(on_top, known) == {"moe_route": 7e6}


@pytest.fixture
def gauges():
    """Set gauges in a registry of the test's own, as
    ``models/transformer.py:routed`` sets them while a step is traced."""
    from horovod_tpu.obs.registry import get_registry, reset_registry

    reset_registry()

    def set_gauges(name, by_layer):
        for layer, value in by_layer.items():
            get_registry().gauge(name, layer=layer).set(value)

    yield set_gauges
    reset_registry()


def _counted(rows_by_layer):
    return {"ran": {"moe_counters": {
        layer: {"rows_held": rows, "overflow_steps": 0}
        for layer, rows in rows_by_layer.items()}}}


def test_live_row_share_counts_the_rows_the_buffers_carried(gauges):
    reader = _reader("moe_live_row_share")
    gauges("moe.row_bound", {"block1": 8192, "block2": 8192})
    gauges("moe.slots", {"block1": 32768, "block2": 32768})
    # both layers inside the bound: two even shares, half of them live
    assert reader.read(_counted({"block1": 4096, "block2": 4000})) == \
        pytest.approx((4096 + 4000) / (2 * 8192))
    assert reader.read(_counted({"block1": 8192, "block2": 1})) == \
        pytest.approx(8193 / (2 * 8192))       # at the bound is inside it
    # a layer over its bound ran on every slot: the program's own rule
    assert reader.read(_counted({"block1": 4096, "block2": 8193})) == \
        pytest.approx((4096 + 8193) / (8192 + 32768))
    assert 0 < reader.read(_counted({"block1": 32768, "block2": 32768})) <= 1


def test_live_row_share_finds_nothing_without_counters_or_gauges(gauges):
    reader = _reader("moe_live_row_share")
    run = _counted({"block1": 4096})
    assert reader.read(run) is None                        # no gauge at all
    gauges("moe.row_bound", {"block1": 8192})
    assert reader.read(run) is None                        # no moe.slots
    gauges("moe.slots", {"block1": 32768})
    assert reader.read(run) == pytest.approx(0.5)
    assert reader.read({"ran": {}}) is None                # no counters
    assert reader.read({"ran": {"moe_counters": {}}}) is None
    # a layer the gauges do not know (another program's counters)
    assert reader.read(_counted({"block1": 4096, "mtp/block": 9})) is None


def test_gmm_tile_fill_reads_the_smallest_layer(gauges):
    reader = _reader("moe_gmm_tile_fill")
    assert reader.read({"ran": {}}) is None                # no expert layer
    gauges("moe.gmm_tile_fill", {"block1": 1.0, "block2": 1.0})
    assert reader.read({"ran": {}}) == 1.0
    gauges("moe.gmm_tile_fill", {"mtp/block": 0.66})
    assert reader.read({"ran": {}}) == pytest.approx(0.66)


@pytest.mark.parametrize("name", sorted({**SCOPES, **COUNTERS}))
def test_the_entry_by_name(name):
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    timed = name in SCOPES
    assert entry == {
        "name": name, "unit": "ms" if timed else "ratio",
        "better": "lower" if timed else "higher",
        "source": "device_trace" if timed else "program_counter",
        "layer": SCOPES[name][2] if timed else COUNTERS[name],
        "moves": "train_throughput", "workloads": entry["workloads"]}
    assert set(CELLS) <= set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
