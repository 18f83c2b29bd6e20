"""The reader ``flash_live_tile_share`` and its entry in
``BENCHMARK.json``, pinned by name: over every layer type of
``ran["flash_tiles"]``, the live tiles over the steps the flash kernels'
grids walk; ``None`` for a program whose builder leaves no such pair, so
that its line leaves the metric out."""

import os

import pytest

from helpers import ROOT

NAME = "flash_live_tile_share"
CELLS = ["trinitym_train_s8192", "phi4mf_train_s8192",
         "smallthinker_train_s16384", "lfm2_train_s32768"]


def _read(tiles):
    from benchmark.harness import registry

    ran = {} if tiles is None else {"flash_tiles": tiles}
    reader = registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", NAME + ".py"))
    return reader.read({"ran": ran, "chips": 1})


# what the four builders left on PR 48's tree (the whole rectangle for a
# grid) and what a grid that walks the live tiles alone leaves
@pytest.mark.parametrize("tiles,share", [
    ({"full_attention": {"live": 133120, "grid": 262144}}, 133120 / 262144),
    ({"full_attention": {"live": 29568, "grid": 57344},
      "sliding_attention": {"live": 14112, "grid": 57344}},
     43680 / 114688),
    ({"cross_attention": {"live": 10880, "grid": 20480},
      "full_attention": {"live": 10880, "grid": 20480},
      "sliding_attention": {"live": 2480, "grid": 20480}}, 24240 / 61440),
    ({"full_attention": {"live": 133120, "grid": 133120}}, 1.0),
    ({"full_attention": {"live": 29568, "grid": 29568},
      "sliding_attention": {"live": 14112, "grid": 14112}}, 1.0),
], ids=["lfm2_rectangle", "smallthinker_rectangle", "phi_rectangle",
        "lfm2_live_alone", "smallthinker_live_alone"])
def test_it_sums_every_layer_type(tiles, share):
    assert _read(tiles) == pytest.approx(share, rel=1e-12)


def test_a_program_without_the_pair_reads_nothing():
    assert _read(None) is None
    assert _read({}) is None
    assert _read({"full_attention": {"live": 0, "grid": 0}}) is None
    assert _read({"full_attention": {}}) is None


def test_the_entry_by_name():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "Kernels",
        "moves": "train_throughput", "workloads": entry["workloads"]}
    assert set(CELLS) <= set(entry["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert NAME in registry.available_metrics(ROOT)
