"""The seven readers of the program's set-up log (PR 37): their entries
in ``BENCHMARK.json`` by name, and one tiny cell through the train
runner on the CPU, where all seven print and the step's share and the
other programs' add up to what ``compile_trace_lower_s`` reads.  What a
reader makes of a recorded log, of the parent's log and of overlapping
records is in the tier-1 ``tests/test_profile_names.py``."""

import json
import os
import time

import pytest

from helpers import ROOT, TINY_GPT, add_cell, make_root

SETUP_READERS = ("step_trace_s", "step_lower_s", "step_backend_s",
                 "cache_load_s", "state_programs_s", "hvd_init_s",
                 "setup_uncovered_s")


def test_the_entries_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name in SETUP_READERS:
        assert by_name[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "Entry points / mesh",
            "moves": "setup_s", "workloads": by_name[name]["workloads"]}
        assert set(by_name[name]["workloads"]) >= {
            "gpt2m_train_s1024", "resnet50_train_b256", "gpt2m_train_dp4",
            "granite4hm_train_s8192", "glm47f_train_s8192",
            "trinitym_train_s8192"}
        assert set(by_name[name]["workloads"]) <= set(cells)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py"))


@pytest.mark.skipif(not os.path.exists("/proc/self/stat"),
                    reason="setup_uncovered_s needs the process's start")
def test_all_seven_print_and_the_parts_add_up(tmp_path):
    import run as cli
    from benchmark.harness import registry
    from horovod_tpu.obs import profile

    # the log is the process's: what earlier tests of this session
    # compiled (a ``local_step`` of their own among it) is not this run's
    log = profile._COMPILE_LOG.records
    kept = [r for r in log if r["phase"] in ("process", "init")]
    log.clear()
    log.extend(kept)
    root = make_root(tmp_path)
    add_cell(root, "tiny_gpt", "gpt2m_train_s1024", TINY_GPT,
             traffic="tiny", config_edits={"program": {"size": "nano"}})
    cell = registry.load_cell("tiny_gpt", root)
    run = registry.load_runner(cell["runner"], root).run(
        cell, 2**31 + 37, 1.0, True, time.perf_counter(), allow_cpu=True)
    line = cli.result_line(run, trace=True)
    assert line["correct"] is True, line["checks"]
    assert all(line["metrics"][name]["unit"] == "s" for name in SETUP_READERS)
    got = {name: value["value"] for name, value in line["metrics"].items()}
    assert got["step_trace_s"] > 0 and got["step_lower_s"] > 0
    assert got["step_backend_s"] > 0 and got["hvd_init_s"] > 0
    assert [r["phase"] for r in profile.compile_log()].count("init") == 1
    assert got["cache_load_s"] == 0.0          # the CPU has no cache
    assert got["state_programs_s"] > 0 and got["setup_uncovered_s"] > 0
    # the step's tracing and lowering and the other programs' are the
    # accepted reader's sum
    step = registry.load_module(os.path.join(
        root, "benchmark", "metrics", "step_trace_s.py"))
    own, others = step.split(run)
    assert {r["program"] for r in own}.isdisjoint(
        r["program"] for r in others)
    assert got["step_trace_s"] + got["step_lower_s"] + sum(
        r["seconds"] for r in others if r["phase"] in ("trace", "lower")
    ) == pytest.approx(got["compile_trace_lower_s"], rel=1e-9)
    # inside against outside: the runner's clock is around the step's
    # lowering and compiling (its tracing too, unless an earlier test of
    # this process traced the same function)
    assert got["step_lower_s"] + got["step_backend_s"] <= got["compile_s"]
    # a union: never more than the records' sum
    assert got["state_programs_s"] <= sum(r["seconds"] for r in others) + 1e-9
