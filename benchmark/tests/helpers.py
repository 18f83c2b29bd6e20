"""A throw-away root for tests: BENCHMARK.json and benchmark/'s data
directories copied into a temporary directory, where a test adds files
and entries without touching what the repository has."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT = {"seq_len": 64, "per_chip_batch": 2, "trace_steps": 3,
            "overrides": {"num_layers": 2, "num_heads": 4, "emb_dim": 64,
                          "vocab_size": 512}}
TINY_RESNET = {"per_chip_batch": 4, "trace_steps": 3, "reference_items": 4,
               "overrides": {"num_filters": 8, "image_size": 32}}
# No cell of BENCHMARK.json is served yet (PERF.md, Open questions), so
# the serve runner's test brings a whole cell and its metrics' entries.
TINY_SERVE_CELL = {
    "config": "gpt2-medium", "traffic": "tiny", "runner": "serve",
    "chips": 1, "why": "test",
    "params": {"num_slots": 4, "max_len": 128, "page_size": 16,
               "rate_per_s": 6.0,
               "prompt_median": 16, "prompt_sigma": 0.8, "prompt_min": 4,
               "prompt_max": 40,
               "budget_median": 8, "budget_sigma": 0.7, "budget_min": 4,
               "budget_max": 16,
               "warm_prompt_lens": [8, 16], "warm_budget": 4,
               "poll_ms": 20, "drain_limit_s": 20, "setup_timeout_s": 300,
               "span_capacity": 400000,
               "overrides": {"vocab_size": 1024}}}
SERVE_METRICS = {
    name: {"kind": kind, "unit": "ms", "better": "lower",
           "source": source, **extra}
    for name, kind, source, extra in (
        ("ttft_p95_ms", "end_to_end", "host_clock", {"bound": 0.1}),
        ("tpot_p95_ms", "end_to_end", "host_clock", {"bound": 0.1}),
        ("decode_compute_ms", "per_layer", "program_span",
         {"layer": "Serving", "moves": "tpot_p95_ms"}),
        ("queue_wait_ms_p95", "per_layer", "program_span",
         {"layer": "Serving", "moves": "ttft_p95_ms"}),
        ("gen_late_ms_p95", "per_layer", "host_clock",
         {"layer": "Load generator", "moves": "ttft_p95_ms"}))}


def make_root(tmp_path) -> str:
    root = str(tmp_path / "root")
    os.makedirs(os.path.join(root, "benchmark"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("workloads", "configs", "metrics", "models", "runners"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    return root


def add_cell(root: str, name: str, like, params: dict,
             traffic: str, config_edits: dict = None,
             metrics: dict = None) -> None:
    """A new cell as new files only: its own workload file, its own
    configuration file (a copy of ``like``'s with ``config_edits``) and
    entries appended to BENCHMARK.json.  ``like`` names a cell that is
    there, or is a whole cell."""
    def path(*parts):
        return os.path.join(root, "benchmark", *parts)

    if isinstance(like, dict):
        cell = json.loads(json.dumps(like))
    else:
        with open(path("workloads", like + ".json")) as f:
            cell = json.load(f)
    with open(path("configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    new_config = name + "-config"
    for key, value in (config_edits or {}).items():
        if isinstance(value, dict):
            config[key].update(value)
        else:
            config[key] = value
    config["name"] = new_config
    with open(path("configs", new_config + ".json"), "w") as f:
        json.dump(config, f)
    shutil.copy(path("configs", cell["config"] + ".reference.py"),
                path("configs", new_config + ".reference.py"))
    cell.update(config=new_config, traffic=traffic, chips=1)
    cell["params"].update(params)
    with open(path("workloads", name + ".json"), "w") as f:
        json.dump(cell, f)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": new_config, "source": config["source"],
        "file": f"benchmark/configs/{new_config}.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": name, "config": new_config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for entry in bench[kind]:
            if "workloads" in entry and like in entry["workloads"]:
                entry["workloads"].append(name)
    for metric_name, entry in (metrics or {}).items():
        entry = dict(entry)
        bench[entry.pop("kind")].append(
            {"name": metric_name, **entry, "workloads": [name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
