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


# ---- a hand-built .xplane.pb (tsl/profiler/protobuf/xplane.proto) ----

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def xplane_bytes(planes) -> bytes:
    """``[(plane name, [(line name, t0_ns, [(event name, scope or None,
    offset_ps, duration_ps), ...]), ...]), ...]`` as the profiler writes
    it: an event refers to its metadata by id, the metadata carries the
    name and, where there is a scope, a ``tf_op`` stat; a scope that
    starts with ``ref:`` is stored as a reference to a stat name, as the
    profiler stores repeated strings."""
    def entry(key, message):
        return _field(1, key) + _field(2, message)

    space = b""
    for plane_name, lines in planes:
        ids, stat_ids = {}, {"tf_op": 1}
        plane = _field(2, plane_name)
        for line_name, t0_ns, events in lines:
            line = _field(2, line_name) + _field(3, t0_ns)
            for name, scope, offset_ps, dur_ps in events:
                md = ids.setdefault((name, scope), len(ids) + 1)
                line += _field(4, _field(1, md) + _field(2, offset_ps)
                               + _field(3, dur_ps))
            plane += _field(3, line)
        for (name, scope), md in ids.items():
            message = _field(1, md) + _field(2, name)
            if scope and scope.startswith("ref:"):
                ref = stat_ids.setdefault(scope[4:], len(stat_ids) + 1)
                message += _field(5, _field(1, 1) + _field(7, ref))
            elif scope:
                # another stat first: the scope is not the only one
                message += _field(5, _field(1, 99) + _field(3, 7))
                message += _field(5, _field(1, 1) + _field(5, scope))
            plane += _field(4, entry(md, message))
        for stat_name, key in stat_ids.items():
            plane += _field(5, entry(key, _field(1, key)
                                     + _field(2, stat_name)))
        space += _field(1, plane)
    return space + _field(4, "hostname")


PALLAS = ('%{0} = (bf16[128,1024,64]{{2,1,0}}) custom-call(bf16[128,1024,64]'
          '{{2,1,0}} %x), custom_call_target="tpu_custom_call"')
STEP = "jit(local_step)/"
FWD, BWD = STEP + "jvp(GPT)/", STEP + "transpose(jvp(GPT))/"
# (event name, scope, offset_ps, duration_ps); the texts and scopes are
# those of a traced GPT step on a TPU v5 lite (my chip run, PR 24)
SLICE = [
    ("%copy-start.17 = (f32[8]) copy-start(f32[8] %p)", None, 0, 2_000_000),
    ("%fusion = f32[8,1024,1024] fusion(%a), kind=kLoop",
     FWD + "embed/wte/jit(_take)/gather:", 2_000_000, 3_000_000),
    ("%convolution_add_fusion.3 = bf16[8192,3072] fusion(%a), kind=kOutput",
     FWD + "block0/attn/qkv/dot_general:", 5_000_000, 10_000_000),
    (PALLAS.format("flash_fwd.2"),
     FWD + "block0/attn/flash_fwd/pallas_call:", 15_000_000, 20_000_000),
    ("%convolution_add_fusion.1 = bf16[8192,4096] fusion(%a), kind=kOutput",
     FWD + "block0/mlp/fc1/dot_general:", 35_000_000, 12_000_000),
    ("%fusion.399 = f32[8192,50257] fusion(%a), kind=kOutput",
     FWD + "head/dot_general:", 47_000_000, 8_000_000),
    ("%multiply_reduce_fusion.9 = f32[1024,50257] fusion(%a), kind=kOutput",
     BWD + "head/dot_general:", 55_000_000, 16_000_000),
    ("%fusion.403 = bf16[8192,4096] fusion(%a), kind=kOutput",
     "ref:" + BWD + "block0/mlp/fc2/dot_general:", 71_000_000, 24_000_000),
    (PALLAS.format("flash_bwd_dq.2"),
     BWD + "block0/attn/flash_bwd_dq/pallas_call:", 95_000_000, 30_000_000),
    ("%fusion.225 = bf16[8192,1024] fusion(%a), kind=kOutput",
     BWD + "block0/attn/proj/dot_general:", 125_000_000, 14_000_000),
    ("%all-reduce.81 = (f32[12596224]{0}) all-reduce(%a), channel_id=1",
     STEP + "grad_allreduce/allreduce/psum:", 139_000_000, 9_000_000),
    ("%fusion.297 = f32[1024,4096] fusion(%a), kind=kLoop",
     STEP + "optimizer_update/mul:", 148_000_000, 1_000_000),
    ("%fusion.298 = f32[1024,4096] fusion(%a), kind=kLoop",
     STEP + "optimizer_update/add:", 149_000_000, 6_000_000),
    ("%fusion.6 = f32[] fusion(%a), kind=kInput",
     STEP + "jvp()/reduce_sum:", 155_000_000, 500_000),
]
HOST = [("dispatch", None, 1_000_000, 500_000),
        ("PjitFunction(local_step)", None, 1_100_000, 300_000),
        ("wait_loss", None, 2_000_000, 150_000_000)]


def xplane_slice(tmp_path) -> str:
    """A slice of a traced GPT step as the profiler writes it: a forward
    and a ``transpose(...)`` event of each scope, two Pallas calls, a
    collective, an unscoped copy; a host plane and a plane that is
    neither."""
    path = tmp_path / "slice.xplane.pb"
    path.write_bytes(xplane_bytes([
        ("/device:TPU:0", [("Steps", 0, [("1", None, 0, 155_000_000)]),
                           ("XLA Ops", 1000, SLICE)]),
        ("/host:CPU", [("python3", 900, HOST)]),
        ("/device:CUSTOM:Megascale", [("XLA Ops", 0, SLICE[:1])])]))
    return str(path)
