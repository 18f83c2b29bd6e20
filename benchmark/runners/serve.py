"""Runner ``serve``: one served cell, once, through ``ServeJob``.

This process is the client and the launcher; the rank it starts holds
the chip, so nothing here may initialise a JAX backend.  Set-up starts
the job and warms every prefill bucket the traffic uses (one request at
a time into an empty pool, one prompt twice for the determinism check);
then the open-loop schedule of benchmark/harness/loadgen.py is offered
for ``seconds``: a submitter thread sends each request when it is due,
this thread polls every outstanding request at the cell's cadence.
Requests still unfinished ``drain_limit_s`` after the window fail.

A corrected copy of ``bench._run_serve_load``: the model is the
configuration's and not a pinned toy, latency counts from when a request
was *due*, the window is bound by time, and generator lateness is
reported.

With ``trace`` the rank's environment arms the program's own spans
(``HVDTPU_TRACE``, obs/trace.py).  The device's time is not measured:
only the rank holds the chip and the program has no profiler hook
(PERF.md, Open questions), so the last line's ``device`` carries no
``busy_s``.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import tempfile
import threading
import time

from benchmark.harness import device as dev, loadgen
from benchmark.harness.peaks import peaks

SPAN_SCHEMA = "hvdtpu-trace-v1"


def _spec(config: dict, params: dict, seed: int) -> dict:
    spec = {"size": config["program"]["size"],
            "overrides": {"vocab_size": config["vocab_size"]},
            "seed": int(seed) % (1 << 31),
            "num_slots": params["num_slots"], "max_len": params["max_len"],
            "kv_mode": "paged", "page_size": params["page_size"]}
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        spec["overrides"] = dict(params["overrides"])
    return spec


def _result(job, rid: str, timeout: float) -> dict:
    """``client.result`` that gives up as soon as the job has died (a
    rank that cannot start must not cost the whole set-up timeout)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        doc = job.client.poll(rid)
        if doc is not None and doc.get("done"):
            if doc.get("error"):
                raise RuntimeError(f"request {rid} refused: {doc['error']}")
            return doc
        error = getattr(job, "_error", None)
        if error is not None:
            raise RuntimeError(f"the serving job died: {error!r}")
        time.sleep(0.05)
    raise TimeoutError(f"request {rid} not finished within {timeout} s")


def _warm_up(job, params: dict, vocab: int, timeout: float) -> dict:
    """One request per prefill bucket, one at a time; the first prompt
    goes twice.  Returns the determinism check."""
    first = None
    for i, length in enumerate(params["warm_prompt_lens"]):
        prompt = [(7 * k + length) % vocab for k in range(length)]
        rid = job.client.submit(prompt,
                                max_new_tokens=params["warm_budget"])
        doc = _result(job, rid, timeout)
        if i == 0:
            first = (prompt, list(doc["tokens"]))
    prompt, tokens = first
    rid = job.client.submit(prompt, max_new_tokens=params["warm_budget"])
    again = list(_result(job, rid, timeout)["tokens"])
    return {"ok": again == tokens, "first": tokens, "again": again}


def _offer(client, requests, t_start, sent, stop):
    """The submitter: each request when it is due, never earlier."""
    for i, req in enumerate(requests):
        wait = t_start + req["due_s"] - time.perf_counter()
        if wait > 0 and stop.wait(wait):
            return
        rid = client.submit(req["prompt"], max_new_tokens=req["budget"])
        sent[i] = (rid, time.perf_counter())


def _poll_until(client, requests, sent, t_start, deadline, poll_s):
    """Poll every outstanding request until all are done or the deadline.
    Per request: when its first token and its last token were seen."""
    seen = [None] * len(requests)  # [t_first, t_last, tokens, done]
    open_ = set(range(len(requests)))
    while open_ and time.perf_counter() < deadline:
        for i in sorted(open_):
            if sent[i] is None:
                continue
            doc = client.poll(sent[i][0])
            if doc is None or not doc.get("tokens"):
                continue
            now = time.perf_counter()
            rec = seen[i]
            if rec is None:
                rec = seen[i] = [now, now, [], False]
            if len(doc["tokens"]) > len(rec[2]):
                rec[1], rec[2] = now, list(doc["tokens"])
            if doc.get("done"):
                rec[3] = not doc.get("error")
                open_.discard(i)
        time.sleep(poll_s)
    return seen


def offer_window(job, requests, seconds: float, params: dict,
                 vocab: int) -> list:
    """Offer ``requests`` open loop from now and follow each to its end
    or to the drain deadline.  One row per request, times in seconds
    from the window's start."""
    from horovod_tpu.serve.frontend import ServeClient

    sent = [None] * len(requests)
    stop = threading.Event()
    t_start = time.perf_counter()
    offer = threading.Thread(
        target=_offer, name="bench_offer", daemon=True,
        args=(ServeClient(job.addr, job.secret), requests, t_start, sent,
              stop))
    offer.start()
    try:
        seen = _poll_until(
            job.client, requests, sent, t_start,
            t_start + seconds + params["drain_limit_s"],
            params["poll_ms"] / 1e3)
    finally:
        stop.set()
        offer.join(timeout=10)
    rows = []
    for req, sub, rec in zip(requests, sent, seen):
        done = bool(rec and rec[3])
        tokens = rec[2] if rec else []
        rows.append({
            "due_s": req["due_s"], "budget": req["budget"],
            "sent_s": None if sub is None else sub[1] - t_start,
            "first_s": None if rec is None else rec[0] - t_start,
            "last_s": None if rec is None else rec[1] - t_start,
            "tokens": len(tokens), "done": done,
            "wrong": done and (len(tokens) != req["budget"] or any(
                not 0 <= t < vocab for t in tokens))})
    return rows


def _read_spans(trace_dir: str, t_from: float, t_to: float) -> list:
    """The program's own spans (obs/trace.py dumps) that began inside
    the window, from every process that wrote one."""
    spans = []
    for path in glob.glob(os.path.join(trace_dir, "*.json")):
        with open(path) as f:
            doc = json.load(f)
        # (ServeJob's shutdown also merges the dumps into a waterfall
        # and a report in the same directory; those are not dumps.)
        if not isinstance(doc, dict) or doc.get("schema") != SPAN_SCHEMA:
            continue
        spans.extend(s for s in doc["spans"] if t_from <= s["t0"] <= t_to)
    return spans


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
        allow_cpu: bool = False, dump_trace: str = None) -> dict:
    params = cell["params"]
    config = cell["config_values"]
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" and not allow_cpu:
        raise dev.NoAccelerator(
            "JAX is held to the CPU (JAX_PLATFORMS=cpu): the benchmark "
            "measures nothing without the chip")

    from horovod_tpu.serve import ServeJob

    spec = _spec(config, params, seed)
    vocab = spec["overrides"]["vocab_size"]
    env = {"JAX_PLATFORMS": "cpu"} if allow_cpu else {}
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_spans_")
        env["HVDTPU_TRACE"] = trace_dir + os.sep
        env["HVDTPU_TRACE_CAPACITY"] = str(params["span_capacity"])
    requests = loadgen.schedule(params, seed, seconds, vocab)
    timeout = params["setup_timeout_s"]
    job = ServeJob(spec, np=1, env=env or None, max_retries=0,
                   timeout=timeout + seconds + params["drain_limit_s"]
                   ).start()
    try:
        same = _warm_up(job, params, vocab, timeout)

        t_start, wall_start = time.perf_counter(), time.time()
        rows = offer_window(job, requests, seconds, params, vocab)
        wall_end = time.time()
        results, _ = job.stop(timeout=timeout)
        front_door = job.front_door.stats()
    finally:
        job.shutdown()
    summary = results[0]
    device = dev.require(summary["device"]["platform"],
                         summary["device"]["kind"],
                         summary["device"]["count"], cell["chips"],
                         allow_cpu)

    from jax._src import xla_bridge

    wrong = sum(1 for r in rows if r["wrong"])
    checks = {
        "budgets_and_vocabulary": {"ok": wrong == 0, "wrong": wrong},
        "same_prompt_same_tokens": same,
        "parent_off_backend": {
            "ok": not xla_bridge.backends_are_initialized()},
        "rank_summary": {"ok": summary.get("kv", {}).get("mode") == "paged",
                         "steps": summary.get("steps"),
                         "tokens": summary.get("tokens"),
                         "completed": summary.get("completed")},
    }
    spans = []
    if trace_dir:
        spans = _read_spans(trace_dir, wall_start, wall_end)
        shutil.rmtree(trace_dir, ignore_errors=True)
    memory = summary.get("memory", {})
    run_ = {
        "cell": cell, "config": config, "params": params,
        "device": device, "chips": cell["chips"],
        "setup_s": t_start - t0, "requests": rows, "spans": spans,
        "window_s": seconds, "summary": summary,
        # A stall in the request plane (a front-door takeover, fewer
        # decode steps than the window has room for) explains a run
        # that reads far off.
        "notes": {"front_door_takeovers": front_door["takeovers"],
                  "decode_steps": summary.get("steps")},
        "attempted": len(rows),
        "failed": sum(1 for r in rows if not r["done"] or r["wrong"]),
        "checks": checks,
        "correct": all(c["ok"] for c in checks.values()),
        "memory_peak_bytes": int(memory.get("census", {}).get(
            "device", {}).get("peak_bytes") or 0),
    }
    if device["platform"] == "tpu":
        run_["peaks"] = peaks(device["kind"])
    return run_
