"""Live telemetry plane (obs/stream.py, obs/live.py, obs/straggler.py):
delta encoding round-trips, aggregator merge across elastic
incarnations, Prometheus exposition validity on the KV server's
/metrics branch, deterministic straggler attribution on both collective
paths (controller cycles, elastic KV waits) under the ``action=delay``
fault, the KV wait backoff, and the 2-proc chaos acceptance: an
injected delay straggler is named live and at job end, and attribution resets across incarnations."""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu.obs as obs
from horovod_tpu.obs import live as obs_live
from horovod_tpu.obs import progress as obs_progress
from horovod_tpu.obs import straggler as obs_straggler
from horovod_tpu.obs import stream as obs_stream
from horovod_tpu.obs import summary as obs_summary
from horovod_tpu.run import rendezvous as rdv
from horovod_tpu.run.rendezvous import KVStoreClient, KVStoreServer
from horovod_tpu.testing import faults


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    monkeypatch.delenv(faults.SPEC_ENV, raising=False)
    faults.reset()
    obs.reset_registry()
    obs_progress.reset()
    obs_stream.stop_stream()
    yield
    faults.reset()
    obs.reset_registry()
    obs_progress.reset()
    obs_stream.stop_stream()


@pytest.fixture()
def kv_server():
    server = KVStoreServer()
    server.start()
    try:
        yield server
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# stream: compact delta encoding
# ---------------------------------------------------------------------------


def _populate(reg):
    reg.counter("ops.total", kind="x").inc(3)
    reg.gauge("queue.depth").set(7)
    h = reg.histogram("lat.ms")
    for v in (1.0, 2.0, 40.0):
        h.observe(v)


def test_delta_roundtrip_changed_only():
    reg = obs.get_registry()
    _populate(reg)
    snap1 = obs_stream.snapshot_map(reg.snapshot())
    reg.counter("ops.total", kind="x").inc(2)
    reg.histogram("lat.ms").observe(99.0)
    snap2 = obs_stream.snapshot_map(reg.snapshot())

    delta = obs_stream.encode_delta(snap1, snap2)
    # only the two touched instruments travel
    assert sorted(d["n"] for d in delta) == ["lat.ms", "ops.total"]
    view = dict(snap1)
    obs_stream.apply_delta(view, delta)
    assert view == snap2


def test_delta_full_snapshot_and_expand_schema():
    reg = obs.get_registry()
    _populate(reg)
    snap = obs_stream.snapshot_map(reg.snapshot())
    delta = obs_stream.encode_delta({}, snap)
    assert len(delta) == 3
    view = {}
    obs_stream.apply_delta(view, delta)
    # expand_metric reconstructs the dump schema exactly (mean included)
    assert view == snap
    hist = view[obs_stream.metric_key(
        {"name": "lat.ms", "tags": {}})]
    for field in ("count", "sum", "min", "max", "mean", "p50", "p90", "p99"):
        assert field in hist


def test_delta_empty_when_nothing_changed():
    reg = obs.get_registry()
    _populate(reg)
    snap = obs_stream.snapshot_map(reg.snapshot())
    assert obs_stream.encode_delta(snap, snap) == []


def test_delta_tombstones_removed_instruments():
    """Instrument removal (the elastic-rendezvous straggler reset) must
    propagate to the aggregator view, or stale blame would survive a
    re-formed world forever."""
    reg = obs.get_registry()
    obs_straggler.record(1, 100.0)
    snap1 = obs_stream.snapshot_map(reg.snapshot())
    obs_straggler.reset()
    snap2 = obs_stream.snapshot_map(reg.snapshot())
    delta = obs_stream.encode_delta(snap1, snap2)
    assert all("rm" in d for d in delta)
    view = dict(snap1)
    obs_stream.apply_delta(view, delta)
    assert view == snap2
    assert not any(k.startswith(obs_straggler.PREFIX) for k in view)


# ---------------------------------------------------------------------------
# publisher -> KV server -> aggregator
# ---------------------------------------------------------------------------


def test_stream_compact_quantile_roundtrip():
    """The wire compaction renames histogram percentiles p50/p90/p99 to
    q50/q90/q99 and back; one dropped or mis-mapped quantile here would
    silently skew every live digest and /metrics summary."""
    reg = obs.get_registry()
    h = reg.histogram("lat.ms")
    for v in (1.0, 5.0, 9.0, 40.0, 400.0):
        h.observe(v)
    (metric,) = [m for m in reg.snapshot() if m["name"] == "lat.ms"]
    compact = obs_stream._compact(metric)
    assert {"q50", "q90", "q99"} <= set(compact)
    assert not {"p50", "p90", "p99"} & set(compact)
    assert compact["q50"] == metric["p50"]
    assert compact["q90"] == metric["p90"]
    assert compact["q99"] == metric["p99"]
    back = obs_stream.expand_metric(json.loads(json.dumps(compact)))
    for field in ("p50", "p90", "p99", "count", "sum", "min", "max"):
        assert back[field] == metric[field], field
    assert not {"q50", "q90", "q99"} & set(back)
    assert back["mean"] == pytest.approx(metric["mean"])


def test_publisher_to_aggregator_end_to_end(kv_server, tmp_path):
    reg = obs.get_registry()
    _populate(reg)
    kv = KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret)
    pub = obs_stream.StreamPublisher(kv, rank=0, epoch=0, interval=60)
    assert pub.publish_once() is not None
    reg.counter("ops.total", kind="x").inc()
    assert pub.publish_once() is not None

    hist = str(tmp_path / "live_history.jsonl")
    plane = obs_live.LivePlane(
        kv_server, interval=60, history_path=hist, expected_ranks=1,
        print_digest=False,
    )
    assert plane.round() == 2
    # consumed keys are pruned from the store (bounded launcher memory)
    assert kv_server.scan(obs_stream.LIVE_SCOPE + "/") == {}
    merged = plane.agg.merged()
    assert list(merged) == [0]
    key = obs_stream.metric_key({"name": "ops.total", "tags": {"kind": "x"}})
    assert merged[0].metrics[key]["value"] == 4
    rows = [json.loads(l) for l in open(hist)]
    assert rows and rows[-1]["ranks_reporting"] == 1


def test_publisher_failure_is_swallowed():
    kv = KVStoreClient("127.0.0.1:1")  # nothing listens there
    pub = obs_stream.StreamPublisher(kv, rank=0, epoch=0, interval=60)
    assert pub.publish_once() is None
    assert pub._seq == 0  # unpublished delta is retried next beat
    pub.stop()  # exit flush against a dead launcher is swallowed too


def test_publisher_stop_flushes_final_partial_interval(kv_server):
    """stop() publishes once more so the last partial interval's
    metrics (the job's concluding attributions) reach the launcher's
    end-of-job drain round."""
    reg = obs.get_registry()
    kv = KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret)
    pub = obs_stream.StreamPublisher(kv, rank=0, epoch=0, interval=3600)
    pub.start()
    pub.publish_once()
    reg.counter("final.events").inc(7)  # lands after the last beat
    pub.stop()
    plane = obs_live.LivePlane(kv_server, interval=3600,
                               history_path=None, print_digest=False)
    plane.round()
    key = obs_stream.metric_key({"name": "final.events", "tags": {}})
    assert plane.agg.merged()[0].metrics[key]["value"] == 7


def test_poison_doc_is_discarded_not_wedging(kv_server):
    """A JSON-valid but schema-invalid snapshot (a version-skewed
    worker) must cost one warning and be pruned — never wedge every
    subsequent round on the same key."""
    kv = KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret)
    kv.put("obs/live/0", "0/0", b'{"epoch": 0}')  # no "rank": ingest raises
    kv.put("obs/live/0", "1/0", json.dumps(
        _payload(1, 0, 0, [_counter("a", 3)])).encode())
    plane = obs_live.LivePlane(kv_server, interval=60, history_path=None,
                               print_digest=False)
    plane.round()
    # the poison key is gone and the good doc was ingested
    assert kv_server.scan(obs_stream.LIVE_SCOPE + "/") == {}
    assert list(plane.agg.merged()) == [1]


def test_live_plane_armed_from_worker_env_dict(kv_server, capsys):
    """The launcher half must arm from base_env — the SAME source the
    spawned workers read — so an env-dict override cannot start workers
    streaming into a store nobody drains."""
    from horovod_tpu.run.runner import (
        _maybe_start_live_plane, _stop_live_plane,
    )

    base_env = {"HVDTPU_LIVE_STATS_SECS": "30"}
    plane, owned = _maybe_start_live_plane(
        base_env, 2, kv_server=kv_server,
        kv_addr=f"10.1.2.3:{kv_server.port}",
    )
    try:
        assert plane is not None and owned is None
        # workers and scrapers are told the same routable endpoint
        assert base_env["HVDTPU_LIVE_KV"] == f"10.1.2.3:{kv_server.port}"
        assert plane.announce_host == "10.1.2.3"
        assert f"http://10.1.2.3:{kv_server.port}/metrics" in (
            capsys.readouterr().out
        )
    finally:
        _stop_live_plane(plane, owned)
    # unarmed env -> no plane, no server
    assert _maybe_start_live_plane({}, 2, kv_server=kv_server) == (None, None)


def test_maybe_start_from_env(kv_server, monkeypatch):
    monkeypatch.setenv("HVDTPU_LIVE_STATS_SECS", "30")
    monkeypatch.setenv("HVDTPU_LIVE_KV", f"127.0.0.1:{kv_server.port}")
    monkeypatch.setenv(rdv.SECRET_ENV, kv_server.secret)
    monkeypatch.setenv("HVDTPU_RANK", "3")
    pub = obs_stream.maybe_start_from_env()
    assert pub is not None and pub.rank == "3"
    assert obs_stream.maybe_start_from_env() is pub  # singleton
    obs_stream.stop_stream()
    monkeypatch.setenv("HVDTPU_LIVE_STATS_SECS", "0")
    assert obs_stream.maybe_start_from_env() is None


# ---------------------------------------------------------------------------
# aggregator: incarnation merge, digest, history
# ---------------------------------------------------------------------------


def _payload(rank, epoch, seq, metrics=(), progress=0, phase="steady",
             full=None):
    return {
        "v": 1, "rank": rank, "epoch": epoch, "seq": seq,
        "t": 1000.0 + seq, "phase": phase, "progress": progress,
        "full": (seq == 0) if full is None else full,
        "metrics": list(metrics),
    }


def _counter(name, value, **tags):
    out = {"n": name, "k": "c", "v": value}
    if tags:
        out["g"] = {k: str(v) for k, v in tags.items()}
    return out


def test_aggregator_merges_across_incarnations():
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(1, 0, 0, [_counter("a", 10)], progress=10))
    agg.ingest(_payload(0, 0, 0, [_counter("a", 11)], progress=11))
    # rank 1 respawned into epoch 2: fresh counters, smaller values
    agg.ingest(_payload(1, 2, 0, [_counter("a", 1)], progress=1))
    merged = agg.merged()
    assert merged[1].epoch == 2
    assert merged[1].metrics[obs_stream.metric_key(
        {"name": "a", "tags": {}})]["value"] == 1
    assert merged[0].epoch == 0
    # the dead incarnation stays queryable
    assert [(v.rank, v.epoch) for v in agg.incarnations()] == [
        (0, 0), (1, 0), (1, 2)]


def test_aggregator_full_snapshot_resets_view():
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(0, 0, 0, [_counter("a", 1), _counter("b", 2)]))
    # publisher restarted in-process: full snapshot without "b"
    agg.ingest(_payload(0, 0, 0, [_counter("a", 5)], full=True))
    metrics = agg.merged()[0].metrics
    assert [m["name"] for m in metrics.values()] == ["a"]


def test_digest_names_straggler_and_lagging_rank():
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(0, 0, 0, [
        _counter(obs_straggler.PREFIX + "last_arrivals", 9, rank=1),
    ], progress=40))
    agg.ingest(_payload(1, 0, 0, [], progress=31))
    d = agg.digest(2)
    assert "ranks 2/2" in d
    assert "min 31 (rank 1)" in d
    assert "straggler rank 1" in d and "9 last-arrivals" in d
    row = agg.history_row(2)
    assert row["straggler"]["rank"] == 1
    assert row["progress"] == {"0": 40, "1": 31}


def test_digest_no_ranks_and_no_straggler():
    agg = obs_live.LiveAggregator()
    assert "no rank" in agg.digest()
    agg.ingest(_payload(0, 0, 0, []))
    assert "straggler none" in agg.digest(1)
    assert agg.straggler() is None


# ---------------------------------------------------------------------------
# Prometheus exposition + /metrics endpoint
# ---------------------------------------------------------------------------

_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*")*\})?'
    r' (NaN|[-+]?[0-9]+(\.[0-9]+)?([eE][-+]?[0-9]+)?)$'
)
_PROM_TYPE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|summary)$"
)
_PROM_HELP = re.compile(
    r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*$"
)


def _assert_valid_exposition(text):
    """Exposition-format conformance (the rules real scrapers enforce):
    every comment is a well-formed HELP or TYPE line, at most one of
    each per family (a second is a hard parse error), HELP precedes
    TYPE, and every sample belongs to a family whose TYPE already
    appeared (bare samples make scrapers warn)."""
    assert text.endswith("\n")
    seen_types = set()
    seen_helps = set()
    for line in text.rstrip("\n").splitlines():
        if line.startswith("#"):
            if line.startswith("# HELP"):
                assert _PROM_HELP.match(line), f"bad HELP line: {line!r}"
                name = line.split()[2]
                assert name not in seen_helps, f"duplicate HELP for {name}"
                assert name not in seen_types, \
                    f"HELP after TYPE for {name}"
                seen_helps.add(name)
            else:
                m = _PROM_TYPE.match(line)
                assert m, f"bad comment line: {line!r}"
                name = line.split()[2]
                assert name not in seen_types, f"duplicate TYPE for {name}"
                seen_types.add(name)
        else:
            assert _PROM_SAMPLE.match(line), f"bad sample line: {line!r}"
            base = re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*", line).group(0)
            # summaries sample under <name>, <name>_sum, <name>_count
            fam = re.sub(r"_(sum|count)$", "", base)
            assert base in seen_types or fam in seen_types, \
                f"sample with no TYPE family: {line!r}"
            # duplicate label names are a hard parse error for scrapers
            keys = re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="', line)
            assert len(keys) == len(set(keys)), \
                f"duplicate label in: {line!r}"
    # every family carries help text, not just a type
    assert seen_types <= seen_helps, \
        f"TYPE without HELP: {sorted(seen_types - seen_helps)}"


def test_prometheus_exposition_is_valid_and_labelled():
    reg = obs.get_registry()
    _populate(reg)
    obs_straggler.record(1, 500.0)
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(
        0, 1, 0,
        obs_stream.encode_delta({}, obs_stream.snapshot_map(reg.snapshot())),
    ))
    text = agg.prometheus()
    _assert_valid_exposition(text)
    assert '# HELP hvdtpu_ops_total ' in text
    assert '# TYPE hvdtpu_ops_total counter' in text
    assert 'hvdtpu_ops_total{rank="0",epoch="1",kind="x"} 3.0' in text
    # histograms render as summaries with quantile labels + sum/count
    assert 'hvdtpu_lat_ms{rank="0",epoch="1",quantile="0.5"}' in text
    assert 'hvdtpu_lat_ms_count{rank="0",epoch="1"} 3' in text
    assert "hvdtpu_live_ranks_reporting 1" in text
    assert "hvdtpu_live_straggler_rank 1" in text
    # the blamed-rank instrument tag collides with the reserved rank
    # label and must be renamed, not duplicated (scrapers reject dups)
    assert ('hvdtpu_engine_straggler_last_arrivals'
            '{rank="0",epoch="1",tag_rank="1"} 1.0') in text


def _strict_parse_labels(line):
    """Char-level strict parse of one sample line's label block (the
    grammar real scrapers implement): label values may contain ONLY the
    escapes ``\\\\``, ``\\"`` and ``\\n``; a raw quote or backslash is
    a hard parse error.  Returns {label: unescaped value}."""
    if "{" not in line:
        return {}
    block = line[line.index("{") + 1: line.rindex("}")]
    labels = {}
    i = 0
    while i < len(block):
        eq = block.index("=", i)
        key = block[i:eq]
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", key), \
            f"bad label name {key!r} in {line!r}"
        assert block[eq + 1] == '"', f"unquoted value in {line!r}"
        j = eq + 2
        out = []
        while True:
            assert j < len(block), f"unterminated value in {line!r}"
            c = block[j]
            if c == "\\":
                esc = block[j + 1] if j + 1 < len(block) else ""
                assert esc in ('\\', '"', 'n'), \
                    f"illegal escape \\{esc} in {line!r}"
                out.append({"\\": "\\", '"': '"', "n": "\n"}[esc])
                j += 2
            elif c == '"':
                j += 1
                break
            else:
                assert c != "\n", f"raw newline in value in {line!r}"
                out.append(c)
                j += 1
        assert key not in labels, f"duplicate label {key} in {line!r}"
        labels[key] = "".join(out)
        i = j + 1 if j < len(block) and block[j] == "," else j
    return labels


def test_prometheus_hostile_label_values_roundtrip():
    """Satellite acceptance: program names (and any instrument tag) can
    carry quotes, backslashes and newlines — the exposition must escape
    them so a strict parser recovers the ORIGINAL value, and the rest
    of the line must stay well-formed."""
    hostile = 'jit_train"step\\fused\nphase2'
    reg = obs.get_registry()
    reg.gauge("mem.compiled.total_bytes", program=hostile).set(123.0)
    reg.gauge("perf.step_ms").set(5.0)
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(
        0, 0, 0,
        obs_stream.encode_delta({}, obs_stream.snapshot_map(reg.snapshot())),
    ))
    text = agg.prometheus()
    assert text.endswith("\n")
    # no raw newline may survive inside any sample line: the hostile
    # value must occupy ONE line
    sample_lines = [l for l in text.splitlines()
                    if l.startswith("hvdtpu_mem_compiled_total_bytes")]
    assert len(sample_lines) == 1
    labels = _strict_parse_labels(sample_lines[0])
    assert labels["program"] == hostile
    assert labels["rank"] == "0"
    # and every line in the whole exposition strict-parses
    for line in text.rstrip("\n").splitlines():
        if not line.startswith("#"):
            _strict_parse_labels(line)
            assert re.search(r" (NaN|[-+]?[0-9.eE+-]+)$", line), line


def test_prometheus_escape_function_table():
    esc = obs_live.prometheus_escape
    assert esc('plain') == 'plain'
    assert esc('a"b') == 'a\\"b'
    assert esc('a\\b') == 'a\\\\b'
    assert esc('a\nb') == 'a\\nb'
    # backslash-first ordering: escaping must not double-process
    assert esc('\\n') == '\\\\n'


def test_digest_and_history_surface_slo_alert():
    """A firing burn-rate alert must be visible in the live digest line
    and counted in live_history.jsonl rows; a healthy plane shows the
    quiet token; jobs with no SLO traffic show nothing."""
    fast = {"g": {"tenant": "acme", "slo": "interactive",
                  "metric": "ttft", "window": "fast"}}
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(0, 0, 0, [
        dict({"n": "serve.slo.burn", "k": "g", "v": 12.3}, **fast),
        dict({"n": "serve.slo.alert", "k": "g", "v": 1.0}, **fast),
        {"n": "serve.slo.alerts", "k": "c", "v": 1,
         "g": {"tenant": "acme", "slo": "interactive", "metric": "ttft"}},
    ]))
    d = agg.digest(1)
    assert "slo ALERT acme/interactive ttft fast" in d
    assert "12.3x" in d
    row = agg.history_row(1)
    assert row["slo"] == {"firing": 1, "alerts": 1}
    # healthy: burn present, alert gauge 0
    agg2 = obs_live.LiveAggregator()
    agg2.ingest(_payload(0, 0, 0, [
        dict({"n": "serve.slo.burn", "k": "g", "v": 0.4}, **fast),
        dict({"n": "serve.slo.alert", "k": "g", "v": 0.0}, **fast),
    ]))
    assert "slo OK burn 0.4x" in agg2.digest(1)
    assert agg2.history_row(1)["slo"] == {"firing": 0, "alerts": 0}
    # no SLO series at all: no token, no history key
    agg3 = obs_live.LiveAggregator()
    agg3.ingest(_payload(0, 0, 0, []))
    assert "slo" not in agg3.digest(1)
    assert "slo" not in agg3.history_row(1)


def test_digest_goodput_token_names_worst_rank_sink():
    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(0, 0, 0, [
        {"n": "goodput.fraction", "k": "g", "v": 0.9},
    ]))
    agg.ingest(_payload(1, 0, 0, [
        {"n": "goodput.fraction", "k": "g", "v": 0.6},
        {"n": "goodput.secs", "k": "g", "v": 30.0,
         "g": {"class": "recovery"}},
        {"n": "goodput.secs", "k": "g", "v": 5.0,
         "g": {"class": "compile"}},
        {"n": "goodput.secs", "k": "g", "v": 60.0,
         "g": {"class": "productive_step"}},
    ]))
    d = agg.digest(2)
    assert "goodput 60%" in d  # the worst rank, not the average
    assert "top sink recovery 30s" in d


def test_metrics_endpoint_render_failure_is_5xx(kv_server):
    kv_server.set_metrics_render(lambda: 1 / 0)
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(
            f"http://127.0.0.1:{kv_server.port}/metrics")
    # visible to scrapers (target unhealthy), but the server survives
    assert exc.value.code == 500
    kv_server.set_metrics_render(lambda: "ok 1\n")
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{kv_server.port}/metrics").read()
    assert body == b"ok 1\n"


def test_metrics_endpoint_read_only_unauthenticated(kv_server):
    url = f"http://127.0.0.1:{kv_server.port}/metrics"
    # no renderer installed -> 404 (plain KV deployments are unchanged)
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(url)
    assert exc.value.code == 404

    agg = obs_live.LiveAggregator()
    agg.ingest(_payload(0, 0, 0, [_counter("a", 1)]))
    kv_server.set_metrics_render(agg.prometheus)
    body = urllib.request.urlopen(url).read().decode()
    _assert_valid_exposition(body)
    assert "hvdtpu_a" in body
    # the KV surface stays HMAC-gated: an unsigned PUT is still refused
    req = urllib.request.Request(
        f"http://127.0.0.1:{kv_server.port}/x/y", data=b"evil",
        method="PUT",
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req)
    assert exc.value.code == 403


# ---------------------------------------------------------------------------
# straggler attribution: controller cycles + elastic waits + reset
# ---------------------------------------------------------------------------


def _request(rank, name="w"):
    from horovod_tpu.runtime.messages import Request, RequestType

    return Request(request_rank=rank, request_type=RequestType.ALLREDUCE,
                   tensor_name=name, dtype="float32", shape=(2,))


def _lists(world, *reqs):
    from horovod_tpu.runtime.messages import RequestList

    out = [RequestList() for _ in range(world)]
    for r in reqs:
        out[r.request_rank].requests.append(r)
    return out


def test_controller_blames_cross_cycle_last_arrival():
    import horovod_tpu.runtime.controller as ctl

    state = ctl.ControllerState(world_size=3)
    ctl.compute_responses(state, _lists(3, _request(0), _request(2)),
                          fusion_threshold_bytes=1 << 20)
    time.sleep(0.005)
    resp, _ = ctl.compute_responses(state, _lists(3, _request(1)),
                                    fusion_threshold_bytes=1 << 20)
    assert len(resp) == 1
    snap = {(m["name"], (m.get("tags") or {}).get("rank")): m
            for m in obs.get_registry().snapshot()}
    assert snap[("engine.straggler.last_arrivals", "1")]["value"] == 1
    hist = snap[("engine.straggler.skew_ms", None)]
    assert hist["count"] == 1 and hist["max"] > 0
    assert snap[("engine.straggler.last_rank", None)]["value"] == 1.0


def test_controller_same_cycle_blames_nobody():
    import horovod_tpu.runtime.controller as ctl

    state = ctl.ControllerState(world_size=2)
    resp, _ = ctl.compute_responses(
        state, _lists(2, _request(0), _request(1)),
        fusion_threshold_bytes=1 << 20,
    )
    assert len(resp) == 1
    names = {m["name"] for m in obs.get_registry().snapshot()}
    assert not any(n.startswith(obs_straggler.PREFIX) for n in names)


def test_controller_alert_threshold_counts_alerts():
    import horovod_tpu.runtime.controller as ctl

    state = ctl.ControllerState(world_size=2)
    ctl.compute_responses(state, _lists(2, _request(0)),
                          fusion_threshold_bytes=1 << 20, alert_skew_ms=0.001)
    time.sleep(0.01)
    ctl.compute_responses(state, _lists(2, _request(1)),
                          fusion_threshold_bytes=1 << 20,
                          alert_skew_ms=0.001)
    snap = {m["name"]: m for m in obs.get_registry().snapshot()}
    assert snap["engine.straggler.alerts"]["value"] == 1
    # below threshold: records but never alerts
    obs.reset_registry()
    obs_straggler.record(1, 10.0, alert_ms=1000.0)
    snap = {m["name"]: m for m in obs.get_registry().snapshot()}
    assert "engine.straggler.alerts" not in snap
    assert snap["engine.straggler.last_arrivals"]["value"] == 1


def test_record_waits_blames_waited_on_peer_only():
    # rank 0 waited 0.5s on rank 2, noise on the others
    blamed = obs_straggler.record_waits(
        {0: 0.0, 1: 0.01, 2: 0.5}, self_rank=0)
    assert blamed == 2
    # a wait under the polling-noise floor blames nobody
    assert obs_straggler.record_waits(
        {0: 0.0, 1: 0.05}, self_rank=0) is None
    # the delayed rank itself (everyone ready when it arrives) is silent
    assert obs_straggler.record_waits(
        {0: 0.01, 1: 0.01}, self_rank=1) is None
    snap = {(m["name"], (m.get("tags") or {}).get("rank")): m
            for m in obs.get_registry().snapshot()}
    assert snap[("engine.straggler.last_arrivals", "2")]["value"] == 1


def test_straggler_reset_clears_instruments():
    obs_straggler.record(1, 100.0)
    obs_straggler.reset()
    names = {m["name"] for m in obs.get_registry().snapshot()}
    assert not any(n.startswith(obs_straggler.PREFIX) for n in names)


def test_elastic_rendezvous_resets_attribution(kv_server):
    import pickle

    from horovod_tpu.elastic.context import ElasticContext

    kv = KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret)
    kv.put("elastic", "world_0", pickle.dumps([0]))
    kv.put("elastic", "epoch", b"0")
    obs_straggler.record(1, 100.0)
    ctx = ElasticContext(0, kv, timeout=10.0)
    ctx.rendezvous()
    names = {m["name"] for m in obs.get_registry().snapshot()}
    assert not any(n.startswith(obs_straggler.PREFIX) for n in names)


def test_elastic_allreduce_attributes_delayed_peer(kv_server):
    """Two in-process 'ranks' over a real KV store; rank 1 carries an
    action=delay fault, so rank 0's wait attribution must name rank 1 —
    deterministic, no wall-clock races (the delay IS the signal)."""
    import pickle

    from horovod_tpu.elastic.context import ElasticContext

    kv = KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret)
    kv.put("elastic", "world_0", pickle.dumps([0, 1]))
    kv.put("elastic", "epoch", b"0")

    c0 = ElasticContext(
        0, KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret),
        timeout=20.0)
    c1 = ElasticContext(
        1, KVStoreClient(f"127.0.0.1:{kv_server.port}", kv_server.secret),
        timeout=20.0)

    def member(ctx, delay):
        ctx.rendezvous()
        if delay:
            time.sleep(delay)  # the straggler (same shape as the fault)
        return ctx.allreduce(np.ones(2), name="g0", average=False)

    out = [None, None]

    def call(i, ctx, delay):
        out[i] = member(ctx, delay)

    threads = [threading.Thread(target=call, args=(0, c0, 0.0)),
               threading.Thread(target=call, args=(1, c1, 0.4))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    np.testing.assert_array_equal(out[0], np.full(2, 2.0))
    snap = {(m["name"], (m.get("tags") or {}).get("rank")): m
            for m in obs.get_registry().snapshot()}
    assert snap[("engine.straggler.last_arrivals", "1")]["value"] == 1
    assert ("engine.straggler.last_arrivals", "0") not in snap


# ---------------------------------------------------------------------------
# summary straggler section
# ---------------------------------------------------------------------------


def _dump_doc(metrics):
    return {"schema": "hvdtpu-metrics-v1", "rank": "0", "metrics": metrics}


def test_summary_straggler_section_names_top_rank():
    obs_straggler.record(1, 480.0)
    obs_straggler.record(1, 520.0)
    obs_straggler.record(0, 30.0)
    doc = _dump_doc(obs.get_registry().snapshot())
    section = obs_summary.straggler_section({"0": doc, "1": doc})
    assert section is not None
    lines = section.splitlines()
    assert lines[0].startswith("rank 1: last to arrive in 2 collectives")
    assert "<- likely straggler" in lines[0]
    assert "rank 0: last to arrive in 1" in lines[1]
    assert "arrival skew: n=3" in section


def test_summary_straggler_section_absent_when_clean():
    assert obs_summary.straggler_section(
        {"0": _dump_doc(obs.get_registry().snapshot())}) is None


# ---------------------------------------------------------------------------
# satellites: delay fault grammar, wait backoff, CLI
# ---------------------------------------------------------------------------


def test_delay_fault_grammar_and_sleep(monkeypatch):
    specs = faults.parse_spec("worker_exit:rank=1:action=delay:250:count=3")
    assert specs[0].action == "delay"
    assert specs[0].delay_ms == 250 and specs[0].count == 3
    assert faults.parse_spec("p:action=delay")[0].delay_ms == 1000
    assert faults.parse_spec("p:action=delay:delay_ms=75")[0].delay_ms == 75
    with pytest.raises(ValueError, match="not key=value"):
        faults.parse_spec("p:action=raise:250")  # bare ms needs delay

    monkeypatch.setenv(faults.SPEC_ENV, "pt:action=delay:200")
    faults.reset()
    t0 = time.monotonic()
    faults.maybe_fail("pt")  # sleeps, then CONTINUES (no raise)
    assert 0.15 < time.monotonic() - t0 < 2.0
    t0 = time.monotonic()
    faults.maybe_fail("pt")  # count exhausted: instant
    assert time.monotonic() - t0 < 0.05


class _FakeTime:
    def __init__(self):
        self.now = 0.0
        self.sleeps = []

    def time(self):
        return self.now

    def sleep(self, secs):
        self.sleeps.append(round(secs, 4))
        self.now += secs


def test_kv_wait_exponential_backoff(monkeypatch):
    clock = _FakeTime()
    monkeypatch.setattr(rdv, "time", clock)
    client = KVStoreClient("127.0.0.1:1", "s")
    monkeypatch.setattr(client, "get", lambda scope, key: None)
    with pytest.raises(TimeoutError):
        client.wait("s", "k", timeout=10.0)
    # doubles from 50 ms, capped at 1 s — not the old fixed 100 ms hammer
    assert clock.sleeps[:6] == [0.05, 0.1, 0.2, 0.4, 0.8, 1.0]
    assert max(clock.sleeps) <= 1.0
    assert len(clock.sleeps) < 20  # fixed 0.1s polling would need 100


def test_cli_live_knobs_map_to_env():
    from horovod_tpu.run.config_parser import set_env_from_args
    from horovod_tpu.run.runner import parse_args

    args = parse_args([
        "-np", "2",
        "--live-stats-secs", "2.5",
        "--live-port", "9999",
        "--live-history-file", "/tmp/h.jsonl",
        "--alert-skew-ms", "250",
        "python", "train.py",
    ])
    env = {}
    set_env_from_args(env, args)
    assert env["HVDTPU_LIVE_STATS_SECS"] == "2.5"
    assert env["HVDTPU_ALERT_SKEW_MS"] == "250.0"
    # launcher-local knobs stay out of the worker env
    assert args.live_port == 9999
    assert args.live_history_file == "/tmp/h.jsonl"
    assert "HVDTPU_LIVE_KV" not in env


# ---------------------------------------------------------------------------
# 2-proc chaos acceptance: delay straggler named live and at job end
# ---------------------------------------------------------------------------


def _delay_chaos_train():
    import numpy as np  # noqa: PLC0415

    import horovod_tpu.elastic as elastic  # noqa: PLC0415

    ctx = elastic.context()
    state = elastic.State(w=np.zeros(2, dtype=np.float64), step=0)

    @elastic.run
    def loop(state):
        while state.step < 6:
            state.w = state.w + ctx.allreduce(
                np.ones(2), name=f"g{state.step}", average=False)
            state.step += 1
            state.commit()
        return state.step

    return loop(state)


@pytest.mark.multiprocess
def test_live_plane_names_delay_straggler_e2e(tmp_path):
    """ISSUE 3 acceptance: a 2-proc elastic job with an injected
    ``action=delay`` straggler on rank 1.  The live history (one row per
    aggregation round, i.e. one reporting interval) must name rank 1
    while the job runs, and the end-of-job dumps must attribute it in
    the straggler section."""
    import horovod_tpu.elastic as elastic

    hist = str(tmp_path / "live_history.jsonl")
    dumps = str(tmp_path / "metrics") + "/"
    env = {
        "JAX_PLATFORMS": "cpu",
        # every allreduce on rank 1 stalls 400 ms before contributing
        "HVDTPU_FAULT_SPEC": "worker_exit:rank=1:action=delay:400:count=6",
        "HVDTPU_METRICS_DUMP": dumps,
    }
    (tmp_path / "metrics").mkdir()
    results, job = elastic.launch(
        _delay_chaos_train, np=2, env=env, timeout=120,
        live_stats_secs=0.2, live_history=hist,
    )
    assert results == {0: 6, 1: 6}
    assert [e[0] for e in job.trace] == ["spawn", "spawn"]

    # live: some aggregation round named the lagging rank
    rows = [json.loads(l) for l in open(hist)]
    assert rows, "no live history rows were appended"
    named = [r["straggler"] for r in rows if r.get("straggler")]
    assert named, f"no round named a straggler: {rows}"
    assert named[-1]["rank"] == 1
    assert named[-1]["worst_skew_ms"] > 200.0

    # job end: the per-rank dumps attribute the same rank
    docs = obs_summary.collect_dumps(dumps)
    assert docs
    section = obs_summary.straggler_section(docs)
    assert section is not None
    assert section.splitlines()[0].startswith("rank 1: last to arrive")
