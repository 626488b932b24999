"""Cluster-wide observability plane (obs/cluster.py, obs/flightrec.py,
obs/trace_merge.py) — units plus the acceptance e2e: a 2-node cluster
whose driver aggregates both nodes' metrics, whose driver+node traces
merge into one clock-aligned timeline sharing a trace_id, and whose
SIGKILLed node still leaves a flight-recorder dump with its final
spans."""

import json
import os
import signal
import threading
import time
import urllib.request

import pytest

from tensorflowonspark_tpu.obs import cluster as obs_cluster
from tensorflowonspark_tpu.obs import flightrec
from tensorflowonspark_tpu.obs import registry as obs_registry
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.obs import trace_merge

from tensorflowonspark_tpu.utils.util import cpu_only_env

NODE_ENV = cpu_only_env()


@pytest.fixture(autouse=True)
def _fresh_trace_context():
    """Each test gets a clean process-global trace context (other
    suites' cluster runs leave one behind)."""
    obs_cluster._reset_for_tests()
    yield
    obs_cluster._reset_for_tests()


# -- trace context + clock sync ---------------------------------------


def test_clock_sync_keeps_min_rtt_sample():
    obs_cluster.note_clock_sync(0.5, rtt_s=0.10)
    obs_cluster.note_clock_sync(9.9, rtt_s=0.30)  # worse bound: ignored
    obs_cluster.note_clock_sync(0.48, rtt_s=0.01)  # tighter: wins
    assert obs_cluster.clock_sync() == {"offset_s": 0.48, "rtt_s": 0.01}
    # gauge mirror (last sample, not the min — it's a live signal)
    g = obs_registry.default_registry().gauge("node_clock_offset_seconds")
    assert g.value() == 0.48


def test_export_carries_trace_context_metadata():
    obs_cluster.set_trace_context("run-abc", node="node3")
    obs_cluster.note_clock_sync(1.25, 0.004)
    tr = obs_spans.SpanTracer()
    with tr.span("x"):
        pass
    ctx = trace_merge.trace_context_of(tr.export()["traceEvents"])
    assert ctx["trace_id"] == "run-abc"
    assert ctx["node"] == "node3"
    assert ctx["clock_offset_s"] == 1.25
    # epoch_unix maps the tracer's monotonic epoch onto the wall clock
    assert abs(ctx["epoch_unix"] - time.time()) < 60


# -- prometheus text parsing ------------------------------------------


def test_parse_prometheus_text_round_trip():
    r = obs_registry.Registry()
    r.counter("req_total", "x").inc(3, route="/a", q='he said "hi"\n')
    r.gauge("depth").set(2.5)
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, phase="fetch")
    fams = obs_cluster.parse_prometheus_text(r.render())
    assert fams["req_total"]["type"] == "counter"
    ((name, labels),) = [
        k for k in fams["req_total"]["samples"] if k[1]
    ]
    # escaped label values survive the round trip exactly
    assert dict(labels) == {"route": "/a", "q": 'he said "hi"\n'}
    assert fams["depth"]["samples"][("depth", ())] == 2.5
    # histogram samples group under the base family via the TYPE line
    hist = fams["lat_seconds"]["samples"]
    key = ("lat_seconds_bucket", (("le", "+Inf"), ("phase", "fetch")))
    assert hist[key] == 1.0
    assert hist[("lat_seconds_count", (("phase", "fetch"),))] == 1.0


def test_parse_prometheus_text_rejects_malformed():
    with pytest.raises(ValueError, match="malformed sample"):
        obs_cluster.parse_prometheus_text("not a metric line at all{")
    with pytest.raises(ValueError, match="duplicate sample"):
        obs_cluster.parse_prometheus_text("a_total 1\na_total 2\n")
    with pytest.raises(ValueError, match="non-numeric"):
        obs_cluster.parse_prometheus_text("a_total NaNana\n")


# -- registry window() -------------------------------------------------


def test_registry_window_deltas():
    r = obs_registry.Registry()
    c = r.counter("ticks_total")
    h = r.histogram("wait_seconds", buckets=(1.0,))
    c.inc(5)
    h.observe(0.5)
    w1 = r.window()
    assert w1["ticks_total"]["series"][""] == {"value": 5.0, "delta": 5.0}
    assert w1["wait_seconds"]["series"][""] == {
        "count": 1, "sum": 0.5, "delta_count": 1, "delta_sum": 0.5,
        "le": [1.0], "buckets": [1], "delta_buckets": [1],
    }
    c.inc(2)
    h.observe(0.25)
    h.observe(0.25)
    w2 = r.window()
    assert w2["ticks_total"]["series"][""] == {"value": 7.0, "delta": 2.0}
    assert w2["wait_seconds"]["series"][""]["delta_count"] == 2
    assert w2["wait_seconds"]["series"][""]["delta_sum"] == pytest.approx(0.5)
    assert w2["wait_seconds"]["series"][""]["delta_buckets"] == [2]
    # quiet window: zero deltas
    assert r.window()["ticks_total"]["series"][""]["delta"] == 0.0


# -- aggregator --------------------------------------------------------


def _serve_registry(reg):
    server, port = obs_cluster.serve_text(reg.render, host="127.0.0.1")
    assert port
    return server, f"http://127.0.0.1:{port}/metrics"


def test_aggregator_merges_per_node_sum_max_and_render():
    r0, r1, rd = (obs_registry.Registry() for _ in range(3))
    r0.counter("frames_total").inc(10)
    r1.counter("frames_total").inc(32)
    r0.gauge("depth").set(1, q="in")
    r1.gauge("depth").set(4, q="in")
    s0, u0 = _serve_registry(r0)
    s1, u1 = _serve_registry(r1)
    try:
        agg = obs_cluster.MetricsAggregator(
            lambda: {0: u0, 1: u1}, registry=rd
        )
        stats = agg.cluster_stats()
        assert stats["nodes"][0]["ok"] and stats["nodes"][1]["ok"]
        assert stats["nodes"]["driver"]["ok"]
        fr = stats["series"]["frames_total"]
        assert fr["type"] == "counter"
        assert fr["per_node"][0][""] == 10.0
        assert fr["per_node"][1][""] == 32.0
        assert fr["sum"][""] == 42.0 and fr["max"][""] == 32.0
        dp = stats["series"]["depth"]
        assert dp["sum"]['q="in"'] == 5.0 and dp["max"]['q="in"'] == 4.0
        # the aggregator's own cost is in the driver registry it shares
        assert stats["series"]["cluster_scrape_total"]["per_node"][
            "driver"
        ][""] >= 1

        # merged re-exposition: ONE TYPE line per family, node labels,
        # and it parses back clean (promtool-shaped)
        text = agg.render()
        assert text.count("# TYPE frames_total counter") == 1
        assert 'frames_total{node="0"} 10' in text
        assert 'frames_total{node="1"} 32' in text
        reparsed = obs_cluster.parse_prometheus_text(text)
        assert (
            reparsed["frames_total"]["samples"][
                ("frames_total", (("node", "1"),))
            ]
            == 32.0
        )
    finally:
        s0.shutdown()
        s1.shutdown()


def test_aggregator_survives_dead_target_and_background_loop():
    r0 = obs_registry.Registry()
    r0.counter("ok_total").inc()
    s0, u0 = _serve_registry(r0)
    try:
        agg = obs_cluster.MetricsAggregator(
            lambda: {0: u0, 1: "http://127.0.0.1:1/metrics"},  # dead
            interval=0.3,
            timeout=1.0,
            registry=obs_registry.Registry(),
        )
        agg.start()
        deadline = time.monotonic() + 10
        while not agg.last_scrape() and time.monotonic() < deadline:
            time.sleep(0.05)
        agg.stop()
        stats = agg.cluster_stats(fresh=False)
        assert stats["nodes"][0]["ok"]
        assert not stats["nodes"][1]["ok"] and stats["nodes"][1]["error"]
        assert stats["series"]["ok_total"]["sum"][""] == 1.0
        assert agg.total_scrape_s > 0.0
    finally:
        s0.shutdown()


# -- flight recorder ---------------------------------------------------


def test_flightrec_dump_atomic_bounded_and_readable(tmp_path):
    tr = obs_spans.SpanTracer(capacity=16)
    reg = obs_registry.Registry()
    reg.counter("evts_total").inc(3)
    obs_cluster.set_trace_context("run-x", node="node0")
    rec = flightrec.FlightRecorder(
        str(tmp_path / "flightrec-node0.json"),
        process="node0",
        tracer=tr,
        registry=reg,
        events_capacity=4,
    )
    for i in range(10):
        rec.note("tick", i=i)
    with tr.span("work.tick"):
        pass
    path = rec.dump("unit")
    dump = json.loads(open(path).read())
    assert dump["reason"] == "unit"
    assert dump["process"] == "node0"
    assert dump["trace_context"]["trace_id"] == "run-x"
    # bounded events keep the NEWEST
    assert [e["i"] for e in dump["events"]] == [6, 7, 8, 9]
    assert "evts_total 3" in dump["metrics"]
    names = [
        e["name"]
        for e in dump["spans"]["traceEvents"]
        if e.get("ph") == "X"
    ]
    assert "work.tick" in names
    # the dump's spans are loadable Chrome JSON: the merge tool reads
    # the dump as a trace file
    assert trace_merge.load_trace(path) == dump["spans"]
    # and no torn tmp file is left behind
    assert os.listdir(tmp_path) == ["flightrec-node0.json"]


def test_flightrec_module_level_and_periodic(tmp_path, monkeypatch):
    # a process with no recorder yet: an earlier test of this worker may
    # have installed one (there is no uninstall); it returns afterwards
    monkeypatch.setattr(flightrec, "_recorder", None)
    assert flightrec.dump_now("nobody-home") is None  # no-op pre-install
    flightrec.note("ignored")
    rec = flightrec.install(
        str(tmp_path / "flightrec-p.json"),
        process="p",
        tracer=obs_spans.SpanTracer(),
        registry=obs_registry.Registry(),
        interval=0.2,
    )
    flightrec.note("boom", detail="x")
    rec.start()
    deadline = time.monotonic() + 10
    while not os.path.exists(rec.path) and time.monotonic() < deadline:
        time.sleep(0.05)
    rec.stop()
    dump = json.loads(open(rec.path).read())
    assert dump["reason"] == "periodic"
    assert any(e["kind"] == "boom" for e in dump["events"])
    # explicit dump overwrites with its reason
    assert flightrec.dump_now("engine_watchdog") == rec.path
    assert json.loads(open(rec.path).read())["reason"] == "engine_watchdog"


# -- trace merge -------------------------------------------------------


def _export_with_ctx(tmp_path, name, node, offset, spans_spec):
    """Write one trace file for `node` whose clock is `offset` seconds
    behind the driver (trace_merge must add it back)."""
    obs_cluster._reset_for_tests()
    obs_cluster.set_trace_context("run-m", node=node)
    if offset:
        obs_cluster.note_clock_sync(offset, 0.002)
    tr = obs_spans.SpanTracer()
    for sname, args in spans_spec:
        with tr.span(sname, **args):
            time.sleep(0.002)
    path = str(tmp_path / name)
    tr.write_chrome_trace(path, process_name=f"{node} host")
    return path


def test_trace_merge_aligns_offsets_and_links_frames(tmp_path):
    driver = _export_with_ctx(
        tmp_path,
        "driver.trace.json",
        "driver",
        0.0,
        [("feed.send", {"stream": "s1", "seq": 0})],
    )
    # node clock reads 100s in the past; its offset estimate says +100
    node = _export_with_ctx(
        tmp_path,
        "node0.trace.json",
        "node0",
        100.0,
        [("feed.queue_get", {"stream": "s1", "seq": 0})],
    )
    # fake the skew: shift the node file's epoch back by its offset
    data = json.load(open(node))
    for e in data["traceEvents"]:
        if e.get("name") == "trace_context":
            e["args"]["epoch_unix"] -= 100.0
    json.dump(data, open(node, "w"))

    merged = trace_merge.merge_traces([driver, node])
    meta = merged["metadata"]
    assert meta["trace_ids"] == ["run-m"]
    assert {s["node"] for s in meta["sources"]} == {"driver", "node0"}
    assert all(s["aligned"] for s in meta["sources"])
    ev = {
        e["name"]: e
        for e in merged["traceEvents"]
        if e.get("ph") == "X"
    }
    send, get = ev["feed.send"], ev["feed.queue_get"]
    # clock-aligned: both events happened within the same real second,
    # so after offset correction they sit within ~1s on the merged
    # timeline (without the correction they'd be 100s apart)
    assert abs(send["ts"] - get["ts"]) < 2e6
    # distinct lanes (pid remap) with node-prefixed names
    assert send["pid"] != get["pid"]
    names = {
        (e.get("args") or {}).get("name")
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert {"driver: driver host", "node0: node0 host"} <= names
    # frame flow link driver->node
    flows = [e for e in merged["traceEvents"] if e.get("ph") in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert all(e["name"] == "frame s1/0" for e in flows)

    # CLI writes the merged file
    out = tmp_path / "merged.json"
    assert trace_merge.main([driver, node, "-o", str(out)]) == 0
    assert json.load(open(out))["metadata"]["trace_ids"] == ["run-m"]


# -- engine watchdog dump ---------------------------------------------


def test_engine_watchdog_fire_dumps_flight_record(tmp_path):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    flightrec.install(
        str(tmp_path / "flightrec-serve.json"),
        process="serve",
        tracer=obs_spans.SpanTracer(),
        registry=obs_registry.Registry(),
    )
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), watchdog_s=60.0
    )
    try:
        eng._watchdog_fire(61.0)
        dump = json.loads(open(tmp_path / "flightrec-serve.json").read())
        assert dump["reason"] == "engine_watchdog"
        assert any(
            e["kind"] == "engine_watchdog" and e["stuck_for"] == 61.0
            for e in dump["events"]
        )
    finally:
        eng.close()
        flightrec.install(str(tmp_path / "other.json"))


# -- acceptance e2e ----------------------------------------------------


@pytest.mark.e2e
def test_cluster_stats_and_merged_timeline_e2e(tmp_path):
    """The acceptance path: 2-node fed train loop; (a) cluster_stats()
    has per-node AND summed series scraped from both nodes, (b) the
    merged timeline holds one stream's driver-side and node-side spans
    under one trace_id, clock-aligned within the heartbeat RTT bound."""
    import numpy as np

    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tests import cluster_fns

    rng = np.random.default_rng(0)
    x = rng.normal(size=256).astype("float32")
    y = 3.0 * x + 1.5
    records = list(zip(x.tolist(), y.tolist()))
    partitions = [records[i::4] for i in range(4)]

    cluster = tfcluster.run(
        cluster_fns.obs_train_fn,
        {"out_dir": str(tmp_path)},
        num_executors=2,
        input_mode=InputMode.SPARK,
        reservation_timeout=180,
        heartbeat_interval=0.5,
        flightrec_dir=str(tmp_path / "logs"),
        env=NODE_ENV,
    )
    try:
        trace_id = cluster.cluster_meta["trace_id"]
        cluster.train(partitions, close_feed=True)

        # (a) driver-side aggregation saw BOTH nodes
        stats = cluster.cluster_stats()
        assert stats["nodes"][0]["ok"] and stats["nodes"][1]["ok"]
        frames = stats["series"]["feed_columnar_frames_total"]
        per_node = frames["per_node"]
        assert all(
            any(v > 0 for v in per_node.get(eid, {}).values())
            for eid in (0, 1)
        ), per_node
        lbl = next(iter(frames["sum"]))
        assert frames["sum"][lbl] >= frames["max"][lbl] > 0
        # liveness satellite: heartbeat ages for both executors, via
        # the aggregator's view of the driver registry
        ages = stats["series"]["node_heartbeat_age_seconds"]["per_node"][
            "driver"
        ]
        assert {'node="0"', 'node="1"'} <= set(ages)
        assert all(v < 30 for v in ages.values())
        # one scrapable driver endpoint with node-labelled samples
        with urllib.request.urlopen(
            cluster.driver_metrics_url(), timeout=30
        ) as resp:
            text = resp.read().decode()
        assert 'feed_columnar_frames_total{node="0"' in text
        obs_cluster.parse_prometheus_text(text)  # valid exposition
    finally:
        cluster.shutdown(timeout=180)

    # (b) merged timeline: driver + both node traces, one trace id
    driver_trace = str(tmp_path / "driver.trace.json")
    obs_spans.get_tracer().write_chrome_trace(driver_trace, "driver host")
    node_traces = [str(tmp_path / f"node{i}.trace.json") for i in (0, 1)]
    assert all(os.path.exists(p) for p in node_traces)
    merged = trace_merge.merge_traces([driver_trace, *node_traces])
    meta = merged["metadata"]
    assert meta["trace_ids"] == [trace_id]
    assert all(s["aligned"] for s in meta["sources"])
    by_src = {s["node"]: s for s in meta["sources"]}
    rtt_bound = max(
        float(by_src[f"node{i}"]["clock_rtt_s"] or 0) for i in (0, 1)
    )
    # every stream that reached a node: its driver-side send spans and
    # node-side queue_get spans coexist, and a receive never COMPLETES
    # (beyond clock error) before the first send of that stream began.
    # Completion (ts + dur), not span start: the queue_get span opens
    # when the consumer starts WAITING, which on a fast-starting node
    # can be well before the driver's first send — pure scheduling
    # luck, not a causality violation.
    sends: dict = {}
    gets: dict = {}
    for e in merged["traceEvents"]:
        args = e.get("args") or {}
        if e.get("ph") != "X" or args.get("stream") is None:
            continue
        key = (args["stream"], args.get("seq"))
        if e["name"] == "feed.send":
            sends.setdefault(key, []).append(e["ts"])
        elif e["name"] == "feed.queue_get":
            gets.setdefault(key, []).append(e["ts"] + e.get("dur", 0))
    linked = set(sends) & {k for k in gets if k[1] is not None}
    assert linked, (list(sends)[:5], list(gets)[:5])
    slack_us = (rtt_bound + 0.25) * 1e6
    for key in linked:
        assert min(gets[key]) >= min(sends[key]) - slack_us, (
            key, min(gets[key]), min(sends[key]), slack_us,
        )
    # the per-frame flow links made it into the merged timeline
    assert any(e.get("cat") == "feed_frame" for e in merged["traceEvents"])

    # both nodes trained on the fed stream
    for i in (0, 1):
        out = json.load(open(tmp_path / f"node{i}.json"))
        assert out["steps"] > 0


@pytest.mark.slow
@pytest.mark.e2e
def test_sigkill_leaves_flight_recorder_dump(tmp_path):
    """Acceptance (c): SIGKILLing a node leaves logs/flightrec-node1
    .json on disk containing that node's final spans — the rolling
    snapshot wrote it while the process was alive; the kill never got
    a chance to."""
    from tensorflowonspark_tpu.cluster import tfcluster
    from tensorflowonspark_tpu.cluster.tfcluster import InputMode
    from tests import cluster_fns

    fr_dir = tmp_path / "logs"
    cluster = tfcluster.run(
        cluster_fns.busy_span_fn,
        {"sleep": 120},
        num_executors=2,
        input_mode=InputMode.TENSORFLOW,
        reservation_timeout=120,
        heartbeat_interval=0.5,
        heartbeat_grace=3.0,
        flightrec_dir=str(fr_dir),
        env=NODE_ENV,
    )
    try:
        dump_path = fr_dir / "flightrec-node1.json"
        # let the victim record spans and roll at least one snapshot
        deadline = time.monotonic() + 30
        while not dump_path.exists() and time.monotonic() < deadline:
            time.sleep(0.2)
        assert dump_path.exists(), "no rolling snapshot before the kill"
        pid = next(
            n["pid"] for n in cluster.cluster_info if n["executor_id"] == 1
        )
        os.kill(pid, signal.SIGKILL)
        t0 = time.monotonic()
        while not cluster.dead_nodes():
            assert time.monotonic() - t0 < 20, "dead_nodes never flipped"
            time.sleep(0.2)
        # the dump survives the death and carries the node's last spans
        dump = json.loads(open(dump_path).read())
        assert dump["process"] == "node1"
        names = {
            e["name"]
            for e in dump["spans"]["traceEvents"]
            if e.get("ph") == "X"
        }
        assert "work.tick" in names
        assert dump["trace_context"]["trace_id"] == (
            cluster.cluster_meta["trace_id"]
        )
        # satellite: the death transition reached the driver registry,
        # and the driver dropped its own postmortem dump
        assert (
            obs_registry.default_registry()
            .counter("cluster_dead_nodes_total")
            .value()
            >= 1
        )
        assert (fr_dir / "flightrec-driver.json").exists()
        driver_dump = json.loads(
            open(fr_dir / "flightrec-driver.json").read()
        )
        assert driver_dump["reason"] == "dead_node"
    finally:
        cluster.launcher.terminate()
        cluster.server.stop()
