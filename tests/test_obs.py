"""obs/: span tracer and metrics registry — and their
wiring into the serving engine, the HTTP server, and the node runtime."""

import gzip
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from tensorflowonspark_tpu.obs import registry as obs_registry
from tensorflowonspark_tpu.obs import spans as obs_spans


# -- spans -------------------------------------------------------------


def _read_chrome_trace(path):
    """(complete events, {tid: thread name}, process name) of one
    exported Chrome-trace file."""
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    threads = {
        e["tid"]: e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    (process,) = [
        e["args"]["name"] for e in meta if e["name"] == "process_name"
    ]
    return [e for e in events if e["ph"] == "X"], threads, process


def test_span_nesting_chrome_export_roundtrip(tmp_path):
    """Nested spans export as Chrome-trace complete events on one lane,
    the inner event's interval inside the outer's."""
    tr = obs_spans.SpanTracer(capacity=64)
    with tr.span("outer", phase="x"):
        with tr.span("inner"):
            time.sleep(0.02)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert outer.dur >= inner.dur >= 0.02
    assert outer.ts <= inner.ts  # outer opened first
    assert outer.args == {"phase": "x"}

    path = tr.write_chrome_trace(
        str(tmp_path / "host.trace.json.gz"), process_name="python host"
    )
    events, threads, process = _read_chrome_trace(path)
    assert process == "python host"
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"inner", "outer"} and len(events) == 2
    ev_in, ev_out = by_name["inner"], by_name["outer"]
    # one call stack, one lane, named after the recording thread
    assert (ev_in["pid"], ev_in["tid"]) == (ev_out["pid"], ev_out["tid"])
    assert ev_out["tid"] == threading.get_ident()
    assert threads[ev_out["tid"]] == threading.current_thread().name
    assert ev_out["args"] == {"phase": "x"} and "args" not in ev_in
    # microseconds, and the nesting survives the round trip: a reader
    # that subtracts children from parents gets outer's self time
    assert ev_in["dur"] == pytest.approx(inner.dur * 1e6, abs=0.01)
    assert ev_out["dur"] == pytest.approx(outer.dur * 1e6, abs=0.01)
    assert ev_out["ts"] <= ev_in["ts"]
    assert ev_in["ts"] + ev_in["dur"] <= ev_out["ts"] + ev_out["dur"] + 0.01


def test_span_tracer_thread_safety_and_capacity():
    tr = obs_spans.SpanTracer(capacity=500)

    def work():
        for _ in range(100):
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.recorded == 800
    assert len(tr.spans()) == 500  # ring keeps the newest
    assert tr.summary()["w"]["count"] == 500

    small = obs_spans.SpanTracer(capacity=3)
    for i in range(10):
        small.record("r", 0.001 * (i + 1))
    assert small.recorded == 10 and len(small.spans()) == 3


def test_span_record_and_decorator_summary():
    tr = obs_spans.SpanTracer()
    tr.record("engine.queue", 0.5)
    tr.record("engine.queue", 0.1)

    @tr.traced("engine.fetch")
    def fetch():
        return 42

    assert fetch() == 42
    sm = tr.summary(prefix="engine.")
    assert set(sm) == {"engine.queue", "engine.fetch"}
    assert sm["engine.queue"]["count"] == 2
    assert sm["engine.queue"]["max_ms"] == pytest.approx(500, rel=0.01)
    assert sm["engine.queue"]["p50_ms"] >= 100
    with pytest.raises(ValueError):
        obs_spans.SpanTracer(capacity=0)


def test_record_interval_lands_on_synthetic_lane(tmp_path):
    """A backdated record() interval must never interleave with the
    recording thread's call-stack spans: a queue wait recorded at
    admission time covers the prefill/dispatch spans the scheduler
    thread recorded DURING the wait without nesting them, so on the
    thread's own lane a nesting-aware reader would subtract them from
    it and report negative self time."""
    tr = obs_spans.SpanTracer(capacity=64)
    t_wait0 = time.perf_counter()
    # real call-stack work on this thread during the "wait"
    with tr.span("engine.prefill"):
        time.sleep(0.03)
    with tr.span("engine.dispatch"):
        time.sleep(0.03)
    # the externally-measured wait, stamped only now — its interval
    # covers both spans above
    tr.record("engine.queue", time.perf_counter() - t_wait0)

    by_span = {s.name: s for s in tr.spans()}
    assert by_span["engine.prefill"].tid == threading.get_ident()
    assert by_span["engine.queue"].tid == "interval:engine.queue"
    assert by_span["engine.queue"].thread_name == "intervals: engine.queue"

    path = tr.write_chrome_trace(str(tmp_path / "host.trace.json.gz"), "host")
    events, threads, _process = _read_chrome_trace(path)
    by = {e["name"]: e for e in events}
    assert len(events) == 3
    queue, prefill, dispatch = (
        by["engine.queue"], by["engine.prefill"], by["engine.dispatch"]
    )
    assert queue["tid"] == "interval:engine.queue"
    assert threads[queue["tid"]] == "intervals: engine.queue"
    assert prefill["tid"] == dispatch["tid"] == threading.get_ident()
    # in time the interval covers both call-stack spans ...
    assert queue["ts"] <= prefill["ts"]
    assert dispatch["ts"] + dispatch["dur"] <= queue["ts"] + queue["dur"] + 1
    # ... and on its own lane it overlaps none of them: nothing else
    # shares the lane, so every event keeps its FULL duration
    assert [e["name"] for e in events if e["tid"] == queue["tid"]] == [
        "engine.queue"
    ]
    for name, ev in by.items():
        assert ev["dur"] == pytest.approx(by_span[name].dur * 1e6, abs=0.01)
    # the call-stack lane itself stays properly nested (here: disjoint)
    assert prefill["ts"] + prefill["dur"] <= dispatch["ts"] + 0.01


# -- registry ----------------------------------------------------------


def test_registry_prometheus_text_golden():
    r = obs_registry.Registry()
    c = r.counter("requests_total", "reqs")
    c.inc()
    c.inc(2, route="/a")
    r.gauge("depth", "queue depth").set(3)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    assert r.render() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 3\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 2\n'
        "lat_seconds_sum 0.55\n"
        "lat_seconds_count 2\n"
        "# HELP requests_total reqs\n"
        "# TYPE requests_total counter\n"
        "requests_total 1\n"
        'requests_total{route="/a"} 2\n'
    )


def test_registry_validation_and_collectors():
    r = obs_registry.Registry()
    r.counter("x_total")
    with pytest.raises(ValueError):
        r.gauge("x_total")  # type conflict
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        r.counter("c_total").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        r.counter("l_total").inc(1, **{"bad-label": "v"})
    assert obs_registry.sanitize_name("loss/train.v2") == "loss_train_v2"
    assert obs_registry.sanitize_name("0step") == "_0step"

    g = r.gauge("sampled")
    r.add_collector(lambda: g.set(7))
    assert "sampled 7" in r.render()
    # a broken collector must not take down the scrape
    r.add_collector(lambda: 1 / 0)
    assert "sampled 7" in r.render()


def test_metrics_writer_is_registry_sink(tmp_path):
    from tensorflowonspark_tpu.utils.metrics import MetricsWriter

    reg = obs_registry.Registry()
    with MetricsWriter(
        str(tmp_path), use_tensorboard=False, registry=reg
    ) as w:
        # push side mirrors into the registry (sanitized name)...
        w.scalar("loss/train", 1.5, step=1)
        assert reg.gauge("loss_train").value() == 1.5
        # ...and the registry publishes into the writer (the sink)
        reg.counter("tokens_total", "t").inc(5)
        reg.histogram("lat_seconds", buckets=(1.0,)).observe(0.25)
        reg.publish(w, step=2)
    rows = [
        json.loads(line) for line in open(tmp_path / "metrics.jsonl")
    ]
    by_name = {(r["name"], r["step"]): r["value"] for r in rows}
    assert by_name[("loss/train", 1)] == 1.5
    assert by_name[("tokens_total", 2)] == 5
    assert by_name[("lat_seconds_count", 2)] == 1
    assert by_name[("lat_seconds_sum", 2)] == 0.25
    # publish used mirror=False: no gauge echo of registry-born series
    names = [m.name for m in reg.metrics()]
    assert names == ["lat_seconds", "loss_train", "tokens_total"]


# -- engine + HTTP wiring ---------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
    model = Llama(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, model, params


def test_engine_stats_phase_percentiles_and_metrics(tiny):
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    cfg, model, params = tiny
    eng = ContinuousBatcher(model, params, slots=2, prompt_widths=(8,))
    try:
        eng.submit([1, 2, 3], 4)
        eng.submit([5], 3)
        stats = eng.stats()
        phases = stats["phase_ms"]
        # every scheduler phase a plain request crosses is measured
        for phase in ("queue", "prefill", "dispatch", "fetch"):
            assert phase in phases, phases
            assert phases[phase]["count"] >= 1
            assert phases[phase]["p50_ms"] >= 0
            assert (
                phases[phase]["p99_ms"] >= phases[phase]["p50_ms"]
            )
        text = eng.metrics.render()
        assert "engine_requests_total 2" in text
        assert "engine_requests_completed_total 2" in text
        assert "engine_tokens_emitted_total 7" in text
        assert 'engine_request_phase_seconds_bucket{phase="fetch",le="+Inf"}' in text
        assert "engine_ttft_seconds_count 2" in text
        # render-time collectors: all slots free after completion
        assert "engine_slots_busy 0" in text
        assert "engine_slots 2" in text
    finally:
        eng.close()


def test_engine_warmup_pin_leaves_decode_block_alone(tiny):
    """Warmup compiles the k=1 program through a pinned request instead
    of mutating the shared decode_block (ADVICE.md #3): /stats must
    never transiently report k=1."""
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    cfg, model, params = tiny
    eng = ContinuousBatcher(
        model, params, slots=2, prompt_widths=(8,), decode_block=4
    )
    seen: list[int] = []
    orig = eng._block_fn

    def spying(k):
        seen.append(k)
        return orig(k)

    eng._block_fn = spying
    try:
        eng.warmup()
        assert eng._decode_block == 4  # never mutated
        assert eng.stats()["decode_block"] == 4
        # the pinned request actually ran single-step, and normal
        # traffic still uses the full block
        assert 1 in seen and 4 in seen
        out = eng.submit([1, 2], 5)
        assert len(out) == 5
    finally:
        eng.close()


def _patch_param_loader(monkeypatch, tiny):
    """Route serve_model's checkpoint restore to in-process params (the
    orbax round-trip is covered elsewhere; these tests target the HTTP
    observability surfaces)."""
    from tensorflowonspark_tpu.tools import generate_text

    _cfg, _model, params = tiny
    monkeypatch.setattr(
        generate_text,
        "_load_params",
        lambda checkpoint, cfg, lora_scale=None: params,
    )


def test_serve_model_metrics_endpoint_end_to_end(tiny, monkeypatch):
    """The acceptance path: a live continuous-engine server answers
    /metrics in Prometheus text format and /stats with span-backed
    per-phase percentiles after real traffic."""
    from tensorflowonspark_tpu.tools import serve_model

    _patch_param_loader(monkeypatch, tiny)
    server = serve_model.make_server(
        None,
        port=0,
        gen=dict(
            checkpoint="unused",
            model="tiny",
            config_overrides='{"remat": false, "dtype": "float32"}',
            width=8,
            batch_size=2,
            max_new_tokens=4,
            engine="continuous",
        ),
    )
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        body = json.dumps({"prompts": [[1, 2, 3]]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            assert len(json.load(resp)["completions"][0]) == 4

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert "# TYPE engine_requests_total counter" in text
        assert "engine_requests_total 1" in text
        assert "engine_tokens_emitted_total 4" in text
        assert "# TYPE engine_request_phase_seconds histogram" in text

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=30
        ) as resp:
            stats = json.load(resp)
        assert stats["mode"] == "continuous"
        for phase in ("queue", "prefill", "dispatch", "fetch"):
            assert stats["phase_ms"][phase]["count"] >= 1
    finally:
        server.shutdown()


def test_build_engine_decode_block_zero_passes_through(tiny, monkeypatch):
    """An explicit decode_block=0 reaches the engine's own max(1, ...)
    clamp instead of being silently mapped to 8 (ADVICE.md #1)."""
    from tensorflowonspark_tpu.tools.serve_model import _build_engine

    _patch_param_loader(monkeypatch, tiny)
    gen = dict(
        checkpoint="unused",
        model="tiny",
        config_overrides='{"remat": false, "dtype": "float32"}',
        width=8,
        max_new_tokens=4,
    )
    eng, _, _, _ = _build_engine(dict(gen, decode_block=0))
    try:
        assert eng._decode_block == 1
    finally:
        eng.close()
    eng, _, _, _ = _build_engine(gen)  # unset -> the default
    try:
        assert eng._decode_block == 8
    finally:
        eng.close()


def test_node_metrics_server_serves_registry():
    from tensorflowonspark_tpu.cluster import node as tf_node

    obs_registry.default_registry().counter(
        "node_test_events_total", "test counter"
    ).inc(3)
    port = tf_node._maybe_start_metrics_server("127.0.0.1")
    assert port
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert "node_test_events_total 3" in text
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=10
        )
