"""Benchmark entry: prints ONE JSON line with the headline metric.

Headline: **training MFU of a 1B-param Llama decoder** on the local
chip(s). ``vs_baseline`` is measured MFU / the 40% target. The
model/mesh/timing code is shared with ``benchmarks/real_chip.py`` (one
implementation, one timing barrier); the per-chip peak comes from
``benchmarks/peaks.py`` by ``device_kind``.

Secondary fields in the same line: MNIST CNN examples/sec end-to-end
through the framework's own data plane (producer -> manager queue ->
DataFeed -> DevicePrefetcher -> jit step), i.e. the "MNIST
InputMode.SPARK" config.

A benchmark number comes only from the chip: unless the platform JAX
picked is ``tpu`` the run exits non-zero and says why.
``BENCH_ALLOW_CPU=1`` lets the flow be rehearsed on the CPU (with
``BENCH_SMOKE=1`` for the tiny model); no utilisation is computed then.
Every emitted line names ``backend``, ``device_kind`` and ``chips``.

A watchdog prints whatever has been measured so far (plus an error
marker) and exits if the run does not finish — a partial line beats
silence.

``--trace`` (DEFAULT ON for real-chip runs): after the timed llama
loop, a few extra steps run under ``jax.profiler.trace`` and the trace
is distilled into ``benchmarks/results/*_trace_report.json`` via
``tensorflowonspark_tpu.obs.trace_report`` — per-lane self-time plus
the MXU/vector/copy/infeed/host attribution table — so every scored
run commits the evidence for its own MFU number. A trace that was asked
for and cannot be reduced fails the run. On CPU backends ``--trace``
is a no-op warning (no MXU to attribute; set ``BENCH_TRACE_CPU=1`` to
force a host-lane capture anyway).
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

WATCHDOG_SECS = 510  # print a partial JSON line rather than be killed mute
MFU_TARGET = 0.40  # acceptance threshold for the headline

_result_printed = threading.Event()
_partial: dict = {}  # results land here as they finish, for the watchdog
_device: dict = {}  # backend / device_kind / chips, set once by main()


def _emit(fields: dict) -> None:
    """Print one result line; every line names the device it ran on."""
    print(json.dumps({**fields, **_device}), flush=True)
    _result_printed.set()


def _results_dir() -> str:
    """Destination for evidence artifacts. The committed baselines in
    benchmarks/results/ are scored on a quiet single-chip host; any run
    that is NOT a deliberate regeneration (the pytest e2e smoke tests
    in particular) must set TFOS_BENCH_RESULTS_DIR to a scratch dir so
    a contended-host run can never overwrite the committed evidence."""
    return os.environ.get("TFOS_BENCH_RESULTS_DIR") or os.path.join(
        "benchmarks", "results"
    )


def _watchdog():
    if not _result_printed.wait(WATCHDOG_SECS):
        _emit(
            {
                "metric": "llama1b_train_mfu",
                "value": _partial.get("mfu_pct", 0),
                "unit": "%",
                "vs_baseline": round(
                    _partial.get("mfu_pct", 0) / (MFU_TARGET * 100), 3
                ),
                "error": f"watchdog: incomplete after {WATCHDOG_SECS}s "
                "(backend init or a compile did not return)",
                **{k: v for k, v in _partial.items() if k != "mfu_pct"},
            }
        )
        os._exit(2)


def _bench_llama(steps: int = 10, smoke: bool = False) -> None:
    """1B Llama train step (shared impl: benchmarks/real_chip.py)."""
    import jax

    from benchmarks import peaks, real_chip

    # remat off: the 1B state+activations fit a single 16 GB chip
    # (15.8 GB peak in the compiled step), and skipping the recompute
    # saves a forward pass. bf16 Adam moments free 3.8 GB of HBM, which
    # is what makes no-remat fit (see compute/optim.py); what either is
    # worth in MFU is not measured on this installation.
    ns = argparse.Namespace(
        steps=2 if smoke else steps,
        # the batch must shard over the fsdp mesh axis: 8 works for the
        # device counts this runs on (1 real chip; 1/2/4/8 virtual CPU
        # devices in CI) — a forced mesh wider than 8 would need more
        batch_size=8,
        seq=64 if smoke else 1024,
        attention="auto", remat="none",
        precision="fp32", moments="bf16",
        # BENCH_SMOKE: tiny decoder so the FULL flow (sharded step,
        # timing barriers, JSON assembly) runs on CPU in seconds —
        # exercised by tests/test_bench_smoke.py so the one
        # driver-scored artifact has CI coverage
        model_scale="tiny" if smoke else "1b",
    )
    if smoke:
        _partial["smoke"] = True
    res = real_chip.bench_llama1b(ns)
    n_chips = len(jax.devices())
    step_time = res["dt"] / ns.steps
    tflops_per_chip = res["flops_fallback"] / step_time / n_chips / 1e12
    _partial.update(
        step_time_ms=round(step_time * 1e3, 1),
        tokens_per_sec_per_chip=round(res["tokens"] / step_time / n_chips),
        n_params=res["n_params"],
        final_loss=round(res["loss"], 4),
        model_tflops_per_sec_per_chip=round(tflops_per_chip, 1),
    )
    if jax.default_backend() == "tpu" and not smoke:
        # never under the headline metric name: a tiny smoke model's
        # near-zero MFU must not look like a scored llama1b result.
        # A TPU kind without a published peak is an error, not a default.
        peak = peaks.peak_for(jax.devices()[0]).bf16_tflops
        _partial["mfu_pct"] = tflops_per_chip / peak * 100


def _bench_zero_ab(smoke: bool, legs: list) -> None:
    """``--zero``: the cross-replica sharded weight update A/B.

    Runs the llama train bench at a FIXED batch on a pure
    data-parallel mesh (``mesh_axis='data'`` — replicated params, the
    regime where the pre-ZeRO optimizer update is computed redundantly
    on every replica) once per requested ``zero_sharding`` setting, and
    commits one artifact with step time, MFU (TPU only), and the
    isolated optimizer-span ms per leg. A smoke run additionally runs
    the byte-identity gate (``tests/test_bench_smoke.py``): the
    weight-update decomposition on identical gradients must be
    byte-exact (``update_params_match`` — elementwise math, only
    placement changes), while the full train legs' digests are reported
    beside it (they may differ by gradient-reduction summation order,
    ~1 ulp). Artifact:
    ``benchmarks/results/zero_weight_update.json`` (``_<backend>_smoke``
    suffixed for smoke runs so CI can never clobber chip evidence).
    """
    import jax

    from benchmarks import peaks, real_chip

    results: dict = {}
    for leg in legs:
        ns = argparse.Namespace(
            steps=4 if smoke else 10,
            batch_size=8,
            seq=64 if smoke else 1024,
            attention="auto",
            remat="none",
            precision="fp32",
            moments="bf16",
            model_scale="tiny" if smoke else "1b",
            mesh_axis="data",
            zero_sharding=(leg == "on"),
            measure_update=True,
            # digesting 1B fp32 params off-device is smoke-only; the
            # real-chip A/B trusts the CI byte-identity gate
            params_digest=smoke,
        )
        res = real_chip.bench_llama1b(ns)
        n_chips = len(jax.devices())
        step_time = res["dt"] / ns.steps
        tflops = res["flops_fallback"] / step_time / n_chips / 1e12
        entry = {
            "step_time_ms": round(step_time * 1e3, 1),
            "weight_update_ms": res["weight_update_ms"],
            "final_loss": round(res["loss"], 4),
        }
        if jax.default_backend() == "tpu" and not smoke:
            entry["mfu_pct"] = round(
                tflops / peaks.peak_for(jax.devices()[0]).bf16_tflops * 100,
                1,
            )
        if "params_digest" in res:
            entry["params_digest"] = res["params_digest"]
        results[f"zero_{leg}"] = entry

    if smoke:
        _partial["smoke"] = True
        # The byte-identity gate: the weight-update DECOMPOSITION must
        # be byte-exact on identical gradients (elementwise math, only
        # placement changes). The full train legs' digests may differ
        # by gradient-reduction summation order (reduce-scatter vs
        # all-reduce grouping) — reported, not gated.
        ab = real_chip.update_ab_digests(
            argparse.Namespace(seq=16, model_scale="tiny", mesh_axis="data")
        )
        _partial["update_params_match"] = ab["on"] == ab["off"]
    out = {
        "metric": "zero_weight_update",
        # the headline: replicated-optimizer span ÷ ZeRO-sharded span
        # (>1 = the cross-replica partition pays)
        "value": round(
            results.get("zero_off", {}).get("weight_update_ms", 0)
            / max(
                results.get("zero_on", {}).get("weight_update_ms", 1e-9),
                1e-9,
            ),
            3,
        )
        if {"zero_on", "zero_off"} <= set(results)
        else 0,
        "unit": "x",
        "vs_baseline": round(
            results.get("zero_off", {}).get("step_time_ms", 0)
            / max(results.get("zero_on", {}).get("step_time_ms", 1e-9), 1e-9),
            3,
        )
        if {"zero_on", "zero_off"} <= set(results)
        else 0,
        "backend": jax.default_backend(),
        "chips": len(jax.devices()),
        "batch": 8,
        "seq": 64 if smoke else 1024,
        **results,
        **_partial,
    }
    if {"zero_on", "zero_off"} <= set(results) and smoke:
        out["train_params_match"] = (
            results["zero_on"]["params_digest"]
            == results["zero_off"]["params_digest"]
        )
    if {"zero_on", "zero_off"} <= set(results):
        path = os.path.join(
            _results_dir(),
            "zero_weight_update"
            + (f"_{jax.default_backend()}_smoke" if smoke else "")
            + ".json",
        )
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(out, f, indent=2, sort_keys=True)
                f.write("\n")
            out["artifact"] = path
        except OSError as e:
            out["artifact_error"] = str(e)
    else:
        # a single-leg quick look must never clobber the committed
        # two-leg A/B evidence the BASELINE row reads
        out["artifact_skipped"] = "partial legs; artifact needs on AND off"
    _emit(out)


def _bench_mnist_feed(steps: int = 40) -> None:
    """MNIST end-to-end through the data plane: columnar wire frames →
    sliced column batches → staged ``DevicePrefetcher.from_feed`` H2D —
    the default feed loop — with feed MB/s recorded beside MFU."""
    import secrets

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu.cluster import manager as tf_manager
    from tensorflowonspark_tpu.cluster.marker import EndOfFeed
    from tensorflowonspark_tpu.compute import TrainState, build_train_step
    from tensorflowonspark_tpu.compute.mesh import make_mesh
    from tensorflowonspark_tpu.feed import DataFeed, DevicePrefetcher
    from tensorflowonspark_tpu.feed import columnar as col
    from tensorflowonspark_tpu.models import mnist

    mesh = make_mesh({"data": len(jax.devices())})
    batch_size = 1024
    warmup = 3
    total = steps + warmup

    model = mnist.CNN()
    rng = np.random.default_rng(0)
    # uint8 records: what a real MNIST pipeline ships; normalize on device
    images = (rng.random((batch_size, 28, 28, 1)) * 255).astype(np.uint8)
    labels = rng.integers(0, 10, size=batch_size).astype(np.int32)
    params = model.init(
        jax.random.PRNGKey(0), images[:2].astype(np.float32)
    )["params"]
    tx = optax.adam(1e-3)
    state = TrainState.create(params, tx)
    base_loss = mnist.loss_fn(model.apply)

    def loss(p, b):
        img = b["image"].astype(jnp.float32) / 255.0
        return base_loss(p, {"image": img, "label": b["label"]})

    step = build_train_step(loss, tx, mesh)

    mgr = tf_manager.start(secrets.token_bytes(8), mode="local", maxsize=64)

    # what one record costs on the wire: the uint8 image + int32 label
    record_bytes = images[0].nbytes + labels[:1].nbytes

    # Aggregator overhead leg: scrape this process's /metrics on the
    # production cadence WHILE training (the driver would, via
    # TFCluster.cluster_stats) and report scrape wall-time as a % of
    # train wall-time — the obs plane must cost < 1% of train.step.
    from tensorflowonspark_tpu.cluster import node as tf_node
    from tensorflowonspark_tpu.obs import cluster as obs_cluster

    agg = None
    metrics_port = tf_node._maybe_start_metrics_server("127.0.0.1")
    if metrics_port:
        agg = obs_cluster.MetricsAggregator(
            lambda: {0: f"http://127.0.0.1:{metrics_port}/metrics"},
            interval=2.0,
        )
        agg.start()

    def produce():
        # the production wire shape: each chunk columnized ONCE into a
        # CRC-framed ColumnarFrame (feed/columnar.py), no row pickles
        q = mgr.get_queue("input")
        chunk = col.columnize_records(list(zip(images, labels)))
        for seq in range(total):
            q.put(
                col.ColumnarFrame(
                    col.frame_bytes(chunk, stream="bench", seq=seq)
                )
            )
        q.put(EndOfFeed())

    threading.Thread(target=produce, daemon=True).start()
    feed = DataFeed(mgr, input_mapping={"image": "image", "label": "label"})

    n = 0
    t0 = None
    pf = DevicePrefetcher.from_feed(feed, batch_size, mesh, depth=2)
    with pf:
        for dev_batch in pf:
            state, loss_v = step(state, dev_batch)
            n += 1
            if n == warmup:
                float(loss_v)
                t0 = time.perf_counter()
    final = float(loss_v)
    dt = time.perf_counter() - t0
    mgr.stop()
    timed = n - warmup
    _partial.update(
        mnist_examples_per_sec=round(timed * batch_size / dt, 1),
        mnist_step_time_ms=round(dt / timed * 1e3, 2),
        # feed plane MB/s beside MFU: wire bytes drained per wall second
        # while training (columnar frames -> sliced batches -> staged H2D)
        mnist_feed_mb_s=round(timed * batch_size * record_bytes / dt / 1e6, 1),
        mnist_final_loss=round(final, 4),
    )
    if agg is not None:
        agg.stop()
        rounds = max(
            1, int(agg.registry.counter("cluster_scrape_total").value())
        )
        if agg.total_scrape_cpu_s == 0.0:
            # run shorter than one cadence: measure one round and
            # amortize it over the production interval
            agg.scrape_once()
            denom = agg.interval
        else:
            denom = max(dt, rounds * agg.interval)
        # CPU seconds the scrape thread consumed, NOT its wall time —
        # on a saturated host wall is mostly GIL/IO waits that steal
        # nothing from train.step
        _partial["mnist_aggregator_overhead_pct"] = round(
            100.0 * agg.total_scrape_cpu_s / denom, 4
        )


def _bench_serve(smoke: bool) -> None:
    """``--serve``: the serving engine tax as ONE committed JSON line.

    ``engine_tax`` = raw single-stream ``llama.generate`` tokens/sec ÷
    continuous-engine tokens/sec on the SAME params — the serving
    engine tax as a first-class bench metric instead of a hand-derived
    ratio of two separate runs. The engine
    leg runs at ``pipeline_depth`` 1 (the pre-overlap serial scheduler)
    AND 2 (the shipped default) so the dispatch-ahead win is measured
    in the same artifact; the depth-2 engine's span ring is distilled
    through ``obs.trace_report`` into
    ``benchmarks/results/serve_*_trace_report.json`` — the engine's
    non-MXU/host residual as a committed artifact, per-phase
    (dispatch/fetch/sweep/prefill) self-time included.
    """
    import tempfile
    import threading as _threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.models.llama import generate
    from tensorflowonspark_tpu.serving import ContinuousBatcher

    ns = argparse.Namespace(
        batch_size=4 if smoke else 8,
        seq=16 if smoke else 128,
        new_tokens=24 if smoke else 256,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    params = jax.tree.map(
        jax.device_put,
        model.init(
            jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
        )["params"],
    )
    reps = 2 if smoke else 3

    # Raw single-stream floor: ONE row through generate() — the "how
    # fast can these params decode with zero scheduling" reference.
    raw_prompt = jnp.asarray(prompts[:1])
    np.asarray(generate(model, params, raw_prompt, new_tokens)[0, :1])
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(
            generate(model, params, raw_prompt, new_tokens)[0, :1]
        )
    raw_tps = reps * new_tokens / (time.perf_counter() - t0)
    _partial["raw_single_stream_tokens_per_sec"] = round(raw_tps, 1)

    def engine_leg(depth: int):
        eng = ContinuousBatcher(
            model,
            params,
            slots=b,
            prompt_widths=(prompts.shape[1],),
            pipeline_depth=depth,
        )

        def fire_all(n_tokens: int) -> None:
            # ferry worker-thread failures (same pattern as
            # benchmarks/real_chip.py bench_llama1b_engine): a dead
            # engine answers instantly and would fake a measurement
            errors: list = [None] * b
            def one(i):
                try:
                    eng.submit(prompts[i].tolist(), n_tokens)
                except BaseException as e:  # noqa: BLE001
                    errors[i] = e
            threads = [
                _threading.Thread(target=one, args=(i,))
                for i in range(b)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for e in errors:
                if e is not None:
                    raise e

        fire_all(4)  # compile prefill + admit + block, warm the loop
        tok0 = eng.tokens_emitted
        t0 = time.perf_counter()
        for _ in range(reps):
            fire_all(new_tokens)
        dt = time.perf_counter() - t0
        timed_tokens = eng.tokens_emitted - tok0
        st = eng.stats()
        # scheduler-loop host cost per emitted token: the PR-1 phase
        # spans' dispatch+fetch totals over the whole engine lifetime
        # (warm included — identical across legs, so the DELTA between
        # depths is the dispatch-ahead win)
        host_ms = sum(
            st["phase_ms"].get(ph, {}).get("total_ms", 0.0)
            for ph in ("dispatch", "fetch")
        )
        leg = dict(
            tokens_per_sec=round(timed_tokens / dt, 1),
            dispatch_fetch_ms_per_token=round(
                host_ms / max(1, eng.tokens_emitted), 4
            ),
            drain_stalls=st["drain_stalls"],
            overlap_hidden_ms=st["overlap_hidden_ms"],
        )
        return eng, leg

    eng1, leg1 = engine_leg(1)
    eng1.close()
    eng2, leg2 = engine_leg(2)
    _partial["engine_depth1"] = leg1
    _partial["engine_depth2"] = leg2
    _partial["pipeline_speedup"] = round(
        leg2["tokens_per_sec"] / max(leg1["tokens_per_sec"], 1e-9), 3
    )

    # Commit the engine's host-residual evidence: the span ring as a
    # chrome trace, distilled by the same obs.trace_report commit path
    # the MFU bench uses — no more dead trace files in /tmp.
    try:
        trace_dir = tempfile.mkdtemp(prefix="serve_trace_")
        eng2._tracer.write_chrome_trace(
            os.path.join(trace_dir, "engine.trace.json"),
            "serving engine (pipeline_depth=2)",
        )
        _emit_trace_report(
            trace_dir, jax.default_backend(), smoke, name="serve"
        )
    finally:
        eng2.close()

    engine_tps = leg2["tokens_per_sec"]
    tax = raw_tps / max(engine_tps, 1e-9)
    _emit(
        {
            "metric": "serve_engine_tax",
            # raw single-stream tok/s ÷ engine tok/s at full occupancy:
            # >1 = scheduling tax dominates, <1 = the engine amortizes
            # its batch
            "value": round(tax, 4),
            "unit": "x",
            # engine throughput as a multiple of the single stream —
            # higher is better, >=1 means batching pays for scheduling
            "vs_baseline": round(engine_tps / max(raw_tps, 1e-9), 3),
            "backend": jax.default_backend(),
            "chips": len(jax.devices()),
            "slots": b,
            "new_tokens": new_tokens,
            **_partial,
        }
    )


def _bench_serve_fleet(smoke: bool) -> None:
    """``--serve-fleet``: saturation throughput scaling, replicas=1 vs 2.

    Each leg puts a :class:`ServingFleet` of N in-process continuous
    engines behind the health-routing ``FleetRouter`` and drives it
    with 2x-slots concurrent blocking submitters for a fixed request
    count, alongside the router's shed/failover counters (both must be
    0 in a healthy unsaturated run: scaling must not come from
    dropping work). Two scaling numbers, the feed-plane (PR 8)
    methodology: the CONTENDED wall ratio (both replicas sharing this
    host's devices — on a 1-core CPU host this reads the routing/
    batch-splitting overhead, not capacity), and the UNCONTENDED
    per-replica rate (each replica driven alone, self-timed — flat
    per-replica rate means the fleet projects to ~N x on pods where
    each replica owns its chip, which is the deployment shape). The
    artifact lands in ``benchmarks/results/serve_fleet_<backend>.json``.
    """
    import threading as _threading

    import jax
    import jax.numpy as jnp

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.slo import SLOEvaluator, router_slos
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet
    from tensorflowonspark_tpu.serving.router import FleetRouter

    ns = argparse.Namespace(
        batch_size=2 if smoke else 4,
        seq=16 if smoke else 128,
        new_tokens=16 if smoke else 128,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    params = jax.tree.map(
        jax.device_put,
        model.init(
            jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
        )["params"],
    )
    requests = (2 if smoke else 6) * b  # per leg, after warmup

    def leg(n_replicas: int) -> dict:
        def factory():
            return ContinuousBatcher(
                model,
                params,
                slots=b,
                prompt_widths=(prompts.shape[1],),
            )

        fleet = ServingFleet(
            factory=factory,
            replicas=n_replicas,
            probe_interval=0.5,
            warmup=False,
            drain_timeout=10.0,
        )
        router = FleetRouter(fleet)
        errors: list = []

        def fire(count: int, n_tok: int, tag: int) -> None:
            def one(i):
                try:
                    # distinct prompts defeat prefix affinity so the
                    # load spreads — this leg measures CAPACITY
                    router.submit(
                        prompts[(tag + i) % len(prompts)].tolist(),
                        n_tok,
                    )
                except BaseException as e:  # noqa: BLE001 - ferried
                    errors.append(e)

            threads = [
                _threading.Thread(target=one, args=(i,))
                for i in range(count)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        fire(n_replicas * b, 4, tag=0)  # compile/warm every replica
        # the SLO budget gate (obs.slo): one History window over the
        # timed fire, warmup compiles excluded via the window cursor
        fleet.metrics.window()
        hist = History(source=f"bench.serve_fleet.r{n_replicas}")
        ev = SLOEvaluator(
            router_slos(latency_objective_s=30.0 if smoke else 10.0),
            hist,
            registry=fleet.metrics,
        )
        t0 = time.perf_counter()
        fire(requests, new_tokens, tag=1)
        dt = time.perf_counter() - t0
        hist.scrape_registry(fleet.metrics)
        verdicts = ev.evaluate()
        st = router.stats()["router"]
        # uncontended: each replica alone, one full b-row batch,
        # self-timed — the per-chip rate a one-replica-per-chip pod
        # would see (the staggered-pull-leg methodology)
        rates = []
        for v in fleet.ready_views():
            best = 0.0
            for _ in range(3):  # best-of: least host interference
                t1 = time.perf_counter()
                v["handle"].submit_many(
                    [
                        prompts[i % len(prompts)].tolist()
                        for i in range(b)
                    ],
                    new_tokens,
                )
                best = max(
                    best,
                    b * new_tokens / (time.perf_counter() - t1),
                )
            rates.append(round(best, 1))
        out = dict(
            tokens_per_sec=round(requests * new_tokens / dt, 1),
            uncontended_per_replica=rates,
            requests=requests,
            shed=sum(st["shed"].values()) if st["shed"] else 0,
            failovers=st["failovers"],
            slo_breaching=sorted(v.slo for v in verdicts if v.breached),
            slo=[v.as_dict() for v in verdicts],
        )
        router.close()
        return out

    leg1 = leg(1)
    leg2 = leg(2)
    _partial["fleet_replicas1"] = leg1
    _partial["fleet_replicas2"] = leg2
    wall_ratio = leg2["tokens_per_sec"] / max(
        leg1["tokens_per_sec"], 1e-9
    )
    # projection: fleet-of-2 aggregate if each replica owned its own
    # device (per-replica uncontended rates summed, over the single
    # replica's uncontended rate) — >= 0.8*N means the router/fleet
    # plane itself costs < 20%; wall_ratio on a shared-device host
    # additionally pays the device contention the projection removes
    projected = sum(leg2["uncontended_per_replica"]) / max(
        leg1["uncontended_per_replica"][0], 1e-9
    )
    result = {
        "metric": "serve_fleet_scaling",
        "value": round(projected, 3),
        "unit": "x",
        "vs_baseline": round(projected / 1.6, 3),
        "wall_ratio_contended": round(wall_ratio, 3),
        "backend": jax.default_backend(),
        "chips": len(jax.devices()),
        "slots_per_replica": b,
        "new_tokens": new_tokens,
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"serve_fleet_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)


def _bench_cache(smoke: bool) -> None:
    """``--cache``: the disaggregated read-through cache tier A/B.

    Serving leg: a 2-replica in-process fleet serves a shared-prefix
    workload — P distinct "system prompts" (prefix families), every
    request one family plus a unique 2-token tail — round-robin across
    the replicas, with ``prefix_l2`` off vs on. Round-robin is the
    cache-hostile shape: each replica's L1 holds only what IT served
    and thrashes across families, so without the fleet tier every
    L1 miss re-prefills the whole family prefix from token 0. With the
    tier, the ladder a sibling replica published turns that miss into
    a fetch + one-chunk continuation (and the reconstructed entry
    re-seeds L1, so the tier heals L1 instead of replacing it). The
    headline ``value`` is the fleet tokens/sec ratio (L2 on / off);
    the leg also commits both legs' tok/s and the cross-replica L2
    hit counters (must be > 0).

    Training leg: two concurrent readers drain one columnar framed
    dataset through a shared ``CacheTier``; the committed counters
    prove backing storage was read ~1x the dataset size (not once per
    reader). Artifact: ``benchmarks/results/cache_<backend>.json``.
    """
    import tempfile
    import threading as _threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet

    ns = argparse.Namespace(
        batch_size=2,
        seq=132,  # a rung (128) + tail: the ladder covers ~the prefix
        new_tokens=4 if smoke else 16,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    params = jax.tree.map(
        jax.device_put,
        model.init(
            jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
        )["params"],
    )
    seq = int(prompts.shape[1])
    chunk = 4 if smoke else 16
    base = [int(t) for t in prompts[0]]
    families = 7  # odd (coprime with the 2-replica round-robin) so
    # EVERY replica serves every family, and more family state than
    # one L1 holds: the per-replica L1 must thrash
    requests = 4 * families

    def mk_prompt(family: int, tail: int) -> list[int]:
        p = list(base)
        p[0] = 2 + family  # family identity up front: distinct prefixes
        p[-2] = 2 + (tail * 7) % 241
        p[-1] = 2 + (tail * 13) % 241
        return p

    def serving_leg(l2) -> dict:
        def factory():
            return ContinuousBatcher(
                model,
                params,
                slots=b,
                prompt_widths=(seq,),
                prefill_chunk=chunk,
                # >= 2x the ladder rungs: boundary inserts (and so L2
                # offers) are flood-capped at prefix_cache//2 per
                # request — smaller and the deep rungs never publish
                prefix_cache=16,
            )

        fleet = ServingFleet(
            factory=factory,
            replicas=2,
            probe_interval=0.5,
            warmup=False,
            drain_timeout=10.0,
            prefix_l2=l2,
        )
        try:
            views = fleet.views()
            # replica 0 prefills every family once: with an L2 this
            # publishes each family's boundary ladder fleet-wide;
            # replica 1 gets one request so it is compile-warm (its L1
            # stays cold for all but that family)
            for f in range(families):
                views[0]["handle"].submit_many([mk_prompt(f, 200 + f)], 2)
            views[1]["handle"].submit_many([mk_prompt(0, 220)], 2)
            if l2 is not None:
                # offers are fire-and-forget; wait for the filler to
                # drain before timing (a real fleet is long-lived)
                deadline = time.monotonic() + 30.0
                while (
                    time.monotonic() < deadline
                    and (fleet.cache_stats() or {}).get("entries", 0)
                    < families
                ):
                    time.sleep(0.05)
            # timed: round-robin, unique tails — min of 2 passes
            walls = []
            for rep in range(2):
                t0 = time.perf_counter()
                for i in range(requests):
                    views[i % 2]["handle"].submit_many(
                        [mk_prompt(i % families, 100 * rep + i)],
                        new_tokens,
                    )
                walls.append(time.perf_counter() - t0)
            dt = min(walls)
            st = [v["handle"].stats() for v in views]
            return dict(
                tokens_per_sec=round(requests * new_tokens / dt, 1),
                requests_per_pass=requests,
                wall_s=[round(w, 3) for w in walls],
                l2_hits=sum(s.get("prefix_l2_hits", 0) for s in st),
                l2_misses=sum(s.get("prefix_l2_misses", 0) for s in st),
                l2_offer_dedups=sum(
                    s.get("prefix_l2_offer_dedups", 0) for s in st
                ),
                tier=fleet.cache_stats(),
            )
        finally:
            fleet.close()

    l1_leg = serving_leg(None)
    _partial["cache_l1_only"] = l1_leg
    l2_leg = serving_leg("inproc")
    _partial["cache_l2"] = l2_leg

    # -- training leg: two readers, one backing pass -------------------
    from tensorflowonspark_tpu.cachetier import (
        CacheTier,
        FrameCache,
        LocalClient,
    )
    from tensorflowonspark_tpu.data.grain_source import (
        ColumnarFrameDataSource,
    )
    from tensorflowonspark_tpu.feed import columnar as col
    from tensorflowonspark_tpu.feed.columnar import scan_frames

    n_records = 512 if smoke else 4096
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "bench.colf")
        col.write_frames(
            path,
            (
                {
                    "x": np.arange(32, dtype=np.float32) + i,
                    "y": np.int64(i),
                }
                for i in range(n_records)
            ),
            records_per_frame=64,
        )
        payload = sum(span for _, span, n in scan_frames(path) if n)
        tier = CacheTier(capacity_bytes=256 << 20)
        srcs = [
            ColumnarFrameDataSource(
                path, frame_cache=FrameCache(LocalClient(tier))
            )
            for _ in range(2)
        ]
        orders = [
            range(n_records),
            range(n_records - 1, -1, -1),
        ]

        def drain(ri: int) -> None:
            for i in orders[ri]:
                srcs[ri][i]

        threads = [
            _threading.Thread(target=drain, args=(ri,)) for ri in range(2)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        tst = tier.stats()
    training = dict(
        records=n_records,
        readers=2,
        payload_bytes=payload,
        backing_read_bytes=tst["backing_read_bytes"],
        # ~1.0 = each frame hit backing storage once ACROSS readers
        # (2.0 would mean the tier saved nothing)
        backing_ratio=round(tst["backing_read_bytes"] / payload, 3),
        tier_hits=tst["hits"],
        tier_misses=tst["misses"],
        wall_s=round(dt, 3),
    )
    _partial["cache_training"] = training

    speedup = l2_leg["tokens_per_sec"] / max(
        l1_leg["tokens_per_sec"], 1e-9
    )
    result = {
        "metric": "cachetier_readthrough",
        # headline: fleet tok/s with the tier over without it on the
        # same round-robin shared-prefix traffic (>1 = the tier
        # recovers prefill compute the L1-thrashing fleet re-pays)
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "tokens_per_sec_l1_only": l1_leg["tokens_per_sec"],
        "tokens_per_sec_l2": l2_leg["tokens_per_sec"],
        "l2_hits": l2_leg["l2_hits"],
        "training_backing_ratio": training["backing_ratio"],
        "backend": jax.default_backend(),
        "chips": len(jax.devices()),
        "new_tokens": new_tokens,
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"cache_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)


def _metric_total(registry, name: str) -> float:
    """Sum every labelled series of one counter straight off the
    registry's rendered exposition — the same surface a scraper reads,
    so the artifact reports the metric's real value, not a shadow."""
    total = 0.0
    for line in registry.render().splitlines():
        if line.startswith(f"{name} ") or line.startswith(f"{name}{{"):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except ValueError:
                pass
    return total


def _bench_autotune(smoke: bool) -> None:
    """``--autotune``: feedback-controlled recovery from bad knobs.

    Two legs, each booted with DELIBERATELY bad knob settings and
    handed to a :class:`tensorflowonspark_tpu.autotune.Controller`
    driving the component's sanctioned actuation path; acceptance is
    the converged throughput reaching >= 90% of the same pipeline
    hand-tuned (``recovered_frac`` per leg):

    - **feed leg** — the mnist feed pipeline (columnar frames ->
      DataFeed -> DevicePrefetcher) started at prefetch depth 1
      against a producer with periodic shard-open stalls plus a
      per-depth host staging tax, so throughput peaks at an interior
      depth: the controller must grow ``feed.prefetch_depth`` to hide
      the stalls, overshoot the peak, and REVERT (the committed audit
      trail must show ``autotune_reverts_total > 0``);
    - **serve leg** — a 1-replica continuous-batching fleet booted at
      ``decode_block=1 / pipeline_depth=1`` (the un-amortized
      host-round-trip config) behind a router with a pessimistic
      cold-start ``service_time_hint_s``: the controller climbs both
      engine knobs through ``ContinuousBatcher.set_knobs`` (installed
      between decode blocks) and the direct router policy replaces the
      hint with the measured p90.

    Every move/revert is a registered flight-recorder event and a row
    in the controllers' decision logs (dumped to ``logs/autotune-*``
    for ``tools/obs_snapshot.py`` and embedded in the committed
    ``benchmarks/results/autotune_<backend>[_smoke].json``).
    """
    import jax

    from tensorflowonspark_tpu.obs import flightrec

    if smoke:
        _partial["smoke"] = True
    rec = flightrec.install(
        os.path.join("logs", "flightrec-bench-autotune.json"),
        process="bench-autotune",
    )

    feed = _autotune_feed_leg()
    _partial["feed_leg"] = feed
    serve = _autotune_serve_leg(smoke)
    _partial["serve_leg"] = serve

    events = rec.snapshot("bench-autotune")["events"]
    at_events = [
        e for e in events if str(e.get("kind", "")).startswith("autotune_")
    ]
    decisions_total = feed["decisions_total"] + serve["decisions_total"]
    reverts_total = feed["reverts_total"] + serve["reverts_total"]
    result = {
        "metric": "autotune_recovery",
        "value": round(
            min(feed["recovered_frac"], serve["recovered_frac"]), 3
        ),
        "unit": "frac_of_hand_tuned",
        "vs_baseline": round(
            min(feed["recovered_frac"], serve["recovered_frac"]) / 0.9, 3
        ),
        "autotune_decisions_total": decisions_total,
        "autotune_reverts_total": reverts_total,
        "flightrec_autotune_events": len(at_events),
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"autotune_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)


def _autotune_feed_leg() -> dict:
    """The mnist-feed autotune leg (see ``_bench_autotune``). Pure-host
    physics so the controller's behavior — not chip speed — is what is
    measured: the consumer "train step" is a fixed sleep, the producer
    stalls periodically (a shard-open hiccup the prefetch queue must
    hide, amortizable up to ``depth x compute`` per stall), and staging
    costs a small per-depth tax (host-memory pressure), giving
    throughput an interior peak the hill-climb must find and defend."""
    import secrets

    import numpy as np

    from tensorflowonspark_tpu.autotune import Controller, KnobRegistry
    from tensorflowonspark_tpu.autotune.policies import (
        prefetch_depth_policy,
    )
    from tensorflowonspark_tpu.cluster import manager as tf_manager
    from tensorflowonspark_tpu.feed import DataFeed, DevicePrefetcher
    from tensorflowonspark_tpu.feed import columnar as col
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.registry import default_registry

    compute_s = 0.010  # the consumer's fixed per-batch "train step"
    stall_every = 16  # producer hiccup cadence (batches)
    stall_s = 0.12  # producer hiccup depth — hidden iff depth >= 12
    tax_knee = 17  # depth past which staging pays a per-batch tax
    tax_s = 0.006  # (host-memory pressure): past the knee the producer
    # becomes the bottleneck, so deeper REGRESSES (the revert bait)
    hand_depth = 15
    batch = 32
    rows = 256

    rng = np.random.default_rng(0)
    images = (rng.random((rows, 28, 28, 1)) * 255).astype(np.uint8)
    labels = rng.integers(0, 10, size=rows).astype(np.int32)

    def pipeline(depth: int):
        mgr = tf_manager.start(
            secrets.token_bytes(8), mode="local", maxsize=8
        )
        stop = threading.Event()

        def produce():
            import queue as _q

            q = mgr.get_queue("input")
            chunk = col.columnize_records(list(zip(images, labels)))
            seq = 0
            while not stop.is_set():
                try:
                    q.put(
                        col.ColumnarFrame(
                            col.frame_bytes(
                                chunk, stream="autotune", seq=seq
                            )
                        ),
                        timeout=0.2,
                    )
                    seq += 1
                except _q.Full:
                    continue
                except (OSError, EOFError, BrokenPipeError):
                    return  # manager torn down at leg end

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        feed = DataFeed(
            mgr, input_mapping={"image": "image", "label": "label"}
        )

        cell: dict = {}
        nbatch = [0]

        def prepare(cols):
            nbatch[0] += 1
            pf = cell.get("pf")
            d = pf.stats()["depth"] if pf is not None else depth
            if d > tax_knee:
                time.sleep(tax_s * (d - tax_knee))
            if nbatch[0] % stall_every == 0:
                time.sleep(stall_s)
            return cols

        pf = DevicePrefetcher.from_feed(
            feed,
            batch,
            depth=depth,
            prepare=prepare,
            transform=lambda b: b,  # host-physics leg: no device hop
        )
        cell["pf"] = pf
        return mgr, stop, producer, pf

    def drive(pf, seconds: float, pump=None) -> float:
        """Consume batches for ~seconds (the training loop stand-in);
        returns delivered batches/sec."""
        count = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        for _ in pf:
            time.sleep(compute_s)
            count += 1
            if pump is not None:
                pump()
            if time.perf_counter() >= deadline:
                break
        return count / max(time.perf_counter() - t0, 1e-9)

    def teardown(mgr, stop, producer, pf) -> None:
        pf.close()
        stop.set()
        producer.join(timeout=2.0)
        mgr.stop()

    # -- hand-tuned reference (static, no controller) -----------------
    mgr, stop, producer, pf = pipeline(hand_depth)
    drive(pf, 1.5)  # settle
    hand_rate = drive(pf, 3.0)
    teardown(mgr, stop, producer, pf)

    # -- bad start, then the controller takes the knob ----------------
    mgr, stop, producer, pf = pipeline(1)
    drive(pf, 1.0)  # settle
    bad_rate = drive(pf, 2.5)

    knobs = KnobRegistry()
    knob, policy = prefetch_depth_policy(
        pf, lo=1, hi=24, window_s=1.0
    )
    knobs.register(knob)
    hist = History(source="bench.autotune.feed")
    ctrl = Controller(
        knobs, hist, [policy], source="bench-feed"
    )

    # a pending move is judged at the NEXT step, so the step cadence
    # must match the objective window for a purely post-move verdict
    scrape_s, step_s = 0.2, 1.0
    state = {"scrape": 0.0, "step": 0.0}

    def pump():
        now = time.time()
        if now >= state["scrape"]:
            state["scrape"] = now + scrape_s
            hist.scrape_registry(default_registry())
        if now >= state["step"]:
            state["step"] = now + step_s
            ctrl.step(now)

    drive(pf, 22.0, pump)  # converge: one knob move per window
    tuned_rate = drive(pf, 3.0, pump)  # still online, now converged
    final_depth = pf.stats()["depth"]
    teardown(mgr, stop, producer, pf)

    log = ctrl.decision_log()
    dump_path = ctrl.dump()
    return {
        "bad_batches_per_sec": round(bad_rate, 1),
        "hand_tuned_batches_per_sec": round(hand_rate, 1),
        "tuned_batches_per_sec": round(tuned_rate, 1),
        "recovered_frac": round(tuned_rate / max(hand_rate, 1e-9), 3),
        "initial_depth": 1,
        "hand_depth": hand_depth,
        "final_depth": final_depth,
        "decisions_total": _metric_total(
            default_registry(), "autotune_decisions_total"
        ),
        "reverts_total": _metric_total(
            default_registry(), "autotune_reverts_total"
        ),
        "decision_log": log,
        "decision_log_path": dump_path,
        "knobs": knobs.snapshot(),
    }


def _autotune_serve_leg(smoke: bool) -> dict:
    """The serve-fleet autotune leg (see ``_bench_autotune``)."""
    import threading as _threading

    import jax
    import jax.numpy as jnp

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.autotune import Controller, KnobRegistry
    from tensorflowonspark_tpu.autotune.policies import (
        engine_knob_policies,
        router_estimate_policy,
    )
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.slo import SLOEvaluator, router_slos
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet
    from tensorflowonspark_tpu.serving.router import FleetRouter

    ns = argparse.Namespace(
        batch_size=2 if smoke else 4,
        seq=16 if smoke else 128,
        new_tokens=8 if smoke else 32,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    params = jax.tree.map(
        jax.device_put,
        model.init(
            jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
        )["params"],
    )
    block_hi = 4 if smoke else 8
    hand_knobs = {"decode_block": block_hi, "pipeline_depth": 2}
    bad_knobs = {"decode_block": 1, "pipeline_depth": 1}

    def build(knob_cfg: dict, hint_s: float | None):
        fleet = ServingFleet(
            factory=lambda: ContinuousBatcher(
                model,
                params,
                slots=b,
                prompt_widths=(prompts.shape[1],),
                **knob_cfg,
            ),
            replicas=1,
            probe_interval=0.5,
            warmup=False,
            drain_timeout=10.0,
        )
        router = FleetRouter(fleet, service_time_hint_s=hint_s)
        return fleet, router

    class _Load:
        """Closed-loop submitters: 2x-slots threads resubmitting
        against the router until stopped; the completed-token tally is
        the throughput read."""

        def __init__(self, router, threads: int):
            self._router = router
            self._stop = _threading.Event()
            self._lock = _threading.Lock()
            self._tokens = 0  # guarded-by: self._lock
            self.errors: list = []
            self._threads = [
                _threading.Thread(target=self._run, args=(t,), daemon=True)
                for t in range(threads)
            ]
            for t in self._threads:
                t.start()

        def _run(self, tag: int) -> None:
            i = 0
            while not self._stop.is_set():
                try:
                    self._router.submit(
                        prompts[(tag + i) % len(prompts)].tolist(),
                        new_tokens,
                    )
                except BaseException as e:  # noqa: BLE001 - ferried
                    if not self._stop.is_set():
                        self.errors.append(e)
                    return
                with self._lock:
                    self._tokens += new_tokens
                i += 1

        def tokens(self) -> int:
            with self._lock:
                return self._tokens

        def stop(self) -> None:
            self._stop.set()
            for t in self._threads:
                t.join(timeout=30.0)

    def warm(router, engine) -> None:
        """Compile prefill and every decode-block program the climb
        will visit, then restore the leg's boot knobs — warmup, not
        tuning: the timed phases still start from the bad config."""
        boot = dict(engine.stats())
        for k in range(1, block_hi + 1):
            engine.set_knobs(decode_block=k)
            threads = [
                _threading.Thread(
                    target=lambda i=i: router.submit(
                        prompts[i % len(prompts)].tolist(), 4
                    )
                )
                for i in range(b)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        engine.set_knobs(
            decode_block=boot["decode_block"],
            pipeline_depth=boot["pipeline_depth"],
        )

    def rate_over(load, seconds: float, pump=None) -> float:
        c0, t0 = load.tokens(), time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            time.sleep(0.05)
            if pump is not None:
                pump()
        if load.errors:
            raise load.errors[0]
        return (load.tokens() - c0) / max(
            time.perf_counter() - t0, 1e-9
        )

    # -- hand-tuned reference -----------------------------------------
    fleet, router = build(hand_knobs, None)
    engine = fleet.ready_views()[0]["handle"].engine
    warm(router, engine)
    load = _Load(router, threads=2 * b)
    time.sleep(1.0)  # settle
    hand_rate = rate_over(load, 5.0)
    load.stop()
    router.close()

    # -- bad boot, then the controller takes the knobs ----------------
    fleet, router = build(bad_knobs, hint_s=25.0)
    engine = fleet.ready_views()[0]["handle"].engine
    warm(router, engine)
    est_before = router.service_estimate()
    load = _Load(router, threads=2 * b)
    time.sleep(1.0)
    bad_rate = rate_over(load, 2.5)

    knobs = KnobRegistry()
    policies = []
    for knob, policy in engine_knob_policies(
        engine,
        deadline_s=30.0,
        decode_block_hi=block_hi,
        pipeline_depth_hi=2,
        window_s=2.0,
    ):
        knobs.register(knob)
        policies.append(policy)
    rknob, rpolicy = router_estimate_policy(
        router, q=0.9, lo_s=0.02, window_s=4.0
    )
    knobs.register(rknob)
    policies.append(rpolicy)
    hist = History(source="bench.autotune.serve")
    ev = SLOEvaluator(
        router_slos(latency_objective_s=30.0 if smoke else 10.0),
        hist,
        registry=fleet.metrics,
    )
    ctrl = Controller(
        knobs,
        hist,
        policies,
        slo=ev,
        metrics_registry=fleet.metrics,
        source="bench-serve",
    )

    state = {"scrape": 0.0, "step": 0.0}

    def pump():
        now = time.time()
        if now >= state["scrape"]:
            state["scrape"] = now + 0.25
            hist.scrape_registry(fleet.metrics)
            hist.scrape_registry(engine.metrics)
        if now >= state["step"]:
            # judge-at-next-step: 2.5s between steps keeps the 2.0s
            # objective window clear of the apply transient (a
            # pipeline-depth change drains the current window first)
            state["step"] = now + 2.5
            ev.evaluate(now)
            ctrl.step(now)

    rate_over(load, 30.0, pump)  # converge
    tuned_rate = rate_over(load, 5.0, pump)  # still online, converged
    final = {
        k: engine.stats()[k] for k in ("decode_block", "pipeline_depth")
    }
    est_after = router.service_estimate()
    load.stop()
    router.close()

    log = ctrl.decision_log()
    dump_path = ctrl.dump()
    return {
        "bad_tokens_per_sec": round(bad_rate, 1),
        "hand_tuned_tokens_per_sec": round(hand_rate, 1),
        "tuned_tokens_per_sec": round(tuned_rate, 1),
        "recovered_frac": round(tuned_rate / max(hand_rate, 1e-9), 3),
        "initial_knobs": bad_knobs,
        "hand_knobs": hand_knobs,
        "final_knobs": final,
        "service_estimate_before_s": round(est_before, 4),
        "service_estimate_after_s": round(est_after, 4),
        "slo_breaching": ev.breaching(),
        "decisions_total": _metric_total(
            fleet.metrics, "autotune_decisions_total"
        ),
        "reverts_total": _metric_total(
            fleet.metrics, "autotune_reverts_total"
        ),
        "decision_log": log,
        "decision_log_path": dump_path,
        "knobs": knobs.snapshot(),
    }


def _bench_rollout(smoke: bool) -> None:
    """``--rollout``: chaos-proving zero-downtime weight rollout.

    A 2-replica in-process fleet behind the health-routing router
    serves SUSTAINED streaming load while K successive weight versions
    roll through the :class:`RolloutController` (per-seat drain →
    between-block swap → re-warm → readiness-gated rejoin). The
    committed artifact asserts the acceptance contract directly:

    - **zero dropped or hung requests** — every stream started during
      the run resolves as ok or a typed shed (worker joins bound it;
      non-shed errors fail the bench),
    - **admitted p99 within the deadline budget** throughout the
      rollouts (every request carries ``deadline_s``; admitted =
      not shed at admission),
    - **every completion stamped with a coherent weights version** —
      a stamp from the published set, with the post-rollout tail
      entirely on the final version.

    Artifact: ``benchmarks/results/rollout_<backend>[_smoke].json``.
    """
    import threading as _threading

    import jax
    import jax.numpy as jnp
    import numpy as _np

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.slo import SLOEvaluator, router_slos
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet
    from tensorflowonspark_tpu.serving.rollout import RolloutController
    from tensorflowonspark_tpu.serving.router import FleetRouter

    ns = argparse.Namespace(
        batch_size=2 if smoke else 4,
        seq=16 if smoke else 64,
        new_tokens=8 if smoke else 32,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    rng = jax.random.PRNGKey(0)
    base_params = jax.tree.map(
        jax.device_put,
        model.init(rng, jnp.asarray(prompts[:2]))["params"],
    )
    n_versions = 2 if smoke else 3
    deadline_s = 60.0 if smoke else 120.0
    n_workers = 4
    versions = {}
    for k in range(1, n_versions + 1):
        vp = model.init(
            jax.random.PRNGKey(k), jnp.asarray(prompts[:2])
        )["params"]
        versions[f"v{k}"] = jax.tree.map(_np.asarray, vp)
    published = {"v0", *versions}

    def factory():
        return ContinuousBatcher(
            model,
            base_params,
            slots=b,
            prompt_widths=(prompts.shape[1],),
        )

    fleet = ServingFleet(
        factory=factory,
        replicas=2,
        probe_interval=0.5,
        warmup=False,
        drain_timeout=30.0,
    )
    router = FleetRouter(fleet)
    ctl = RolloutController(
        fleet, drain_timeout=60.0, verify_timeout=120.0
    )
    # the SLO budget gate (obs.slo): windowed history over the whole
    # run; the latency objective IS the deadline budget, so "admitted
    # p99 within deadline" and the declarative SLO agree by design
    hist = History(source="bench.rollout")
    slo_ev = SLOEvaluator(
        router_slos(latency_objective_s=deadline_s),
        hist,
        registry=fleet.metrics,
    )
    results: dict[int, tuple] = {}
    stop_load = _threading.Event()
    phase = {"current": "v0"}  # version being served when issued

    def load_worker(widx: int) -> None:
        n = 0
        while not stop_load.is_set():
            key = widx * 1_000_000 + n
            n += 1
            t0 = time.perf_counter()
            try:
                s = router.stream(
                    prompts[key % len(prompts)].tolist(),
                    new_tokens,
                    deadline_s=deadline_s,
                )
                toks = list(s)
                results[key] = (
                    "ok",
                    time.perf_counter() - t0,
                    s.weights_version,
                    len(toks),
                    phase["current"],
                )
            except BaseException as e:  # noqa: BLE001 - the verdict
                results[key] = (
                    "err",
                    time.perf_counter() - t0,
                    type(e).__name__,
                    0,
                    phase["current"],
                )
            time.sleep(0.01)

    workers = [
        _threading.Thread(target=load_worker, args=(i,), daemon=True)
        for i in range(n_workers)
    ]
    t_start = time.perf_counter()
    for t in workers:
        t.start()
    time.sleep(1.0)
    outcomes = []
    for k in range(1, n_versions + 1):
        ver = f"v{k}"
        out = ctl.publish(versions[ver], version=ver)
        outcomes.append({"version": ver, "outcome": out})
        phase["current"] = ver
        hist.scrape_registry(fleet.metrics)
        time.sleep(0.5)  # serve a beat between versions
    time.sleep(1.0)  # post-rollout tail on the final version
    stop_load.set()
    hung = 0
    for t in workers:
        t.join(timeout=max(120.0, deadline_s + 60.0))
        if t.is_alive():
            hung += 1
    wall_s = time.perf_counter() - t_start
    hist.scrape_registry(fleet.metrics)
    slo_verdicts = slo_ev.evaluate()
    router.close()

    oks = [v for v in results.values() if v[0] == "ok"]
    errs = [v for v in results.values() if v[0] == "err"]
    sheds = [
        v
        for v in errs
        if v[2] in ("FleetOverloaded", "FleetUnavailable")
    ]
    hard_errors = [v for v in errs if v not in sheds]
    latencies = sorted(v[1] for v in oks)
    p99 = (
        latencies[max(0, int(len(latencies) * 0.99) - 1)]
        if latencies
        else float("inf")
    )
    version_counts: dict[str, int] = {}
    bad_stamps = 0
    for v in oks:
        stamp = v[2]
        version_counts[stamp] = version_counts.get(stamp, 0) + 1
        if stamp not in published:
            bad_stamps += 1
    final_ver = f"v{n_versions}"
    tail_ok = [v for v in oks if v[4] == final_ver]
    tail_on_final = sum(1 for v in tail_ok if v[2] == final_ver)
    checks = {
        "zero_dropped_or_hung": hung == 0 and not hard_errors,
        "all_rollouts_completed": all(
            o["outcome"] == "completed" for o in outcomes
        ),
        "admitted_p99_within_deadline": p99 <= deadline_s,
        "every_completion_version_stamped": bad_stamps == 0
        and all(v[2] is not None for v in oks),
        "tail_serves_final_version": (
            tail_ok and tail_on_final == len(tail_ok)
        )
        or not tail_ok,
        # the declarative gate: rollouts must not burn the fleet's
        # latency budget (availability verdicts are reported below but
        # not gated — transient drain sheds are the tolerated cost)
        "slo_latency_silent": not any(
            v.slo == "fleet_latency" and v.breached for v in slo_verdicts
        ),
    }
    result = {
        "metric": "rollout_zero_downtime",
        "value": float(len(oks)),
        "unit": "requests",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "passed": all(checks.values()),
        "checks": checks,
        "versions_rolled": n_versions,
        "rollouts": outcomes,
        "requests_ok": len(oks),
        "requests_shed": len(sheds),
        "requests_hard_errors": len(hard_errors),
        "hung_workers": hung,
        "admitted_p99_s": round(p99, 3),
        "deadline_budget_s": deadline_s,
        "version_counts": version_counts,
        "slo": [v.as_dict() for v in slo_verdicts],
        "rollout_stats": ctl.stats(),
        "wall_s": round(wall_s, 1),
        "replicas": 2,
        "new_tokens": new_tokens,
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"rollout_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)
    if not all(checks.values()):
        raise SystemExit(
            f"rollout bench failed acceptance checks: "
            f"{ {k: v for k, v in checks.items() if not v} }"
        )


def _bench_online(smoke: bool) -> None:
    """``--online``: close the continual-training loop on live traffic.

    A 2-replica in-process fleet serves sustained streaming load; every
    completed request is appended to a crash-safe
    :class:`~tensorflowonspark_tpu.feed.livelog.TrafficLog` stamped
    with the ``weights_version`` that generated it. The driver-side
    :class:`~tensorflowonspark_tpu.online.OnlineLoop` discovers each
    sealed segment and hands it to a trainer, which folds the logged
    records into a new weights version and publishes it through the
    :class:`RolloutController` — so the fleet hot-swaps to weights
    trained on its OWN live traffic, mid-run, K times. The committed
    artifact asserts the loop's acceptance contract:

    - **generation measurably shifts toward fresh data**: the share of
      completions stamped with a live-trained version goes from 0
      before the first cycle to ~1.0 in the tail;
    - **zero requests dropped**: no hard errors or hung workers on the
      serve path, and zero traffic-log records dropped
      (``online_records_dropped_total`` stays 0 — the log never
      blocks or loses the serve path's data);
    - **serve p99 within the SLO budget** throughout the in-loop
      rollouts (the same declarative ``router_slos`` gate the rollout
      bench uses);
    - **the loop stays healthy**: every cycle trains on nonzero fresh
      records, no stall events, final data age within the freshness
      objective.

    Artifact: ``benchmarks/results/online_<backend>[_smoke].json``.
    """
    import tempfile as _tempfile
    import threading as _threading

    import jax
    import jax.numpy as jnp
    import numpy as _np

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.feed.livelog import (
        TrafficLog,
        decode_records,
        metrics as livelog_metrics,
    )
    from tensorflowonspark_tpu.feed.manifest import read_manifest
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.slo import SLOEvaluator, router_slos
    from tensorflowonspark_tpu.online import OnlineLoop
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet
    from tensorflowonspark_tpu.serving.rollout import RolloutController
    from tensorflowonspark_tpu.serving.router import FleetRouter

    ns = argparse.Namespace(
        batch_size=2 if smoke else 4,
        seq=16 if smoke else 64,
        new_tokens=8 if smoke else 32,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    rng = jax.random.PRNGKey(0)
    base_params = jax.tree.map(
        jax.device_put,
        model.init(rng, jnp.asarray(prompts[:2]))["params"],
    )
    n_cycles = 2 if smoke else 3
    deadline_s = 60.0 if smoke else 120.0
    freshness_objective_s = 30.0
    n_workers = 4

    def factory():
        return ContinuousBatcher(
            model,
            base_params,
            slots=b,
            prompt_widths=(prompts.shape[1],),
        )

    fleet = ServingFleet(
        factory=factory,
        replicas=2,
        probe_interval=0.5,
        warmup=False,
        drain_timeout=30.0,
    )
    router = FleetRouter(fleet)
    ctl = RolloutController(
        fleet, drain_timeout=60.0, verify_timeout=120.0
    )
    hist = History(source="bench.online")
    slo_ev = SLOEvaluator(
        router_slos(latency_objective_s=deadline_s),
        hist,
        registry=fleet.metrics,
    )

    # the live traffic log the serve path feeds (small rotation so
    # segments seal within each beat) and the loop that grows the
    # "training run" — here a stub cluster whose appended shards feed
    # the in-process trainer below
    log_root = _tempfile.mkdtemp(prefix="tfos-online-bench-")
    traffic = TrafficLog(
        log_root,
        rotate_records=16 if smoke else 64,
        frame_records=8,
    )

    class _BenchCluster:
        def __init__(self):
            self.pending: list = []
            self.lock = _threading.Lock()

        def extend_shards(self, files):
            with self.lock:
                self.pending.extend(files)

        def take(self):
            with self.lock:
                out, self.pending = self.pending, []
            return out

    cluster = _BenchCluster()
    progress = {"v": "v0"}
    loop = OnlineLoop(
        cluster,
        log_root,
        progress_fn=lambda: progress["v"],
        stall_after_s=60.0,
        freshness_objective_s=freshness_objective_s,
    )

    results: dict[int, tuple] = {}
    stop_load = _threading.Event()
    phase = {"current": "v0"}

    def load_worker(widx: int) -> None:
        n = 0
        while not stop_load.is_set():
            key = widx * 1_000_000 + n
            n += 1
            t0 = time.perf_counter()
            prompt = prompts[key % len(prompts)].tolist()
            try:
                s = router.stream(prompt, new_tokens, deadline_s=deadline_s)
                toks = list(s)
                results[key] = (
                    "ok",
                    time.perf_counter() - t0,
                    s.weights_version,
                    len(toks),
                    phase["current"],
                )
                # the serve path's write into the loop: stamped with
                # the version that generated the completion
                traffic.append(
                    prompt,
                    toks,
                    outcome=1.0,
                    weights_version=s.weights_version,
                    trace_id=f"r{key}",
                )
            except BaseException as e:  # noqa: BLE001 - the verdict
                results[key] = (
                    "err",
                    time.perf_counter() - t0,
                    type(e).__name__,
                    0,
                    phase["current"],
                )
            time.sleep(0.01)

    workers = [
        _threading.Thread(target=load_worker, args=(i,), daemon=True)
        for i in range(n_workers)
    ]
    # pay the jit compile before the timed beats: the cycles below
    # measure the loop, not XLA's first-touch latency
    for _ in range(2):
        list(router.stream(prompts[0].tolist(), new_tokens,
                           deadline_s=deadline_s))
    t_start = time.perf_counter()
    for t in workers:
        t.start()

    published = {"v0"}
    cycles = []
    consumed_total = 0
    for k in range(1, n_cycles + 1):
        time.sleep(1.0)  # serve a beat: traffic accumulates
        traffic.rotate()  # seal what the beat logged
        step = loop.step()  # discover + extend (the growing dataset)
        shards = cluster.take()
        # the "trainer": fold the freshly logged records into a new
        # weights version — a convex step from the served params toward
        # a data-derived target, so the published weights demonstrably
        # depend on the live traffic just consumed
        records = []
        for fm in shards:
            records.extend(decode_records(read_manifest(fm)))
        consumed_total += len(records)
        ver = f"live{k}"
        if records:
            seed = sum(int(r["completion"][0]) for r in records if
                       len(r["completion"])) + len(records)
            target = model.init(
                jax.random.PRNGKey(seed % (2**31)),
                jnp.asarray(prompts[:2]),
            )["params"]
            w = 0.1
            new_params = jax.tree.map(
                lambda a, t: _np.asarray((1.0 - w) * a + w * t),
                base_params,
                target,
            )
            out = ctl.publish(new_params, version=ver)
            published.add(ver)
            progress["v"] = ver
            phase["current"] = ver
        else:
            out = "skipped_no_records"
        hist.scrape_registry(fleet.metrics)
        after = loop.step()  # observe the publish: loop lag resets
        cycles.append(
            {
                "cycle": k,
                "version": ver,
                "rollout_outcome": out,
                "discovered": step["discovered"],
                "records_consumed": len(records),
                "data_age_s": round(after["data_age_s"], 3),
                "loop_lag_s": round(after["loop_lag_s"], 3),
            }
        )
    time.sleep(1.0)  # tail: the loop's final version serves
    stop_load.set()
    hung = 0
    for t in workers:
        t.join(timeout=max(120.0, deadline_s + 60.0))
        if t.is_alive():
            hung += 1
    wall_s = time.perf_counter() - t_start
    final_step = loop.step()
    hist.scrape_registry(fleet.metrics)
    slo_verdicts = slo_ev.evaluate()
    router.close()
    traffic.close()

    oks = [v for v in results.values() if v[0] == "ok"]
    errs = [v for v in results.values() if v[0] == "err"]
    sheds = [
        v for v in errs if v[2] in ("FleetOverloaded", "FleetUnavailable")
    ]
    hard_errors = [v for v in errs if v not in sheds]
    latencies = sorted(v[1] for v in oks)
    p99 = (
        latencies[max(0, int(len(latencies) * 0.99) - 1)]
        if latencies
        else float("inf")
    )
    live_versions = {v for v in published if v.startswith("live")}
    early = [v for v in oks if v[4] == "v0"]
    late = [v for v in oks if v[4] == f"live{n_cycles}"]
    early_fresh = sum(1 for v in early if v[2] in live_versions)
    late_fresh = sum(1 for v in late if v[2] in live_versions)
    early_share = early_fresh / len(early) if early else 0.0
    late_share = late_fresh / len(late) if late else 0.0
    dropped = sum(
        livelog_metrics()["dropped"].value(reason=r)
        for r in ("failpoint", "io_error", "closed", "disk_budget")
    )
    stats = loop.stats()
    checks = {
        # the loop's point: the served generation shifts onto weights
        # trained from the live traffic mid-run
        "freshness_shift": late_share >= 0.9 and late_share > early_share,
        "zero_dropped_or_hung": hung == 0 and not hard_errors,
        "zero_log_records_dropped": dropped == 0,
        "all_rollouts_completed": all(
            c["rollout_outcome"] == "completed" for c in cycles
        ),
        "every_cycle_trained_fresh_records": all(
            c["records_consumed"] > 0 for c in cycles
        ),
        "admitted_p99_within_deadline": p99 <= deadline_s,
        "slo_latency_silent": not any(
            v.slo == "fleet_latency" and v.breached for v in slo_verdicts
        ),
        "no_stalls": stats["stalls"] == 0,
        "final_data_age_within_objective": (
            final_step["data_age_s"] <= freshness_objective_s
        ),
    }
    result = {
        "metric": "online_continual_loop",
        "value": float(consumed_total),
        "unit": "records_trained",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "passed": all(checks.values()),
        "checks": checks,
        "cycles": cycles,
        "requests_ok": len(oks),
        "requests_shed": len(sheds),
        "requests_hard_errors": len(hard_errors),
        "hung_workers": hung,
        "admitted_p99_s": round(p99, 3),
        "deadline_budget_s": deadline_s,
        "fresh_share_early": round(early_share, 3),
        "fresh_share_late": round(late_share, 3),
        "records_trained": consumed_total,
        "log_records_dropped": int(dropped),
        "loop_stats": stats,
        "slo": [v.as_dict() for v in slo_verdicts],
        "rollout_stats": ctl.stats(),
        "wall_s": round(wall_s, 1),
        "replicas": 2,
        "new_tokens": new_tokens,
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"online_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)
    if not all(checks.values()):
        raise SystemExit(
            f"online bench failed acceptance checks: "
            f"{ {k: v for k, v in checks.items() if not v} }"
        )


def _bench_serve_slo(smoke: bool) -> None:
    """``--serve-slo``: the end-to-end trace + SLO burn proof (ISSUE 16).

    A 2-replica in-process fleet behind the health-routing router runs
    two legs against ONE History + SLO evaluator:

    - **clean leg**: requests well inside the latency objective — the
      evaluator must stay silent (no false burn at baseline);
    - **armed leg**: ``fleet.dispatch`` drops the proof request's first
      dispatch (a forced failover hop) while ``engine.submit`` delays
      it past the objective, then a latency failpoint slows the rest of
      the leg — the fleet_latency SLO must fire exactly here, with the
      availability SLO (no sheds) still silent.

    The proof request is traced end-to-end: the committed artifact
    asserts one trace id spans router placement -> failover hop ->
    replica -> engine segments with >= 95% of its wall time attributed
    to named segments, and that the timeline round-trips through
    ``obs.trace_merge``. Artifact:
    ``benchmarks/results/serve_slo_<backend>[_smoke].json``.
    """
    import threading as _threading

    import jax
    import jax.numpy as jnp

    from benchmarks.real_chip import _llama1b_decode_setup
    from tensorflowonspark_tpu.obs import reqtrace, trace_merge
    from tensorflowonspark_tpu.obs.history import History
    from tensorflowonspark_tpu.obs.slo import SLOEvaluator, router_slos
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.serving.fleet import ServingFleet
    from tensorflowonspark_tpu.serving.router import FleetRouter
    from tensorflowonspark_tpu.utils import failpoints

    ns = argparse.Namespace(
        batch_size=2 if smoke else 4,
        seq=16 if smoke else 64,
        new_tokens=8 if smoke else 32,
        spec_k=0,
        model_scale="tiny" if smoke else "1b",
        kv_quantize=False,
    )
    if smoke:
        _partial["smoke"] = True
    b, new_tokens, cfg, model, prompts = _llama1b_decode_setup(ns)
    params = jax.tree.map(
        jax.device_put,
        model.init(
            jax.random.PRNGKey(0), jnp.asarray(prompts[:2])
        )["params"],
    )
    # retain EVERY finished trace: the proof below reads the ring back
    ring = reqtrace.install(capacity=64, sample_every=1)

    def factory():
        return ContinuousBatcher(
            model,
            params,
            slots=b,
            prompt_widths=(prompts.shape[1],),
        )

    fleet = ServingFleet(
        factory=factory,
        replicas=2,
        probe_interval=0.5,
        warmup=False,
        drain_timeout=10.0,
    )
    router = FleetRouter(fleet)
    objective_s = 1.0  # a bucket edge: fraction_le needs no interpolation
    delay_s = 1.6  # past the objective, inside the next bucket
    history = History(source="bench.serve_slo")
    ev = SLOEvaluator(
        router_slos(
            latency_objective_s=objective_s,
            latency_budget=0.1,
            shed_budget=0.02,
            fast_burn=5.0,  # breach at >= 50% of requests slow (fast)
            slow_burn=2.5,  # and >= 25% over the slow window
        ),
        history,
        registry=fleet.metrics,
    )
    clean_n, armed_n = (4, 5) if smoke else (8, 10)

    def fire(count: int, tag: int, trace: str | None = None) -> None:
        errors: list = []

        def one(i):
            try:
                router.submit(
                    prompts[(tag + i) % len(prompts)].tolist(),
                    new_tokens,
                    **({"trace": trace} if trace and i == 0 else {}),
                )
            except BaseException as e:  # noqa: BLE001 - ferried
                errors.append(e)

        threads = [
            _threading.Thread(target=one, args=(i,))
            for i in range(count)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    try:
        fire(2 * b, tag=0)  # compile/warm both replicas
        # consume the warmup's registry window so the evaluator's first
        # scrape delta covers exactly the clean leg, not the compiles
        fleet.metrics.window()

        fire(clean_n, tag=100)
        history.scrape_registry(fleet.metrics)
        clean_verdicts = ev.evaluate()

        # -- armed leg: proof request takes a forced failover hop AND
        # the latency delay; the rest of the leg is just slow ---------
        proof_tid = reqtrace.mint(route="bench.proof")
        t_proof = time.perf_counter()
        failpoints.arm("fleet.dispatch", "drop", count=1)
        failpoints.arm("engine.submit", "delay", delay_s=delay_s, count=1)
        fire(1, tag=200, trace=proof_tid)
        proof_wall = time.perf_counter() - t_proof
        reqtrace.finish(proof_tid, outcome="ok")
        failpoints.arm(
            "fleet.dispatch", "delay", delay_s=delay_s, count=armed_n
        )
        fire(armed_n - 1, tag=300)
        failpoints.disarm_all()
        history.scrape_registry(fleet.metrics)
        armed_verdicts = ev.evaluate()
        # one more scrape so the breach counter + burn gauges the
        # evaluation just wrote are themselves in the windowed history
        history.scrape_registry(fleet.metrics)
    finally:
        failpoints.disarm_all()
        router.close()

    # -- the trace proof ----------------------------------------------
    attribution = ring.attribution(proof_tid) or {}
    record = reqtrace.get_record(proof_tid) or {}
    seg_names = {s["name"] for s in record.get("segments", ())}
    ev_names = {e["name"] for e in record.get("events", ())}
    merged_events = 0
    trace_path = os.path.join(
        _results_dir(), "serve_slo_proof_trace.json"
    )
    chrome = reqtrace.to_chrome(proof_tid)
    if chrome is not None:
        os.makedirs(os.path.dirname(trace_path), exist_ok=True)
        with open(trace_path, "w", encoding="utf-8") as f:
            json.dump(chrome, f)
        merged_events = len(
            trace_merge.merge_traces([trace_path]).get("traceEvents") or []
        )

    clean_breached = sorted(v.slo for v in clean_verdicts if v.breached)
    armed_breached = sorted(v.slo for v in armed_verdicts if v.breached)
    checks = {
        "clean_leg_silent": not clean_breached,
        "armed_leg_fires_latency_slo": armed_breached == ["fleet_latency"],
        "breach_is_rising_edge_once": history.delta(
            "slo_breaches_total", window_s=None
        ) == 1.0,
        "proof_trace_retained": proof_tid in ring.ids(),
        "proof_spans_router_to_engine": (
            "router.submit" in seg_names
            and any(n.startswith("engine.") for n in seg_names)
            and "router.failover" in ev_names
        ),
        "proof_attribution_ge_95pct": (
            attribution.get("covered_fraction", 0.0) >= 0.95
        ),
        "proof_slower_than_objective": proof_wall >= objective_s,
        "timeline_merges": merged_events > 0,
    }
    result = {
        "metric": "serve_slo_burn_gate",
        "value": 1.0 if all(checks.values()) else 0.0,
        "unit": "pass",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "passed": all(checks.values()),
        "checks": checks,
        "objective_s": objective_s,
        "armed_delay_s": delay_s,
        "requests_clean": clean_n,
        "requests_armed": armed_n,
        "proof_trace_id": proof_tid,
        "proof_wall_s": round(proof_wall, 3),
        "attribution": attribution,
        "slo_clean": [v.as_dict() for v in clean_verdicts],
        "slo_armed": [v.as_dict() for v in armed_verdicts],
        "reqtrace": ring.stats(),
        "history": history.to_artifact(
            names=(
                "router_request_seconds",
                "router_requests_total",
                "router_shed_total",
                "slo_burn_rate",
                "slo_breaches_total",
            )
        ),
        "merged_trace_events": merged_events,
        **_partial,
    }
    path = os.path.join(
        _results_dir(),
        f"serve_slo_{jax.default_backend()}"
        + ("_smoke" if smoke else "")
        + ".json",
    )
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        result["artifact"] = path
    except OSError as e:
        result["artifact_error"] = str(e)
    _emit(result)
    if not all(checks.values()):
        raise SystemExit(
            f"serve-slo bench failed acceptance checks: "
            f"{ {k: v for k, v in checks.items() if not v} }"
        )


def _setup_trace(backend: str) -> str | None:
    """Point real_chip's post-timing profile hook at a scratch dir;
    returns the dir, or None (with a stderr warning) when tracing is
    unavailable on this backend."""
    import sys
    import tempfile

    if backend != "tpu" and not os.environ.get("BENCH_TRACE_CPU"):
        print(
            f"bench: --trace is a no-op on the {backend!r} backend "
            "(no device timeline to attribute); set BENCH_TRACE_CPU=1 "
            "to capture host lanes anyway",
            file=sys.stderr,
            flush=True,
        )
        return None
    from benchmarks import real_chip

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    real_chip._PROFILE_DIR = trace_dir
    return trace_dir


def _emit_trace_report(
    trace_dir: str, backend: str, smoke: bool, name: str = "llama1b"
) -> None:
    """Distill the captured trace into a committed artifact. A trace
    that was asked for and cannot be reduced fails the run: a scored
    line without its evidence is not a result. A smoke run writes a
    DISTINCT filename so it can never clobber the evidence artifact of
    the last real scored run. ``name`` prefixes the artifact
    (``llama1b`` for the MFU bench, ``serve`` for the serving bench) so
    each bench owns its own evidence file."""
    from tensorflowonspark_tpu.obs import trace_report

    repo = os.path.dirname(os.path.abspath(__file__))
    out = os.path.join(
        repo,
        _results_dir(),
        f"{name}_{backend}{'_smoke' if smoke else ''}_trace_report.json",
    )
    report = trace_report.write_report(trace_dir, out)
    att = report["attribution"]
    _partial["trace_report"] = (
        os.path.relpath(out, repo)
        if not os.environ.get("TFOS_BENCH_RESULTS_DIR")
        else out
    )
    _partial["trace_mxu_fraction"] = att["mxu_fraction"]
    _partial["trace_device_ms"] = round(att["device_total_us"] / 1e3, 1)
    _partial["trace_host_ms"] = round(att["host_total_us"] / 1e3, 1)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="bench")
    ap.add_argument(
        "--trace",
        dest="trace",
        action="store_true",
        default=None,
        help="capture a jax.profiler trace after the timed loop and "
        "commit a benchmarks/results/*_trace_report.json attribution "
        "artifact (default: on; a no-op warning on CPU backends)",
    )
    ap.add_argument(
        "--no-trace", dest="trace", action="store_false",
        help="skip the trace capture",
    )
    ap.add_argument(
        "--serve-fleet",
        action="store_true",
        help="measure serving-fleet saturation scaling: replicas=1 vs "
        "2 in-process continuous engines behind the health-routing "
        "FleetRouter, reporting the throughput ratio plus "
        "shed/failover counts, committed to "
        "benchmarks/results/serve_fleet_*.json (BENCH_SMOKE=1 for the "
        "tiny model)",
    )
    ap.add_argument(
        "--cache",
        action="store_true",
        help="prove the disaggregated read-through cache tier: a "
        "2-replica fleet under a shared-prefix workload with the "
        "fleet-global prefix L2 on vs off (cold-replica first-request "
        "speedup + cross-replica L2 hits > 0), plus two concurrent "
        "columnar readers sharing one CacheTier (backing reads ~1x "
        "the dataset, not per-reader), committed to "
        "benchmarks/results/cache_*.json (BENCH_SMOKE=1 for the tiny "
        "model)",
    )
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="prove feedback-controlled knob recovery: the mnist feed "
        "pipeline at prefetch depth 1 and a continuous-batching fleet "
        "at decode_block=1/pipeline_depth=1 each hand their knobs to "
        "an autotune Controller, which must recover >= 90% of the "
        "hand-tuned throughput (with every move a flight-recorder "
        "event and at least one audited revert), committed to "
        "benchmarks/results/autotune_*.json (BENCH_SMOKE=1 for the "
        "tiny model)",
    )
    ap.add_argument(
        "--zero",
        nargs="?",
        const="on,off",
        default=None,
        metavar="on,off",
        help="run the cross-replica sharded weight-update A/B instead "
        "of the headline bench: the llama train step at fixed batch on "
        "a pure data-parallel mesh with zero_sharding on vs off, "
        "committing benchmarks/results/zero_weight_update*.json "
        "(step_time_ms, MFU on TPU, optimizer-span ms per leg; "
        "BENCH_SMOKE=1 for the tiny model + params byte-identity hash)",
    )
    ap.add_argument(
        "--rollout",
        action="store_true",
        help="chaos-prove zero-downtime weight rollout: a 2-replica "
        "fleet serves sustained streaming load while K successive "
        "versions hot-swap through the RolloutController; the "
        "committed benchmarks/results/rollout_*.json asserts zero "
        "dropped/hung requests, admitted p99 within the deadline "
        "budget, and coherent per-completion version stamps "
        "(BENCH_SMOKE=1 for the tiny model)",
    )
    ap.add_argument(
        "--online",
        action="store_true",
        help="close the continual-training loop on live traffic: a "
        "2-replica fleet's completions feed a crash-safe TrafficLog, "
        "the online loop discovers sealed segments and a trainer folds "
        "them into new weights versions that hot-swap mid-run; the "
        "committed benchmarks/results/online_*.json asserts the served "
        "generation shifts onto live-trained weights with zero dropped "
        "requests or log records and p99 within the SLO budget "
        "(BENCH_SMOKE=1 for the tiny model)",
    )
    ap.add_argument(
        "--serve-slo",
        action="store_true",
        help="end-to-end trace + SLO burn proof: a 2-replica fleet "
        "runs a clean leg then a failpoint-armed leg (one forced "
        "failover hop + a latency delay) against one History-backed "
        "SLO evaluator; the committed benchmarks/results/serve_slo_*"
        ".json asserts the latency SLO fires exactly on the armed leg "
        "and that the proof request's trace attributes >= 95% of its "
        "wall time to named router/engine segments (BENCH_SMOKE=1 for "
        "the tiny model)",
    )
    ap.add_argument(
        "--serve",
        action="store_true",
        help="measure the serving engine tax instead of training MFU: "
        "continuous-engine tokens/sec (pipeline_depth 1 and 2) vs raw "
        "single-stream generate on the same params, plus a committed "
        "benchmarks/results/serve_*_trace_report.json of the engine's "
        "host-side phase residual (BENCH_SMOKE=1 for the tiny model)",
    )
    args = ap.parse_args(argv)
    threading.Thread(target=_watchdog, daemon=True).start()

    import jax

    from benchmarks import peaks

    dev = peaks.bench_device("bench")
    _device.update(
        backend=dev.platform,
        device_kind=dev.device_kind,
        chips=len(jax.devices()),
    )

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if args.zero:
        legs = [leg.strip() for leg in args.zero.split(",") if leg.strip()]
        bad = [leg for leg in legs if leg not in ("on", "off")]
        if bad or not legs:
            ap.error(f"--zero legs must be 'on'/'off', got {bad or args.zero!r}")
        _bench_zero_ab(smoke, legs)
        return
    if args.cache:
        _bench_cache(smoke)
        return
    if args.autotune:
        _bench_autotune(smoke)
        return
    if args.serve_fleet:
        _bench_serve_fleet(smoke)
        return
    if args.rollout:
        _bench_rollout(smoke)
        return
    if args.online:
        _bench_online(smoke)
        return
    if args.serve_slo:
        _bench_serve_slo(smoke)
        return
    if args.serve:
        # the serving bench commits its own span-based trace report;
        # the jax.profiler MFU trace path doesn't apply here
        _bench_serve(smoke)
        return
    trace_dir = None
    # default-on applies to REAL runs only; a smoke run traces just when
    # asked (its tiny-model attribution is not scoring evidence)
    if args.trace is True or (args.trace is None and not smoke):
        trace_dir = _setup_trace(jax.default_backend())
    _bench_llama(smoke=smoke)  # headline first; a late wedge still reports
    if trace_dir is not None:
        _emit_trace_report(trace_dir, jax.default_backend(), smoke)
    _bench_mnist_feed(steps=5 if smoke else 40)

    mfu = _partial.pop("mfu_pct", None)
    _emit(
        {
            "metric": "llama1b_train_mfu",
            "value": round(mfu, 1) if mfu is not None else 0,
            "unit": "%",
            "vs_baseline": (
                round(mfu / (MFU_TARGET * 100), 3) if mfu is not None else 0.0
            ),
            **_partial,
        }
    )


if __name__ == "__main__":
    main()
