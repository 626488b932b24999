"""HTTP inference server over an AOT export artifact.

Extends the no-user-code inference surface (reference parity: the Scala
``TFModel`` batch API — SURVEY.md §2.2 — covered for batch by
``tools/run_model``) to an online endpoint: load the artifact once, then
serve JSON predictions. stdlib-only (``http.server``), threaded, one
model instance shared across requests (jit-compiled call is thread-safe
to invoke).

Endpoints::

    GET  /healthz            -> {"status": "ok", "export_dir": ...}
    GET  /metrics            -> Prometheus text-format metrics (the
                                process registry + the continuous
                                engine's counters/gauges/histograms)
    GET  /stats              -> scheduler JSON incl. per-phase request
                                latency percentiles (queue/prefill/
                                dispatch/fetch/sweep) backed by obs
                                spans, plus the overlap pipeline's
                                pipeline_depth / inflight_depth /
                                drain_stalls / overlap_hidden_ms
    GET  /statusz            -> SLO burn-rate verdicts (multi-window)
                                + windowed-history stats + trace-ring
                                stats; pumps the telemetry window on
                                demand so pollers see fresh verdicts
    GET  /debugz/traces      -> tail-sampled request-trace ring stats
                                + retained trace ids
    GET  /debugz/trace/<id>  -> one retained request timeline as a
                                Chrome trace (merge with node traces
                                via tools/trace_merge.py). Requests
                                adopt an ``X-TFOS-Trace`` header (or
                                mint an id); every JSON reply — 429/
                                503/504 included — echoes ``trace``
    GET  /signature          -> the artifact's signature metadata
    POST /predict            -> body {"rows": [<row>, ...]}
                                (rows as dicts per input_mapping, or raw
                                arrays for single-input models)
                                -> {"predictions": [...]}
    POST /generate           -> body {"prompts": [[token ids], ...]}
                                -> {"completions": [[token ids], ...]}
                                (``--llama-checkpoint`` mode; decode
                                params are fixed server-side at startup
                                so the jitted decode compiles ONCE for
                                one static (batch, width) shape).
                                Continuous engine adds per-request
                                ``deadline_s``: budget expiry answers
                                504; a watchdog abort answers 503 +
                                Retry-After (docs/ROBUSTNESS.md)
    POST /score              -> body {"sequences": [[token ids], ...]}
                                -> {"logprobs": [[float, ...], ...]}
                                (per-token next-token logprobs — the
                                eval-harness surface; one static
                                compile, same bucketing as /generate)
    POST /v1/completions     -> OpenAI-completions-shaped alias over the
                                same engine (``--gen-engine continuous``
                                required: the translation always sets
                                max_tokens). Token ids only — ``prompt``
                                is [ids] or [[ids], ...]; text prompts
                                and string stops are a 400 (tokenizers
                                are corpus-specific, out of framework
                                scope). Response: the standard
                                text_completion envelope with
                                ``choices[].tokens`` carrying the ids
                                (``text`` is empty — no tokenizer),
                                per-token sampled logprobs under
                                ``choices[].logprobs.token_logprobs``
                                when ``logprobs`` >= 1, finish_reason
                                stop|length, and usage counts. Errors
                                keep this server's ``{"error": str}``
                                shape.
    GET  /v1/models          -> single-model list (``--served-model-name``)
    POST /admin/reload       -> authenticated weight hot-swap (token
                                from --admin-token-file or
                                TFOS_ADMIN_TOKEN; 403 without one):
                                body {"version", "path", "kind"} loads
                                a published orbax checkpoint and swaps
                                it into the live engine(s) between
                                decode blocks — synchronous for a
                                single engine, 202 + rolling update in
                                fleet mode. ``--rollout-channel DIR``
                                instead watches a publication channel
                                (docs/SERVING.md "Rolling weight
                                updates")

Usage::

    python -m tensorflowonspark_tpu.tools.serve_model \
        --export-dir /models/mnist [--port 8500] [--batch-size 64]
    python -m tensorflowonspark_tpu.tools.serve_model \
        --llama-checkpoint ckpt/ --model tiny [--gen-width 128] \
        [--max-new-tokens 64] [--eos-id N] [--temperature 0.8 ...]
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

from tensorflowonspark_tpu.cluster import wire
from tensorflowonspark_tpu.obs import reqtrace
from tensorflowonspark_tpu.tools.run_model import _to_jsonable

logger = logging.getLogger(__name__)

# The server most recently started by main() — lets tooling and tests
# reach a CLI-started server (e.g. its ephemeral port under --port 0).
_last_server = None


class _Handler(BaseHTTPRequestHandler):
    # set by make_server():
    model: Any = None
    export_dir: str = ""
    batch_size: int = 64
    gen_fn: Any = None  # prompts -> completions (checkpoint mode)
    gen_batcher: Any = None  # _GenBatcher when --gen-batch-window > 0
    gen_engine: Any = None  # ContinuousBatcher (--gen-engine continuous)
    gen_max_new: int = 64  # per-request decode budget in engine mode
    score_fn: Any = None  # sequences -> per-token logprobs (/score)
    model_name: str = "default"  # /v1/models id + completion envelopes
    # zero-downtime weight rollout (docs/SERVING.md "Rolling weight
    # updates"): the RolloutController driving this server's engine(s),
    # and the shared secret gating POST /admin/reload (None = endpoint
    # disabled — hot-swapping weights is an operator-only surface)
    rollout_ctl: Any = None
    admin_token: str | None = None
    # request-level observability plane (docs/OBSERVABILITY.md):
    # the _ObsPlane pumping this server's registry into a windowed
    # History and evaluating SLO burn rates (/statusz); None = no
    # continuous engine to observe
    obs_plane: Any = None
    # the CURRENT request's trace id (adopted from X-TFOS-Trace or
    # minted at ingress); _reply stamps it into every JSON body so
    # error answers — 429/503/504 included — are trace-attributable
    _trace: str | None = None
    _last_code: int = 200
    # per-server lock (set in make_server): serializes jax dispatch on
    # one model while the HTTP layer stays threaded, so health checks
    # never queue behind a big batch
    predict_lock: threading.Lock

    def log_message(self, fmt, *fargs):  # route to logging, not stderr
        logger.info("%s " + fmt, self.client_address[0], *fargs)

    def _read_json_body(self):
        length = int(self.headers.get("Content-Length", 0))
        return json.loads(self.rfile.read(length) or b"{}")

    def _reply(
        self, code: int, payload: dict, headers: dict | None = None
    ) -> None:
        if self._trace is not None and "trace" not in payload:
            payload = {**payload, "trace": self._trace}
        self._reply_text(
            code, json.dumps(payload), "application/json", headers
        )

    def _reply_text(
        self,
        code: int,
        text: str,
        content_type: str,
        headers: dict | None = None,
    ) -> None:
        self._last_code = code
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self) -> None:  # noqa: N802 - http.server API
        self._trace = None
        if self.path in ("/healthz", "/readyz"):
            # Liveness vs readiness, SPLIT (docs/ROBUSTNESS.md "Serving
            # fleet"): live = the process/scheduler runs (restarting a
            # live server helps nobody); ready = route traffic here
            # (false during warmup and drain — a warmup stall must not
            # look wedged to a prober, and a draining server must fall
            # out of rotation without being killed). /healthz answers
            # 200 iff live, /readyz 200 iff ready; in fleet mode the
            # body carries the per-replica split too.
            h = {"live": True, "ready": True}
            if self.gen_engine is not None:
                try:
                    h = self.gen_engine.health()
                except Exception:  # noqa: BLE001 - a dead engine is a
                    # health verdict, not a 500
                    h = {"live": False, "ready": False}
            ok = h.get("live") if self.path == "/healthz" else h.get("ready")
            self._reply(
                200 if ok else 503,
                {
                    "status": "ok" if h.get("live") else "dead",
                    "export_dir": self.export_dir,
                    **h,
                },
            )
        elif self.path == "/signature" and self.model is not None:
            self._reply(200, self.model.meta)
        elif self.path == "/v1/models":
            # the OpenAI SDK's client.models.list() handshake — some
            # eval harnesses refuse to start without it
            self._reply(
                200,
                {
                    "object": "list",
                    "data": [
                        {
                            "id": self.model_name,
                            "object": "model",
                            "created": 0,
                            "owned_by": "tensorflowonspark_tpu",
                        }
                    ],
                },
            )
        elif self.path == "/metrics":
            # Prometheus text exposition: the process-global registry
            # (MetricsWriter mirrors, feed/train instrumentation) plus
            # the engine's per-instance registry when one is serving.
            from tensorflowonspark_tpu.obs import registry as obs_reg

            text = obs_reg.default_registry().render()
            if self.gen_engine is not None:
                text += self.gen_engine.metrics.render()
            self._reply_text(200, text, obs_reg.CONTENT_TYPE)
        elif self.path == "/stats":
            stats: dict = {"mode": "aot" if self.model is not None else ""}
            if self.gen_engine is not None:
                stats.update(
                    self.gen_engine.stats(),
                    mode=(
                        "fleet"
                        if getattr(self.gen_engine, "IS_FLEET", False)
                        else "continuous"
                    ),
                )
                if self.rollout_ctl is not None:
                    stats["rollout"] = self.rollout_ctl.stats()
            elif self.gen_batcher is not None:
                stats.update(
                    mode="coalesced",
                    decode_calls=self.gen_batcher.decode_calls,
                )
            elif self.gen_fn is not None:
                stats["mode"] = "fixed"
            self._reply(200, stats)
        elif self.path == "/statusz":
            # the SLO verdict surface: pump the windowed history NOW
            # (deterministic for pollers/tests — no waiting on the
            # background cadence) and report burn rates + breaches
            out: dict = {"export_dir": self.export_dir}
            if self.obs_plane is not None:
                try:
                    self.obs_plane.pump()
                    out.update(self.obs_plane.statusz())
                except Exception as e:  # noqa: BLE001 - a broken
                    # evaluator is a report, not a 500 — /statusz is
                    # what operators read DURING incidents
                    out["error"] = f"{type(e).__name__}: {e}"
            out["reqtrace"] = reqtrace.get_ring().stats()
            self._reply(200, out)
        elif self.path == "/debugz/traces":
            ring = reqtrace.get_ring()
            self._reply(200, {**ring.stats(), "trace_ids": ring.ids()})
        elif self.path.startswith("/debugz/trace/"):
            tid = self.path.rsplit("/", 1)[1]
            data = reqtrace.to_chrome(tid)
            if data is None:
                self._reply(
                    404,
                    {"error": f"no retained trace {tid!r} (unknown, "
                              "evicted, or not tail-sampled)"},
                )
            else:
                self._reply(200, data)
        else:
            self._reply(404, {"error": f"unknown path {self.path}"})

    def do_POST(self) -> None:  # noqa: N802 - http.server API
        self._trace = None
        if self.path == "/generate":
            self._do_generate()
            return
        if self.path == "/admin/reload":
            self._do_admin_reload()
            return
        if self.path == "/v1/completions":
            self._do_v1_completions()
            return
        if self.path == "/score":
            self._do_score()
            return
        if self.path != "/predict":
            self._reply(404, {"error": f"unknown path {self.path}"})
            return
        if self.model is None:
            self._reply(
                400, {"error": "server is in --llama-checkpoint mode; "
                      "POST /generate instead"}
            )
            return
        try:
            payload = self._read_json_body()
            rows = payload["rows"]
            if not isinstance(rows, list) or not rows:
                raise ValueError("'rows' must be a non-empty list")
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        try:
            with self.predict_lock:
                preds = self.model.transform(
                    rows, batch_size=self.batch_size
                )
        except Exception as e:  # noqa: BLE001 - ferried to the client
            logger.exception("prediction failed")
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        # outside the try: a client hanging up mid-response must not be
        # logged as a prediction failure nor answered with a second reply
        self._reply(200, {"predictions": [_to_jsonable(p) for p in preds]})

    def _do_admin_reload(self) -> None:
        """Authenticated hot weight swap (docs/SERVING.md "Rolling
        weight updates"). Body: ``{"version": ..., "path": <committed
        orbax checkpoint dir>, "kind": "full"|"lora", "step": N?}``.

        Single-engine mode answers SYNCHRONOUSLY once the swap,
        re-warm, and verification finished (this is the surface a
        fleet supervisor's ``SubprocessReplica.reload`` drives): 200
        on ``completed``, 409 on a shape/layout mismatch
        (``WeightsIncompatible`` — the caller triggers rollback), 500
        otherwise. Fleet mode (the router front-end) starts a rolling
        update in the background and answers 202 — rolling N replicas
        under drain is minutes, not an HTTP round trip."""
        import hmac

        if self.admin_token is None:
            self._reply(
                403,
                {"error": "admin endpoint disabled (no admin token "
                          "configured: set TFOS_ADMIN_TOKEN or "
                          "--admin-token-file)"},
            )
            return
        auth = self.headers.get("Authorization", "")
        token = (
            auth[len("Bearer "):]
            if auth.startswith("Bearer ")
            else self.headers.get("X-Admin-Token", "")
        )
        if not hmac.compare_digest(token, self.admin_token):
            self._reply(403, {"error": "invalid admin token"})
            return
        if self.rollout_ctl is None:
            self._reply(
                400,
                {"error": "/admin/reload requires --gen-engine "
                          "continuous"},
            )
            return
        from tensorflowonspark_tpu.serving.rollout import WeightsUpdate

        try:
            payload = self._read_json_body()
            update = WeightsUpdate(
                version=str(payload["version"]),
                kind=str(payload.get("kind") or "full"),
                path=str(payload["path"]),
                step=(
                    None
                    if payload.get("step") is None
                    else int(payload["step"])
                ),
            )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        # stamp the rollout onto every in-flight request's timeline:
        # a trace spanning the swap shows WHICH weights served it
        reqtrace.mark("admin.reload", version=update.version)
        ctl = self.rollout_ctl
        if getattr(self.gen_engine, "IS_FLEET", False):
            threading.Thread(
                target=ctl.roll, args=(update,), daemon=True,
                name="admin-rollout",
            ).start()
            self._reply(
                202,
                wire.encode(
                    "serve.reload", status="rolling",
                    version=update.version,
                ),
            )
            return
        t0 = time.monotonic()
        try:
            outcome = ctl.roll(update)
        except Exception as e:  # noqa: BLE001 - ferried to the caller
            logger.exception("admin reload crashed")
            self._reply(
                500,
                wire.encode(
                    "serve.error",
                    error=f"{type(e).__name__}: {e}",
                    error_type=type(e).__name__,
                ),
            )
            return
        if outcome == "completed":
            self._reply(
                200,
                wire.encode(
                    "serve.reload",
                    status="completed",
                    version=update.version,
                    swap_seconds=round(time.monotonic() - t0, 3),
                ),
            )
            return
        err = ctl.last_error or {}
        etype = err.get("type", "RolloutFailed")
        self._reply(
            409 if etype == "WeightsIncompatible" else 500,
            wire.encode(
                "serve.error",
                error=(
                    f"rollout {outcome}: "
                    f"{err.get('error', 'unknown failure')}"
                ),
                error_type=etype,
                outcome=outcome,
            ),
        )

    def _do_score(self) -> None:
        if self.score_fn is None:
            self._reply(
                400, {"error": "server was not started with "
                      "--llama-checkpoint; /score unavailable"}
            )
            return
        from tensorflowonspark_tpu.tools.generate_text import PromptError

        try:
            payload = self._read_json_body()
            seqs = payload["sequences"]
            if not isinstance(seqs, list):
                raise ValueError("'sequences' must be a list")
            seqs = [[int(t) for t in s] for s in seqs]
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        try:
            with self.predict_lock:
                logprobs = self.score_fn(seqs)
        except PromptError as e:
            self._reply(400, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - server-side; log + 500
            logger.exception("scoring failed")
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        self._reply(200, {"logprobs": logprobs})

    def _do_v1_completions(self) -> None:
        """OpenAI /v1/completions alias: translate the request into the
        native /generate schema and run the shared path, then wrap the
        result in the text_completion envelope."""
        try:
            raw = self._read_json_body()
            payload, meta = _openai_to_generate(raw, self.gen_max_new)
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        self._do_generate(payload=payload, v1_meta=meta)

    def _do_generate(self, payload=None, v1_meta=None) -> None:
        """Trace-owning ingress shell around :meth:`_generate_inner`:
        adopt the caller's ``X-TFOS-Trace`` id (a routed hop from a
        fleet parent — flagged ``propagated`` so the hop is always
        retrievable by the parent's tooling) or mint a fresh one, then
        stamp the terminal ``http.generate`` segment and finish the
        record with the HTTP outcome. Whoever BEGAN the trace finishes
        it — an in-process router/engine below us only appends."""
        hdr = self.headers.get(reqtrace.HEADER)
        tid, owned = reqtrace.ensure(hdr, route="http.generate")
        if tid is not None and hdr:
            reqtrace.flag(tid, propagated=True)
        self._trace = tid
        self._last_code = 200
        t0 = time.monotonic()
        try:
            self._generate_inner(payload, v1_meta, tid)
        except BaseException as e:
            reqtrace.flag(tid, error=type(e).__name__)
            if owned:
                reqtrace.finish(
                    tid, outcome="error", error=type(e).__name__
                )
            raise
        code = self._last_code
        reqtrace.segment(
            tid, "http.generate", time.monotonic() - t0
        )
        if code >= 400:
            reqtrace.flag(tid, http_error=code)
        if owned:
            reqtrace.finish(
                tid,
                outcome="ok" if code < 400 else "error",
                http_status=code,
            )

    def _generate_inner(self, payload=None, v1_meta=None, trace=None) -> None:
        if self.gen_fn is None and self.gen_engine is None:
            self._reply(
                400, {"error": "server was not started with "
                      "--llama-checkpoint; /generate unavailable"}
            )
            return
        try:
            if payload is None:
                payload = self._read_json_body()
            prompts = payload["prompts"]
            if not isinstance(prompts, list) or not prompts:
                raise ValueError("'prompts' must be a non-empty list")
            prompts = [[int(t) for t in p] for p in prompts]
            if any(not p for p in prompts):
                raise ValueError("prompts must be non-empty token lists")
            temperature = payload.get("temperature")
            max_new = payload.get("max_new_tokens")
            eos_id = payload.get("eos_id")
            adapter = payload.get("adapter")
            stop = payload.get("stop")
            n_samples = payload.get("n")
            req_top_k = payload.get("top_k")
            req_top_p = payload.get("top_p")
            req_seed = payload.get("seed")
            req_min_p = payload.get("min_p")
            req_fpen = payload.get("frequency_penalty")
            req_ppen = payload.get("presence_penalty")
            req_bias = payload.get("logit_bias")
            req_deadline = payload.get("deadline_s")
            want_logprobs = bool(payload.get("logprobs"))
            # rollout coherence surface: stamp each completion with the
            # weights version it resolved under (continuous engine only)
            want_versions = bool(payload.get("versions"))
            if (
                temperature is not None
                or max_new is not None
                or eos_id is not None
                or adapter is not None
                or stop is not None
                or n_samples is not None
                or req_top_k is not None
                or req_top_p is not None
                or req_seed is not None
                or req_min_p is not None
                or req_fpen is not None
                or req_ppen is not None
                or req_bias is not None
                or req_deadline is not None
                or want_logprobs
                or want_versions
            ) and self.gen_engine is None:
                raise ValueError(
                    "per-request temperature/max_new_tokens/eos_id/"
                    "adapter/stop/n/top_k/top_p/min_p/seed/penalties/"
                    "logprobs/deadline_s require --gen-engine "
                    "continuous (the fixed path bakes decode params "
                    "at startup)"
                )
            if temperature is not None:
                temperature = float(temperature)
            if max_new is not None:
                max_new = int(max_new)
                if not 1 <= max_new <= self.gen_max_new:
                    raise ValueError(
                        f"max_new_tokens must be in [1, "
                        f"{self.gen_max_new}] (the server's configured "
                        f"budget), got {max_new}"
                    )
            if eos_id is not None:
                eos_id = int(eos_id)
            if adapter is not None:
                adapter = int(adapter)
            if stop is not None:
                stop = [[int(t) for t in seq] for seq in stop]
            if req_top_k is not None:
                req_top_k = int(req_top_k)
            if req_top_p is not None:
                req_top_p = float(req_top_p)
            if req_seed is not None:
                req_seed = int(req_seed)
            if req_min_p is not None:
                req_min_p = float(req_min_p)
            if req_fpen is not None:
                req_fpen = float(req_fpen)
            if req_ppen is not None:
                req_ppen = float(req_ppen)
            if req_bias is not None:
                # OpenAI wire format: JSON object keys are strings
                req_bias = {
                    int(t): float(v) for t, v in dict(req_bias).items()
                }
            if req_deadline is not None:
                req_deadline = float(req_deadline)
            if n_samples is not None:
                n_samples = int(n_samples)
                if not 1 <= n_samples <= 16:
                    raise ValueError(
                        f"n must be in [1, 16], got {n_samples}"
                    )
                # EFFECTIVE temperature: the request value, else the
                # engine-wide default (--temperature); the engine
                # decodes any temp <= 0 greedily (_sample_rows selects
                # on temps > 0), which would return n identical rows
                eff_temp = (
                    temperature
                    if temperature is not None
                    else getattr(self.gen_engine, "_temperature", 0.0)
                )
                if n_samples > 1 and eff_temp <= 0:
                    raise ValueError(
                        "n > 1 with greedy decoding (effective "
                        "temperature <= 0) would return n identical "
                        "completions; set a temperature"
                    )
        except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        from tensorflowonspark_tpu.tools.generate_text import PromptError

        stream = bool(payload.get("stream"))
        if stream and self.gen_engine is None:
            self._reply(
                400,
                {"error": "streaming requires --gen-engine continuous"},
            )
            return
        if stream and len(prompts) != 1:
            self._reply(
                400, {"error": "streaming supports exactly one prompt"}
            )
            return
        if stream and (n_samples or 1) > 1:
            self._reply(
                400,
                {"error": "streaming supports exactly one completion "
                          "(n must be 1)"},
            )
            return
        if stream:
            self._engine_stream(
                prompts[0], temperature, max_new, eos_id, want_logprobs,
                adapter, stop, req_top_k, req_top_p, req_seed,
                req_min_p, req_fpen, req_ppen, req_bias, req_deadline,
                trace=trace,
            )
            return
        from tensorflowonspark_tpu.serving import (
            DeadlineExceeded,
            EngineOverloaded,
            EngineWedged,
            FleetOverloaded,
            FleetUnavailable,
            ReplicaGone,
        )

        logprobs = None
        versions = None
        try:
            if self.gen_engine is not None:
                try:
                    n = n_samples or 1
                    fan = [p for p in prompts for _ in range(n)]
                    completions = self._engine_generate(
                        fan, temperature, max_new, eos_id,
                        want_logprobs, adapter, stop, req_top_k,
                        req_top_p, req_seed, req_min_p, req_fpen,
                        req_ppen, req_bias, req_deadline,
                        want_versions, trace=trace,
                    )
                    versions = None
                    if want_versions:
                        *rest, versions = completions
                        completions = (
                            rest[0] if len(rest) == 1 else tuple(rest)
                        )
                    if want_logprobs:
                        completions, logprobs = completions
                    if n > 1 and v1_meta is None:
                        # regroup: completions[i] becomes the LIST of n
                        # samples for prompt i (documented shape change;
                        # the OpenAI envelope keeps the flat order —
                        # prompt 0's n samples, then prompt 1's, ...)
                        completions = [
                            completions[i * n : (i + 1) * n]
                            for i in range(len(prompts))
                        ]
                        if logprobs is not None:
                            logprobs = [
                                logprobs[i * n : (i + 1) * n]
                                for i in range(len(prompts))
                            ]
                        if versions is not None:
                            versions = [
                                versions[i * n : (i + 1) * n]
                                for i in range(len(prompts))
                            ]
                except FleetOverloaded as e:
                    # router admission shed: the deadline cannot be met
                    # from queue-depth estimates (or every queue is
                    # full) — tell the client WHEN to come back, and
                    # WHERE the number came from (the router's
                    # queue-depth/EWMA estimate, not a fixed backoff)
                    self._reply(
                        429,
                        wire.encode(
                            "serve.error", error=str(e),
                            error_type="FleetOverloaded",
                            retry_after_src="router_estimate",
                        ),
                        {"Retry-After": str(int(math.ceil(e.retry_after)))},
                    )
                    return
                except FleetUnavailable as e:
                    # full-fleet drain / no ready replica
                    self._reply(
                        503,
                        wire.encode(
                            "serve.error", error=str(e),
                            error_type="FleetUnavailable",
                            retry_after_src="static",
                        ),
                        {"Retry-After": "2"},
                    )
                    return
                except EngineOverloaded as e:
                    self._reply(
                        503,
                        wire.encode(
                            "serve.error", error=str(e),
                            error_type="EngineOverloaded",
                            retry_after_src="static",
                        ),
                        {"Retry-After": "1"},
                    )
                    return
                except DeadlineExceeded as e:
                    # the documented degradation contract: an expired
                    # per-request budget is a gateway-timeout class
                    # outcome, not a server defect
                    self._reply(
                        504,
                        wire.encode(
                            "serve.error", error=str(e),
                            error_type="DeadlineExceeded",
                        ),
                    )
                    return
                except (EngineWedged, ReplicaGone) as e:
                    # the watchdog aborted in-flight work (or the
                    # replica died and failover was already spent) and
                    # the fleet/engine keeps serving — a retryable
                    # unavailability, not a generic 500
                    self._reply(
                        503,
                        wire.encode(
                            "serve.error", error=str(e),
                            error_type=type(e).__name__,
                            retry_after_src="static",
                        ),
                        {"Retry-After": "1"},
                    )
                    return
                except ValueError as e:
                    # the engine's submit-side prompt validation (width/
                    # budget) — client fault, like PromptError below; a
                    # ValueError from the OTHER paths stays a 500 (it
                    # would be a server-side defect, not bad input)
                    self._reply(400, {"error": str(e)})
                    return
            elif self.gen_batcher is not None:
                # coalesced path: the batcher's worker serializes the
                # decode (and takes predict_lock itself)
                completions = self.gen_batcher.submit(prompts)
            else:
                with self.predict_lock:
                    completions = self.gen_fn(prompts)
        except PromptError as e:  # the caller's prompts are at fault
            self._reply(400, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001 - server-side; log + 500
            logger.exception("generation failed")
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
            return
        if v1_meta is not None:
            eff_max = (
                max_new if max_new is not None else self.gen_max_new
            )
            choices = []
            for i, comp in enumerate(completions):
                ch = {
                    "index": i,
                    # token-id server: no tokenizer to render text with;
                    # the ids ride in "tokens" (clients detokenize)
                    "text": "",
                    "tokens": comp,
                    "logprobs": None,
                    "finish_reason": (
                        "stop" if len(comp) < eff_max else "length"
                    ),
                }
                if logprobs is not None:
                    ch["logprobs"] = {
                        "tokens": comp,
                        "token_logprobs": logprobs[i],
                        "top_logprobs": None,
                        "text_offset": None,
                    }
                choices.append(ch)
            import uuid

            self._reply(
                200,
                {
                    "id": f"cmpl-{uuid.uuid4().hex}",
                    "object": "text_completion",
                    "created": int(time.time()),
                    "model": v1_meta["model"] or self.model_name,
                    "choices": choices,
                    "usage": {
                        "prompt_tokens": sum(len(p) for p in prompts),
                        "completion_tokens": sum(
                            len(c) for c in completions
                        ),
                        "total_tokens": sum(len(p) for p in prompts)
                        + sum(len(c) for c in completions),
                    },
                },
            )
            return
        kw: dict[str, Any] = {"completions": completions}
        if logprobs is not None:
            kw["logprobs"] = logprobs
        if versions is not None:
            kw["weights_versions"] = versions
        self._reply(200, wire.encode("serve.completion", **kw))

    def _engine_stream(
        self,
        prompt,
        temperature=None,
        max_new=None,
        eos_id=None,
        want_logprobs=False,
        adapter=None,
        stop=None,
        top_k=None,
        top_p=None,
        seed=None,
        min_p=None,
        frequency_penalty=None,
        presence_penalty=None,
        logit_bias=None,
        deadline_s=None,
        trace=None,
    ) -> None:
        """Stream one completion as newline-delimited JSON: a
        ``{"token": t}`` line per decoded token (one engine step of
        latency each), then a ``{"done": true, "completion": [...]}``
        trailer. The response is close-delimited (no Content-Length);
        a mid-stream failure surfaces as an ``{"error": ...}`` line
        since the 200 status is already on the wire."""
        from tensorflowonspark_tpu.serving import (
            EngineOverloaded,
            FleetOverloaded,
            FleetUnavailable,
            ReplicaGone,
        )

        try:
            gen = self.gen_engine.stream(
                prompt,
                max_new or self.gen_max_new,
                temperature=temperature,
                eos_id=eos_id,
                yield_logprobs=want_logprobs,
                adapter=adapter,
                stop=stop,
                top_k=top_k,
                top_p=top_p,
                seed=seed,
                min_p=min_p,
                frequency_penalty=frequency_penalty,
                presence_penalty=presence_penalty,
                logit_bias=logit_bias,
                deadline_s=deadline_s,
                trace=trace,
            )
        except FleetOverloaded as e:
            self._reply(
                429,
                wire.encode(
                    "serve.error", error=str(e),
                    error_type="FleetOverloaded",
                    retry_after_src="router_estimate",
                ),
                {"Retry-After": str(int(math.ceil(e.retry_after)))},
            )
            return
        except (FleetUnavailable, ReplicaGone) as e:
            self._reply(
                503,
                wire.encode(
                    "serve.error", error=str(e),
                    error_type=type(e).__name__,
                    retry_after_src="static",
                ),
                {"Retry-After": "2"},
            )
            return
        except EngineOverloaded as e:
            self._reply(
                503,
                wire.encode(
                    "serve.error", error=str(e),
                    error_type="EngineOverloaded",
                    retry_after_src="static",
                ),
                {"Retry-After": "1"},
            )
            return
        except ValueError as e:  # submit-side prompt validation
            self._reply(400, {"error": str(e)})
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Connection", "close")
        self.end_headers()
        out: list = []
        lps: list = []
        try:
            for item in gen:
                if want_logprobs:
                    t, lp = item
                    lps.append(lp)
                    line = wire.encode(
                        "serve.stream_chunk", token=t, logprob=lp
                    )
                else:
                    t = item
                    line = wire.encode("serve.stream_chunk", token=t)
                out.append(t)
                self.wfile.write(json.dumps(line).encode() + b"\n")
                self.wfile.flush()
            # the engine's result is the stop-TRIMMED completion (the
            # streamed tokens include any matched stop suffix); fall
            # back to the raw tokens if the iterator wasn't exhausted
            final = gen.result if gen.result is not None else out
            tkw: dict[str, Any] = {"done": True, "completion": final}
            if trace is not None:
                tkw["trace"] = trace
            if want_logprobs:
                tkw["logprobs"] = (
                    gen.logprobs if gen.result is not None else lps
                )
            wv = getattr(gen, "weights_version", None)
            if wv is not None:
                tkw["weights_version"] = wv
            trailer = wire.encode("serve.stream_trailer", **tkw)
            self.wfile.write(json.dumps(trailer).encode() + b"\n")
        except (BrokenPipeError, ConnectionResetError):
            logger.info("stream client disconnected")
        except Exception as e:  # noqa: BLE001 - status already sent
            logger.exception("stream failed mid-decode")
            reqtrace.flag(trace, error=type(e).__name__)
            try:
                ekw: dict[str, Any] = {
                    "error": f"{type(e).__name__}: {e}",
                    # typed so a fleet router fronting THIS server
                    # can reconstruct the engine error
                    "error_type": type(e).__name__,
                }
                if trace is not None:
                    # the 200 is long gone: the error TRAILER is the
                    # only place the stream's trace id can ride
                    ekw["trace"] = trace
                err_line = wire.encode("serve.stream_error", **ekw)
                self.wfile.write(
                    json.dumps(err_line).encode() + b"\n"
                )
            except OSError:
                pass
        finally:
            # Deterministic cancel on client disconnect (the primary
            # case this exists for) — don't lean on refcount GC of
            # `gen` to free the slot for a dead consumer.
            gen.close()

    def _engine_generate(
        self,
        prompts,
        temperature=None,
        max_new=None,
        eos_id=None,
        want_logprobs=False,
        adapter=None,
        stop=None,
        top_k=None,
        top_p=None,
        seed=None,
        min_p=None,
        frequency_penalty=None,
        presence_penalty=None,
        logit_bias=None,
        deadline_s=None,
        want_versions=False,
        trace=None,
    ):
        """Continuous-batching path: the request's rows are admitted
        ATOMICALLY (all accepted, or a 400/503 before any decodes — a
        partial admission would burn slots on work the erroring client
        discards), then decode concurrently, interleaved with other
        requests' rows — no convoying."""
        return self.gen_engine.submit_many(
            prompts,
            max_new or self.gen_max_new,
            temperature=temperature,
            eos_id=eos_id,
            return_logprobs=want_logprobs,
            adapter=adapter,
            stop=stop,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
            min_p=min_p,
            frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty,
            logit_bias=logit_bias,
            deadline_s=deadline_s,
            return_versions=want_versions,
            trace=trace,
        )


def _openai_to_generate(raw: Any, budget: int) -> tuple[dict, dict]:
    """Translate an OpenAI /v1/completions body into the native
    /generate schema (+ envelope metadata). Raises ValueError on
    malformed or unsupported fields; the caller replies 400.

    Token ids only: ``prompt`` is [ids] or [[ids], ...] and ``stop`` is
    [ids] or [[ids], ...] — text forms are rejected with an explanation
    (tokenizers are corpus-specific, out of framework scope; pipe
    through one client-side). ``max_tokens`` defaults to the OpenAI 16
    clamped to the server's decode ``budget`` (a request that omitted
    every optional field must not 400 on a small-budget server; an
    EXPLICIT over-budget or zero value still rides the existing [1, N]
    validation); ``temperature`` defaults to the OpenAI 1.0 (NOT the
    engine's startup default, which is typically greedy — a client that
    sent nothing must get OpenAI semantics). ``logprobs: N`` maps to
    the sampled token's logprob for any non-null N including 0 (top-N
    alternatives are not offered). ``echo``, ``suffix``, ``best_of``
    (beyond n) and ``stream`` are unsupported.
    """
    if not isinstance(raw, dict):
        raise ValueError("body must be a JSON object")
    if raw.get("echo"):
        raise ValueError("'echo' is not supported; POST /score for "
                         "prompt logprobs")
    if raw.get("suffix"):
        raise ValueError("'suffix' (insertion) is not supported")
    if raw.get("stream"):
        raise ValueError("'stream' is not supported on /v1/completions;"
                         " POST /generate with stream=true instead")
    n = raw.get("n")
    best_of = raw.get("best_of")
    if best_of is not None and best_of != (n or 1):
        raise ValueError("'best_of' beyond 'n' is not supported")

    def _token_rows(value, what):
        if isinstance(value, str) or (
            isinstance(value, list)
            and any(isinstance(v, str) for v in value)
        ):
            raise ValueError(
                f"text {what} need a tokenizer, which is corpus-"
                f"specific and out of framework scope; send token ids "
                f"([[int, ...]]) and detokenize client-side"
            )
        if not isinstance(value, list) or not value:
            raise ValueError(
                f"'{what}' must be a non-empty token-id list or a "
                f"list of them"
            )
        return (
            [list(r) for r in value]
            if isinstance(value[0], list)
            else [list(value)]
        )

    payload: dict = {"prompts": _token_rows(raw.get("prompt"), "prompts")}
    max_tokens = raw.get("max_tokens")
    payload["max_new_tokens"] = (
        min(16, budget) if max_tokens is None else int(max_tokens)
    )
    temp = raw.get("temperature")
    payload["temperature"] = 1.0 if temp is None else float(temp)
    for key in (
        "top_p",
        "seed",
        "frequency_penalty",
        "presence_penalty",
        "logit_bias",
        "n",
        # extensions shared with /generate (not OpenAI, but harmless)
        "eos_id",
        "adapter",
        "top_k",
        "min_p",
    ):
        if raw.get(key) is not None:
            payload[key] = raw[key]
    if raw.get("stop") is not None:
        payload["stop"] = _token_rows(raw["stop"], "stop sequences")
    if raw.get("logprobs") is not None:  # 0 is valid: sampled-token lp
        payload["logprobs"] = True
    return payload, {"model": raw.get("model")}


class _GenBatcher:
    """Coalesce concurrent /generate requests into shared decode calls.

    Decode throughput is batch-bound (the weight reads amortize over
    rows), but HTTP requests arrive one at a time; per-request decoding
    leaves the batch mostly padding. The batcher's worker thread takes
    the first queued request, lingers up to ``window`` seconds
    collecting more (up to ``max_rows`` prompt rows — the server's one
    compiled batch shape), runs ONE decode for all of them, and
    distributes per-request slices. A failing batch retries each
    request individually so one bad prompt cannot poison its
    co-batched neighbors.
    """

    _STOP = object()

    def __init__(self, gen_fn, lock, window: float, max_rows: int):
        import queue as _q

        self._gen_fn = gen_fn
        self._lock = lock
        self._window = float(window)
        self._max_rows = int(max_rows)
        self._queue: "_q.Queue" = _q.Queue()
        self._closed = False
        # Orders submit()'s closed-check-then-put against close()'s
        # set-flag-then-put-STOP, so no request can enqueue behind the
        # STOP marker (it would hang unanswered once the worker exits).
        self._submit_lock = threading.Lock()
        self.decode_calls = 0  # observability (asserted in tests)
        threading.Thread(
            target=self._worker, daemon=True, name="gen-batcher"
        ).start()

    def submit(self, prompts: list[list[int]]) -> list[list[int]]:
        slot: dict = {"event": threading.Event()}
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("server shutting down")
            self._queue.put((prompts, slot))
        slot["event"].wait()
        if "error" in slot:
            raise slot["error"]
        return slot["result"]

    def close(self) -> None:
        """Release the worker thread (and, with it, the model params
        its gen_fn closure pins) — the server calls this on shutdown.
        Requests racing the shutdown are failed, not left hanging: the
        worker drains the queue behind the _STOP and errors every slot,
        and submit() fails fast once the flag is up."""
        with self._submit_lock:
            self._closed = True
            self._queue.put(self._STOP)

    def _fail_pending(self) -> None:
        import queue as _q

        while True:
            try:
                item = self._queue.get_nowait()
            except _q.Empty:
                return
            if item is self._STOP:
                continue
            _, slot = item
            slot["error"] = RuntimeError("server shutting down")
            slot["event"].set()

    def _decode(self, prompts):
        self.decode_calls += 1
        with self._lock:
            return self._gen_fn(prompts)

    def _worker(self) -> None:
        import queue as _q

        pending = None
        while True:
            first = pending if pending is not None else self._queue.get()
            pending = None
            if first is self._STOP:
                self._fail_pending()
                return
            batch = [first]
            rows = len(first[0])
            deadline = time.monotonic() + self._window
            while rows < self._max_rows:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._queue.get(timeout=remaining)
                except _q.Empty:
                    break
                if item is self._STOP or rows + len(item[0]) > self._max_rows:
                    # capacity (or shutdown): carry into the next round
                    # rather than overshooting the one compiled batch
                    # shape into a second full-size decode
                    pending = item
                    break
                batch.append(item)
                rows += len(item[0])
            flat = [p for req, _ in batch for p in req]
            try:
                results = self._decode(flat)
            except Exception as e:  # noqa: BLE001
                from tensorflowonspark_tpu.tools.generate_text import (
                    PromptError,
                )

                if len(batch) > 1 and isinstance(e, PromptError):
                    # isolate the guilty request(s): PromptError is
                    # raised by cheap pre-decode validation, so
                    # per-request retry costs ~nothing and co-batched
                    # neighbors must not inherit a 400
                    for req, slot in batch:
                        try:
                            slot["result"] = self._decode(req)
                        except Exception as e_one:  # noqa: BLE001
                            slot["error"] = e_one
                        slot["event"].set()
                else:
                    # server-side fault: every retry is doomed — fail
                    # the whole batch at once
                    for _, slot in batch:
                        slot["error"] = e
                        slot["event"].set()
                continue
            i = 0
            for req, slot in batch:
                slot["result"] = results[i : i + len(req)]
                i += len(req)
                slot["event"].set()


def _parse_gen_mesh(gen: dict):
    """Build the --gen-mesh device mesh (or None) — one parser for the
    fixed-batch and continuous-engine paths so axis handling cannot
    diverge between them."""
    if not gen.get("mesh"):
        return None
    from tensorflowonspark_tpu.compute.mesh import (
        make_mesh,
        parse_axis_spec,
    )

    return make_mesh(parse_axis_spec(gen["mesh"]))


def _build_engine(gen: dict):
    """Build the continuous-batching engine for ``--gen-engine
    continuous``: one persistent slot-based decode loop instead of the
    fixed-batch gen_fn. Composes with ``--gen-mesh`` (TP on 'model';
    other axes replicate). Incompatible with the fixed-batch-only
    options (coalescing window, speculative draft) — reject at startup,
    not on the first request."""
    from tensorflowonspark_tpu.models.llama import Llama
    from tensorflowonspark_tpu.serving import ContinuousBatcher
    from tensorflowonspark_tpu.tools.generate_text import (
        _load_config,
        _load_params,
    )

    for bad, flag in (
        ("batch_window", "--gen-batch-window"),
        ("draft_checkpoint", "--draft-checkpoint"),
    ):
        if gen.get(bad):
            raise ValueError(
                f"--gen-engine continuous does not compose with {flag} "
                "(the engine schedules per token; those options belong "
                "to the fixed-batch path)"
            )
    cfg = _load_config(
        argparse.Namespace(
            model=gen["model"], config_overrides=gen.get("config_overrides")
        )
    )
    model = Llama(cfg)
    max_new = int(gen.get("max_new_tokens", 64))
    raw_widths = gen.get("widths")
    if raw_widths:
        # --gen-widths replaces --gen-width entirely; validate at
        # startup like every other shape parameter (a 0-width bucket
        # would start fine and then reject every request).
        try:
            widths = tuple(int(w) for w in str(raw_widths).split(","))
        except ValueError:
            raise ValueError(
                f"--gen-widths must be a CSV of integers, got "
                f"{raw_widths!r}"
            ) from None
        if not widths or any(w < 1 for w in widths):
            raise ValueError(
                f"--gen-widths buckets must be >= 1, got {raw_widths!r}"
            )
    else:
        widths = (int(gen.get("width", 128)),)
    if max(widths) + max_new > cfg.max_seq_len:
        raise ValueError(
            f"largest prompt-width bucket ({max(widths)}) + "
            f"--max-new-tokens ({max_new}) exceeds max_seq_len "
            f"({cfg.max_seq_len})"
        )
    mesh = _parse_gen_mesh(gen)
    if mesh is not None:
        # Duplicates ContinuousBatcher.__init__'s check so it fires in
        # milliseconds, BEFORE the (potentially multi-GB) restore below.
        tp = mesh.shape.get("model", 1)
        if cfg.num_heads % tp or cfg.num_kv_heads % tp:
            raise ValueError(
                f"heads ({cfg.num_heads}/{cfg.num_kv_heads} kv) not "
                f"divisible by the mesh 'model' extent {tp}"
            )
    max_queue = gen.get("max_queue")
    if max_queue is not None and int(max_queue) < 1:
        raise ValueError(
            f"--gen-max-queue must be >= 1, got {max_queue}"
        )
    # Cheap shape validation above happens BEFORE the (potentially
    # multi-GB) checkpoint restore, same policy as the draft path.
    params = _load_params(
        gen["checkpoint"], cfg, lora_scale=gen.get("lora_scale")
    )

    def _new_prefix_l2():
        # Fresh per engine (each facade owns a filler thread + client);
        # any construction failure degrades to L1-only — the cache tier
        # must never keep a replica from serving.
        addr = gen.get("cachetier_l2")
        if not addr or not gen.get("prefix_cache"):
            return None
        try:
            from tensorflowonspark_tpu.cachetier import (
                CacheClient,
                PrefixL2,
            )

            return PrefixL2(
                CacheClient(addr),
                chunk=int(gen.get("prefill_chunk") or 1),
                own_client=True,
            )
        except Exception:  # noqa: BLE001 - L2 is optional
            logger.warning("cachetier L2 attach failed", exc_info=True)
            return None

    def factory():
        # One engine per call: the fleet path respawns replicas through
        # this, so everything scheduler-stateful must be built fresh
        # here (model/params are shared read-only — jax arrays).
        return ContinuousBatcher(
            model,
            params,
            slots=int(gen.get("slots") or gen.get("batch_size", 8)),
            prompt_widths=widths,
            temperature=float(gen.get("temperature", 0.0)),
            top_k=gen.get("top_k"),
            top_p=gen.get("top_p"),
            min_p=gen.get("min_p"),
            eos_id=gen.get("eos_id"),
            seed=int(gen.get("seed", 0)),
            mesh=mesh,
            max_queue=gen.get("max_queue"),
            prefill_chunk=gen.get("prefill_chunk"),
            prefix_cache=gen.get("prefix_cache"),
            prefix_l2=_new_prefix_l2(),
            # `or 8` would map an EXPLICIT 0 to 8; only None (unset)
            # takes the default — explicit values pass through to the
            # engine's own max(1, ...) clamp, consistent with direct
            # construction.
            decode_block=(
                8 if gen.get("decode_block") is None
                else int(gen["decode_block"])
            ),
            pipeline_depth=(
                2 if gen.get("pipeline_depth") is None
                else int(gen["pipeline_depth"])
            ),
            watchdog_s=(
                None if gen.get("watchdog_s") is None
                else float(gen["watchdog_s"])
            ),
        )

    n_replicas = int(gen.get("replicas") or 1)
    if n_replicas > 1:
        # The fleet plane: N in-process replicas (each with its own
        # scheduler + watchdog) behind the health-routing FleetRouter —
        # the handler talks to the router exactly as it would to one
        # engine (docs/SERVING.md "Serving fleet").
        from tensorflowonspark_tpu.serving.fleet import ServingFleet
        from tensorflowonspark_tpu.serving.router import FleetRouter

        t0 = time.monotonic()
        fleet = ServingFleet(
            factory=factory,
            replicas=n_replicas,
            probe_interval=float(gen.get("probe_interval") or 1.0),
            warmup=bool(gen.get("warmup")),
        )
        router = FleetRouter(
            fleet,
            default_temperature=float(gen.get("temperature", 0.0)),
        )
        logger.info(
            "serving fleet of %d replicas ready in %.1fs",
            n_replicas,
            time.monotonic() - t0,
        )
        return router, max_new, model, params

    engine = factory()
    if gen.get("warmup"):
        t0 = time.monotonic()
        engine.warmup()
        logger.info(
            "engine warmup compiled all programs in %.1fs",
            time.monotonic() - t0,
        )
    return engine, max_new, model, engine._params


def _build_gen_fn(gen: dict):
    """Build ``prompts -> completions`` over a Llama checkpoint with ONE
    static decode shape: (gen_batch_size, gen_width). Requests are padded
    into that shape (rows repeat the last prompt, results trimmed), so
    the jitted prefill + decode loop compiles exactly once, at startup
    policy rather than per request — the bucketing discipline every
    static-shape serving stack uses. Returns ``(gen_fn, batch_size)`` —
    the batch size actually compiled, so the request batcher's row cap
    cannot drift from it."""
    import jax

    from tensorflowonspark_tpu.models.llama import Llama
    from tensorflowonspark_tpu.tools.generate_text import (
        _load_config,
        _load_params,
        decode_batches,
    )

    if float(gen.get("temperature", 0.0)) == 0.0 and any(
        gen.get(k) is not None for k in ("top_k", "top_p", "min_p")
    ):
        # generate() raises the same error per call; surface it at
        # startup, BEFORE the (potentially multi-GB) checkpoint restore
        raise ValueError(
            "--top-k/--top-p/--min-p require --temperature > 0 "
            "(temperature 0 is greedy argmax, which would silently "
            "ignore them)"
        )
    cfg = _load_config(
        argparse.Namespace(
            model=gen["model"], config_overrides=gen.get("config_overrides")
        )
    )
    model = Llama(cfg)
    params = _load_params(
        gen["checkpoint"], cfg, lora_scale=gen.get("lora_scale")
    )
    width = int(gen.get("width", 128))
    bsz = int(gen.get("batch_size", 8))
    max_new = int(gen.get("max_new_tokens", 64))
    if bsz < 1:
        raise ValueError(f"--gen-batch-size must be >= 1, got {bsz}")
    if width + max_new > cfg.max_seq_len:
        raise ValueError(
            f"--gen-width ({width}) + --max-new-tokens ({max_new}) "
            f"exceeds max_seq_len ({cfg.max_seq_len})"
        )
    rng_box = [jax.random.PRNGKey(int(gen.get("seed", 0)))]
    draft = None
    if gen.get("draft_checkpoint"):
        # fail at startup, not on the first request — and BEFORE the
        # (potentially multi-GB) draft checkpoint restore
        spec_k = int(gen.get("spec_k", 4))
        if spec_k < 1:
            raise ValueError(f"--spec-k must be >= 1, got {spec_k}")
        if (
            gen.get("top_k") is not None
            or gen.get("top_p") is not None
            or gen.get("min_p") is not None
        ):
            raise ValueError(
                "--draft-checkpoint supports greedy and plain-"
                "temperature sampling; drop --top-k/--top-p/--min-p "
                "(truncation would change the distribution the "
                "rejection rule preserves)"
            )
        dcfg = _load_config(
            argparse.Namespace(
                model=gen.get("draft_model", "tiny"),
                config_overrides=gen.get("draft_config_overrides"),
            )
        )
        # speculative needs k slots of verify-window headroom in BOTH
        # models' caches (speculative_generate re-checks per call; this
        # makes a doomed configuration fail before serving starts)
        for nm, c in (("--model", cfg), ("--draft-model", dcfg)):
            if width + max_new + spec_k > c.max_seq_len:
                raise ValueError(
                    f"--gen-width ({width}) + --max-new-tokens "
                    f"({max_new}) + --spec-k ({spec_k}) exceeds {nm}'s "
                    f"max_seq_len ({c.max_seq_len})"
                )
        draft = (
            Llama(dcfg),
            _load_params(gen["draft_checkpoint"], dcfg),
        )
    mesh = _parse_gen_mesh(gen)
    if mesh is not None:
        if bsz % mesh.shape["data"]:
            raise ValueError(
                f"--gen-batch-size ({bsz}) must be divisible by the "
                f"mesh 'data' extent ({mesh.shape['data']})"
            )
        from tensorflowonspark_tpu.compute import layout
        from tensorflowonspark_tpu.models.llama import llama_param_shardings

        # Pre-place the weights in their layouts ONCE at startup (target
        # TP-sharded, draft replicated): the decode path's per-call
        # device_put is then the no-op it assumes, instead of a full
        # weight reshard/broadcast on every request.
        params = jax.device_put(params, llama_param_shardings(params, mesh))
        if draft is not None:
            draft = (
                draft[0],
                jax.device_put(draft[1], layout.replicated(mesh)),
            )

    def gen_fn(prompts: list[list[int]]) -> list[list[int]]:
        out, rng_box[0] = decode_batches(
            model,
            params,
            prompts,
            batch_size=bsz,
            mesh=mesh,
            draft=draft,
            spec_k=int(gen.get("spec_k", 4)),
            # server mode: one (gen_batch_size, width) shape EVER
            # compiles — per-request sizes must not each compile
            pad_to_batch=True,
            width=width,
            max_new_tokens=max_new,
            rng=rng_box[0],
            temperature=float(gen.get("temperature", 0.0)),
            top_k=gen.get("top_k"),
            top_p=gen.get("top_p"),
            min_p=gen.get("min_p"),
            eos_id=gen.get("eos_id"),
        )
        return out

    return gen_fn, bsz, model, params


class _ObsPlane:
    """The serving process's windowed-telemetry + SLO plane: ONE
    History pumping ONE registry (``Registry.window()`` deltas are
    stateful, so the registry gets exactly one pumping consumer), and
    an :class:`~tensorflowonspark_tpu.obs.slo.SLOEvaluator` reading
    burn rates off it. A background thread pumps on ``interval`` so
    ``slo_burn_rate`` stays current between requests; ``/statusz``
    additionally pumps on demand so pollers see fresh verdicts."""

    def __init__(self, registry, slos, interval: float = 5.0):
        from tensorflowonspark_tpu.obs import History, SLOEvaluator

        self.registry = registry
        self.history = History(source="serve_model")
        self.evaluator = SLOEvaluator(slos, self.history, registry=registry)
        self.interval = float(interval)
        self._pump_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def pump(self):
        """One scrape + evaluation; serialized (the background cadence
        and /statusz share the registry's single delta window)."""
        with self._pump_lock:
            self.history.scrape_registry(self.registry)
            return self.evaluator.evaluate()

    def statusz(self) -> dict:
        return {
            "slo": self.evaluator.statusz(),
            "history": self.history.stats(),
        }

    def start(self) -> None:
        if self.interval <= 0 or self._thread is not None:
            return

        def loop() -> None:
            while not self._stop.wait(self.interval):
                try:
                    self.pump()
                except Exception as e:  # noqa: BLE001 - keep pumping
                    logger.warning("obs pump failed: %s", e)

        self._thread = threading.Thread(
            target=loop, daemon=True, name="obs-pump"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)


class _Server(ThreadingHTTPServer):
    """ThreadingHTTPServer that also releases the request batcher's
    worker thread (and the params its closure pins) on shutdown."""

    gen_batcher = None
    gen_engine = None
    rollout_ctl = None
    obs_plane = None
    drain_on_shutdown = False

    def shutdown(self) -> None:
        super().shutdown()
        if self.obs_plane is not None:
            self.obs_plane.stop()
        if self.rollout_ctl is not None:
            # stop watching the channel BEFORE the engines go away —
            # a rollout racing teardown would hold seats of a closing
            # fleet
            self.rollout_ctl.stop()
        if self.gen_batcher is not None:
            self.gen_batcher.close()
        if self.gen_engine is not None:
            # drain: accepted requests finish before the engine stops
            # (--gen-drain-on-shutdown); default remains abrupt
            self.gen_engine.close(drain=self.drain_on_shutdown)


def make_server(
    export_dir: str | None,
    port: int = 8500,
    batch_size: int = 64,
    host: str = "127.0.0.1",
    gen: dict | None = None,
) -> ThreadingHTTPServer:
    """Load the artifact (and/or the ``gen`` Llama checkpoint config)
    and return a ready (unstarted) HTTP server; callers drive
    ``serve_forever``/``shutdown`` (tests bind port 0). Binds localhost
    by default — the endpoint is unauthenticated, so exposing it
    (``host='0.0.0.0'``) is an explicit operator choice."""
    model = None
    if export_dir is not None:
        from tensorflowonspark_tpu.api.export import load_model

        model = load_model(export_dir)
    gen_fn, gen_bsz = (None, 0)
    engine, engine_max_new = (None, 64)
    score_fn = None
    if gen is not None and gen.get("engine") == "continuous":
        engine, engine_max_new, lm, lm_params = _build_engine(gen)
    elif gen is not None:
        gen_fn, gen_bsz, lm, lm_params = _build_gen_fn(gen)
    if gen is not None:
        from tensorflowonspark_tpu.tools.generate_text import (
            build_score_fn,
        )

        # Score width must cover anything /generate can emit: the
        # LARGEST prompt bucket + the decode budget, capped at the
        # model's context (an over-long compile would score positions
        # the model was never shaped for).
        if gen.get("engine") == "continuous" and gen.get("widths"):
            max_bucket = max(
                int(w) for w in str(gen["widths"]).split(",")
            )
        else:
            max_bucket = int(gen.get("width", 128))
        score_fn = build_score_fn(
            lm,
            lm_params,
            width=min(
                max_bucket + int(gen.get("max_new_tokens", 64)),
                lm.cfg.max_seq_len,
            ),
            bsz=int(gen.get("batch_size", 8)),
        )
    lock = threading.Lock()  # per-server, not shared
    batcher = None
    window = float(gen.get("batch_window", 0.0) or 0.0) if gen else 0.0
    if gen_fn is not None and window > 0:
        batcher = _GenBatcher(gen_fn, lock, window, gen_bsz)
    rollout_ctl = None
    if engine is not None:
        # Zero-downtime weight rollout plane (docs/SERVING.md "Rolling
        # weight updates"): a controller always fronts the continuous
        # engine(s) — /admin/reload drives it directly, and
        # --rollout-channel additionally starts the channel watcher.
        # Construction is cheap: no threads until start().
        from tensorflowonspark_tpu.serving.rollout import (
            RolloutController,
            checkpoint_loader,
        )

        rollout_ctl = RolloutController(
            engine.fleet
            if getattr(engine, "IS_FLEET", False)
            else engine,
            channel_dir=gen.get("rollout_channel"),
            loader=checkpoint_loader(lm_params),
            poll_interval=float(gen.get("rollout_poll") or 2.0),
        )
        if gen.get("rollout_channel"):
            rollout_ctl.start()
    obs_plane = None
    if engine is not None:
        # SLO burn-rate plane over the engine's (or, in fleet mode,
        # the router's) registry — /statusz reads it, and the gauges
        # land in the same registry /metrics already renders
        from tensorflowonspark_tpu.obs.slo import (
            default_serving_slos,
            router_slos,
        )

        if getattr(engine, "IS_FLEET", False):
            slos = router_slos(
                latency_objective_s=float(
                    gen.get("slo_latency_s") or 30.0
                ),
                shed_budget=float(gen.get("slo_error_budget") or 0.02),
            )
            obs_registry = engine.fleet.metrics
        else:
            slos = default_serving_slos(
                ttft_objective_s=float(gen.get("slo_ttft_s") or 2.5),
                error_budget=float(gen.get("slo_error_budget") or 0.02),
            )
            obs_registry = engine.metrics
        obs_plane = _ObsPlane(
            obs_registry,
            slos,
            interval=float(gen.get("obs_window_s") or 5.0),
        )
        obs_plane.start()
    handler = type(
        "_BoundHandler",
        (_Handler,),
        {
            "model": model,
            "export_dir": export_dir or "",
            "batch_size": batch_size,
            # staticmethod: a bare function class attribute would bind
            # as a method and receive the handler as its first argument
            "gen_fn": staticmethod(gen_fn) if gen_fn is not None else None,
            "gen_batcher": batcher,
            "gen_engine": engine,
            "gen_max_new": engine_max_new,
            "score_fn": staticmethod(score_fn)
            if score_fn is not None
            else None,
            "model_name": (
                str(gen.get("served_model_name") or "default")
                if gen
                else "default"
            ),
            "rollout_ctl": rollout_ctl,
            "admin_token": (
                gen.get("admin_token") if gen else None
            ),
            "obs_plane": obs_plane,
            "predict_lock": lock,
        },
    )
    server = _Server((host, port), handler)
    server.gen_batcher = batcher
    server.gen_engine = engine
    server.rollout_ctl = rollout_ctl
    server.obs_plane = obs_plane
    server.drain_on_shutdown = bool(
        gen.get("drain_on_shutdown") if gen else False
    )
    return server


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="serve_model",
        description="HTTP inference over an AOT export and/or a Llama "
        "checkpoint (/generate)",
    )
    p.add_argument("--export-dir", default=None)
    p.add_argument("--port", type=int, default=8500)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument(
        "--host",
        default="127.0.0.1",
        help="bind address (unauthenticated endpoint: exposing beyond "
        "localhost is an explicit choice)",
    )
    p.add_argument("--llama-checkpoint", default=None)
    p.add_argument("--model", choices=("tiny", "1b", "7b"), default="tiny")
    p.add_argument("--config-overrides", default=None)
    p.add_argument("--gen-width", type=int, default=128)
    p.add_argument("--gen-batch-size", type=int, default=8)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--min-p", type=float, default=None)
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--draft-checkpoint",
        default=None,
        help="speculative decoding for /generate: draft model "
        "checkpoint (greedy output identical to plain greedy; "
        "temperature>0 preserves the target's sampling distribution "
        "via the rejection rule); no --top-k/--top-p; composes with "
        "--gen-mesh (TP target, replicated draft)",
    )
    p.add_argument(
        "--draft-model", choices=("tiny", "1b", "7b"), default="tiny"
    )
    p.add_argument("--draft-config-overrides", default=None)
    p.add_argument("--spec-k", type=int, default=4)
    p.add_argument(
        "--gen-batch-window",
        type=float,
        default=0.0,
        help="coalesce concurrent /generate requests: linger this many "
        "seconds collecting requests into one shared decode batch (up "
        "to --gen-batch-size rows); 0 = decode per request. Decode "
        "cost is per-batch (weight reads amortize over rows), so under "
        "concurrent load a small window multiplies throughput",
    )
    p.add_argument(
        "--gen-mesh",
        default=None,
        help="shard /generate decoding over a device mesh, e.g. "
        "'data=2,model=4' (TP weights on 'model', batch + KV caches on "
        "'data'); --gen-batch-size must be divisible by the 'data' "
        "extent",
    )
    p.add_argument(
        "--gen-engine",
        choices=("fixed", "continuous"),
        default="fixed",
        help="'continuous' = slot-based continuous batching: requests "
        "join/leave a persistent decode loop at token granularity "
        "(no convoying behind a batch window); composes with "
        "--gen-mesh for TP serving (the 'model' axis; other axes only "
        "replicate) but not with "
        "--gen-batch-window/--draft-checkpoint",
    )
    p.add_argument(
        "--gen-slots",
        type=int,
        default=None,
        help="continuous engine KV-cache slots (default: "
        "--gen-batch-size)",
    )
    p.add_argument(
        "--gen-widths",
        default=None,
        help="continuous engine prompt-width buckets as a CSV (e.g. "
        "'32,128'): each prompt prefills at the smallest bucket that "
        "fits, one compilation per bucket (default: one bucket of "
        "--gen-width)",
    )
    p.add_argument(
        "--gen-max-queue",
        type=int,
        default=None,
        help="continuous engine: shed load with HTTP 503 once this "
        "many requests are waiting for a slot (default: unbounded)",
    )
    p.add_argument(
        "--gen-drain-on-shutdown",
        action="store_true",
        help="continuous engine: on server shutdown, finish accepted "
        "requests before stopping instead of failing them",
    )
    p.add_argument(
        "--served-model-name",
        default="default",
        help="model id reported by GET /v1/models and echoed in "
        "/v1/completions envelopes (OpenAI-compatible clients key on it)",
    )
    p.add_argument(
        "--gen-lora-scale",
        type=float,
        default=None,
        help="LoRA checkpoints: alpha/rank scale to re-apply after "
        "restore (orbax does not store the static scale field; "
        "default 1.0 matches add_lora's default alpha=rank)",
    )
    p.add_argument(
        "--gen-warmup",
        action="store_true",
        help="continuous engine: pre-compile every decode/prefill "
        "program at startup so the first real request's TTFT doesn't "
        "pay the XLA compiles",
    )
    p.add_argument(
        "--gen-prefix-cache",
        type=int,
        default=None,
        help="continuous engine: keep an LRU of this many prompt-prefix "
        "KV caches so requests sharing a prefix (system prompts, "
        "re-submits) resume prefill instead of recomputing it; each "
        "entry holds one full-length single-row KV cache in HBM. "
        "Requires --gen-prefill-chunk",
    )
    p.add_argument(
        "--cachetier-l2",
        default=None,
        metavar="HOST:PORT",
        help="continuous engine: attach the fleet-global prefix L2 at "
        "this cachetier daemon address (a ServingFleet in spawn mode "
        "injects it); requires --gen-prefix-cache. The service is an "
        "optimization, never a dependency — unreachable = L1-only",
    )
    p.add_argument(
        "--gen-decode-block",
        type=int,
        default=8,
        help="continuous engine: decode this many tokens per host "
        "scheduling iteration as one on-device lax.scan (fewer "
        "host round-trips per token); 1 = per-token scheduling "
        "(minimum admission-latency jitter)",
    )
    p.add_argument(
        "--gen-pipeline-depth",
        type=int,
        default=2,
        help="continuous engine: keep this many decode blocks in "
        "flight (dispatch-ahead software pipelining) so the host "
        "sweep/emit/stream cost hides behind device compute; 1 = the "
        "strictly serial dispatch->fetch->sweep loop (identical "
        "tokens either way; only latency/drain behavior differs)",
    )
    p.add_argument(
        "--gen-prefill-chunk",
        type=int,
        default=None,
        help="continuous engine: prefill prompts in chunks of this "
        "many tokens interleaved with decode steps, so a long "
        "admission doesn't stall live requests for its whole prefill "
        "(also skips the padding region: a short prompt costs "
        "ceil(len/chunk) chunks, not the full width bucket); default: "
        "whole-bucket prefill",
    )
    p.add_argument(
        "--gen-replicas",
        type=int,
        default=1,
        help="continuous engine: run this many engine replicas (each "
        "with its own scheduler/watchdog) behind a health-routing "
        "fleet router — prefix-aware placement, failover, draining, "
        "deadline-based load shedding (429/503). 1 = the single "
        "engine, no router",
    )
    p.add_argument(
        "--gen-probe-interval",
        type=float,
        default=1.0,
        help="fleet mode: replica health-probe cadence in seconds; an "
        "unhealthy replica flips to draining within miss_limit "
        "probes and is respawned",
    )
    p.add_argument(
        "--port-file",
        default=None,
        help="write the actually-bound port (useful with --port 0) to "
        "this file once the server is ready to accept requests — the "
        "spawn barrier fleet supervisors poll",
    )
    p.add_argument(
        "--admin-token-file",
        default=None,
        help="enable the authenticated POST /admin/reload weight "
        "hot-swap endpoint with the token read from this file "
        "(alternatively set TFOS_ADMIN_TOKEN — fleet supervisors "
        "inject it into subprocess replicas); without a token the "
        "endpoint answers 403",
    )
    p.add_argument(
        "--rollout-channel",
        default=None,
        help="continuous engine: watch this checkpoint publication "
        "channel directory (an atomically-written LATEST pointer at "
        "orbax step dirs; see serving/rollout.py) and hot-swap each "
        "newly published version into the live engine(s) — rolled one "
        "replica at a time under router health with --gen-replicas, "
        "with automatic rollback on failure",
    )
    p.add_argument(
        "--rollout-poll",
        type=float,
        default=2.0,
        help="rollout channel poll interval in seconds",
    )
    p.add_argument(
        "--slo-ttft-s",
        type=float,
        default=2.5,
        help="single-engine SLO: time-to-first-token objective in "
        "seconds (GET /statusz reports multi-window burn rates; "
        "breaches count in slo_breaches_total and dump the flight "
        "recorder)",
    )
    p.add_argument(
        "--slo-latency-s",
        type=float,
        default=30.0,
        help="fleet SLO (--gen-replicas > 1): end-to-end routed "
        "request latency objective in seconds",
    )
    p.add_argument(
        "--slo-error-budget",
        type=float,
        default=0.02,
        help="SLO error budget: allowed bad-request fraction (errors "
        "single-engine, admission sheds in fleet mode)",
    )
    p.add_argument(
        "--obs-window-s",
        type=float,
        default=5.0,
        help="windowed-telemetry pump cadence in seconds: each tick "
        "scrapes the serving registry into the bounded History rings "
        "and re-evaluates the SLO burn rates",
    )
    p.add_argument(
        "--gen-watchdog",
        type=float,
        default=None,
        help="continuous engine: abort in-flight requests (terminal "
        "EngineWedged) and keep serving when the scheduler makes no "
        "progress for this many seconds with work in flight — a "
        "wedged device transfer must not hang every caller forever. "
        "Use with --gen-warmup (first compiles look like stalls; "
        "warmup itself is exempt). Default: disabled",
    )
    args = p.parse_args(argv)
    if args.export_dir is None and args.llama_checkpoint is None:
        p.error("need --export-dir and/or --llama-checkpoint")
    if args.gen_replicas > 1 and args.gen_engine != "continuous":
        p.error(
            "--gen-replicas > 1 requires --gen-engine continuous "
            "(the fleet router fronts continuous engines)"
        )
    if args.gen_replicas < 1:
        p.error(f"--gen-replicas must be >= 1, got {args.gen_replicas}")
    if args.rollout_channel and args.gen_engine != "continuous":
        p.error(
            "--rollout-channel requires --gen-engine continuous "
            "(only the continuous engine hot-swaps weights)"
        )
    logging.basicConfig(level=logging.INFO)
    from tensorflowonspark_tpu.utils.util import enable_compile_cache

    enable_compile_cache()
    admin_token = None
    if args.admin_token_file:
        with open(args.admin_token_file, encoding="utf-8") as f:
            admin_token = f.read().strip() or None
    if admin_token is None:
        import os as _os

        admin_token = _os.environ.get("TFOS_ADMIN_TOKEN") or None
    gen = None
    if args.llama_checkpoint is not None:
        gen = dict(
            checkpoint=args.llama_checkpoint,
            model=args.model,
            config_overrides=args.config_overrides,
            width=args.gen_width,
            batch_size=args.gen_batch_size,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            min_p=args.min_p,
            eos_id=args.eos_id,
            seed=args.seed,
            mesh=args.gen_mesh,
            batch_window=args.gen_batch_window,
            draft_checkpoint=args.draft_checkpoint,
            draft_model=args.draft_model,
            draft_config_overrides=args.draft_config_overrides,
            spec_k=args.spec_k,
            engine=args.gen_engine,
            slots=args.gen_slots,
            widths=args.gen_widths,
            max_queue=args.gen_max_queue,
            prefill_chunk=args.gen_prefill_chunk,
            prefix_cache=args.gen_prefix_cache,
            cachetier_l2=args.cachetier_l2,
            decode_block=args.gen_decode_block,
            pipeline_depth=args.gen_pipeline_depth,
            watchdog_s=args.gen_watchdog,
            warmup=args.gen_warmup,
            lora_scale=args.gen_lora_scale,
            drain_on_shutdown=args.gen_drain_on_shutdown,
            served_model_name=args.served_model_name,
            replicas=args.gen_replicas,
            probe_interval=args.gen_probe_interval,
            admin_token=admin_token,
            rollout_channel=args.rollout_channel,
            rollout_poll=args.rollout_poll,
            slo_ttft_s=args.slo_ttft_s,
            slo_latency_s=args.slo_latency_s,
            slo_error_budget=args.slo_error_budget,
            obs_window_s=args.obs_window_s,
        )
    server = make_server(
        args.export_dir, args.port, args.batch_size, host=args.host, gen=gen
    )
    global _last_server  # drive/inspect a CLI-started server (tests,
    _last_server = server  # operator tooling; the bound port for --port 0)
    logger.info(
        "serving %s on :%d",
        args.export_dir or args.llama_checkpoint,
        server.server_address[1],
    )
    if args.port_file:
        # atomic (tmp + rename): a poller must never read a torn port.
        # Written AFTER make_server returns — the engine is built (and
        # warmed, with --gen-warmup), so the file doubles as the
        # replica spawn barrier.
        import os as _os

        tmp = f"{args.port_file}.tmp.{_os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(str(server.server_address[1]))
        _os.replace(tmp, args.port_file)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        server.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
