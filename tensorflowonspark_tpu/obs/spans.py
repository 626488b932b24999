"""Host-side span tracing: where does the host's time go, per phase.

The device side already has a first-class story (``jax.profiler.trace``);
what the stack lacked was the HOST side — queue waits, batch formation,
dispatch, fetches — the glue between device programs. A
:class:`SpanTracer` records named intervals into a thread-safe ring
buffer with microsecond timestamps, cheap enough to leave on in
production hot paths (one ``perf_counter`` pair + a deque append per
span; no allocation beyond the span tuple).

Three consumption surfaces, one recording API:

- **Percentiles in-process**: :meth:`SpanTracer.summary` aggregates the
  ring buffer per span name (count/p50/p90/p99/total) — what the
  serving engine's ``/stats`` serves per request phase.
- **Chrome trace export**: :meth:`SpanTracer.export` /
  :meth:`write_chrome_trace` emit standard ``traceEvents`` JSON
  (``ph: "X"`` complete events, per-thread lanes) that
  ``obs.trace_merge``, chrome://tracing and Perfetto read directly.
- **XLA timeline bridge**: every span body also runs under
  ``jax.profiler.TraceAnnotation`` (and :meth:`step_span` under
  ``StepTraceAnnotation``), so when a device trace is active the host
  spans land on the SAME timeline as the XLA ops. When jax is absent or
  no trace is active these are no-ops costing one TraceMe call.

Usage::

    from tensorflowonspark_tpu.obs import spans

    with spans.span("engine.dispatch", rows=8):
        out = step_fn(...)

    @spans.traced("feed.columnize")
    def columnize(...): ...

    spans.get_tracer().summary(prefix="engine.")
"""

from __future__ import annotations

import functools
import gzip
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable

__all__ = [
    "Span",
    "SpanTracer",
    "get_tracer",
    "span",
    "step_span",
    "record",
    "traced",
    "summary",
]

_CLOCK = time.perf_counter

# jax.profiler resolved lazily and at most once: obs must import (and
# record) fine in processes that never touch jax, and the bridge must
# not pay an import-attempt per span.
_UNSET = object()
_PROF: Any = _UNSET


def _profiler():
    global _PROF
    if _PROF is _UNSET:
        try:
            from jax import profiler as _p  # noqa: PLC0415

            _PROF = _p
        except Exception:  # pragma: no cover - jax is present in CI
            _PROF = None
    return _PROF


class Span(tuple):
    """One recorded interval: ``(name, ts, dur, tid, thread_name, args)``
    with ``ts``/``dur`` in seconds on the tracer's monotonic clock.
    ``tid`` is the recording thread's ident for call-stack spans, or a
    synthetic ``"interval:<name>"`` lane id for :meth:`SpanTracer.record`
    intervals (which don't nest with any thread's call stack)."""

    __slots__ = ()
    name = property(lambda s: s[0])
    ts = property(lambda s: s[1])
    dur = property(lambda s: s[2])
    tid = property(lambda s: s[3])
    thread_name = property(lambda s: s[4])
    args = property(lambda s: s[5])


class _SpanCtx:
    """Context manager for one open span; also usable as a decorator via
    :func:`traced`. Enters a ``jax.profiler`` annotation so the span
    shows on the device timeline when a trace is active."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann", "_step_num",
                 "dur")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict,
                 step_num: int | None = None):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._step_num = step_num
        self._ann = None
        # seconds, set at exit: the one duration ring, profiler
        # annotation and any histogram a caller feeds describe
        self.dur: float | None = None

    def set(self, **args: Any) -> None:
        """Attach args discovered DURING the span body (the consumer
        pull learns the frame's ``stream``/``seq`` only after the
        blocking get returns) — they land on the recorded span like
        ctor args, so cross-process trace stitching can key on them."""
        self._args.update(args)

    def __enter__(self) -> "_SpanCtx":
        prof = _profiler()
        if prof is not None:
            try:
                if self._step_num is not None:
                    ann = prof.StepTraceAnnotation(
                        self._name, step_num=self._step_num
                    )
                else:
                    ann = prof.TraceAnnotation(self._name)
                ann.__enter__()
                self._ann = ann
            except Exception:  # annotation is best-effort observability
                self._ann = None
        self._t0 = _CLOCK()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = dur = _CLOCK() - self._t0
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        self._tracer._append(self._name, self._t0, dur, self._args)


class SpanTracer:
    """Thread-safe ring buffer of completed spans.

    ``capacity`` bounds memory: the buffer holds the most recent spans
    (older ones are silently dropped — ``recorded`` keeps the lifetime
    count, so ``recorded - len(spans())`` is the drop count). All
    methods are safe to call from any thread; recording takes one lock
    around a deque append.
    """

    def __init__(self, capacity: int = 8192):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._buf: "deque[Span]" = deque(maxlen=int(capacity))  # guarded-by: self._lock
        self._lock = threading.Lock()
        self._epoch = _CLOCK()
        # lifetime spans, including dropped ones
        self.recorded = 0  # guarded-by: self._lock

    # -- recording -----------------------------------------------------

    def span(self, name: str, **args: Any) -> _SpanCtx:
        """Context manager measuring its body as one span."""
        return _SpanCtx(self, name, args)

    def step_span(self, name: str, step_num: int, **args: Any) -> _SpanCtx:
        """Like :meth:`span`, but bridges to
        ``jax.profiler.StepTraceAnnotation`` so an active device trace
        groups the device ops under this step number (the per-step
        attribution the profiler UI keys on)."""
        return _SpanCtx(self, name, dict(args, step=step_num), step_num)

    def record(self, name: str, dur: float, ts: float | None = None,
               **args: Any) -> None:
        """Record an already-measured interval of ``dur`` seconds ending
        now (or starting at monotonic ``ts``) — for durations measured
        elsewhere, e.g. a request's queue wait stamped at enqueue.

        The interval lands on a synthetic per-name lane
        (``tid="interval:<name>"``), NOT the calling thread's lane: a
        backdated interval (a ~1s queue wait recorded at admission time)
        would otherwise span real call-stack spans the same thread
        recorded in the meantime without properly nesting them, and a
        nesting-aware reader (self time = duration minus children)
        would subtract those spans from it — producing negative self
        time.
        ``summary()`` percentiles key on name only and are identical
        either way.
        """
        t_start = (_CLOCK() - dur) if ts is None else ts
        self._append(
            name, t_start, dur, args,
            tid=f"interval:{name}",
            thread_name=f"intervals: {name}",
        )

    def traced(self, name: str | None = None) -> Callable:
        """Decorator: run the function body under a span (default name:
        the function's qualified name)."""

        def deco(fn):
            span_name = name or fn.__qualname__

            @functools.wraps(fn)
            def inner(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)

            return inner

        return deco

    def _append(self, name: str, ts: float, dur: float, args: dict,
                tid: Any = None, thread_name: str | None = None) -> None:
        if tid is None:
            t = threading.current_thread()
            tid, thread_name = t.ident, t.name
        s = Span((name, ts, dur, tid, thread_name, args or None))
        with self._lock:
            self._buf.append(s)
            self.recorded += 1

    # -- consumption ---------------------------------------------------

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def summary(self, prefix: str = "") -> dict[str, dict[str, float]]:
        """Aggregate the buffered spans per name (optionally filtered by
        ``prefix``): ``{name: {count, total_ms, p50_ms, p90_ms,
        p99_ms, max_ms}}``. Percentiles are nearest-rank over whatever
        the ring currently holds — a sliding window by construction."""
        by_name: dict[str, list[float]] = {}
        for s in self.spans():
            if s.name.startswith(prefix):
                by_name.setdefault(s.name, []).append(s.dur)
        out: dict[str, dict[str, float]] = {}
        for name, durs in sorted(by_name.items()):
            durs.sort()
            n = len(durs)

            def pct(p: float) -> float:
                return durs[min(n - 1, int(p * n))]

            out[name] = {
                "count": n,
                "total_ms": round(sum(durs) * 1e3, 3),
                "p50_ms": round(pct(0.50) * 1e3, 3),
                "p90_ms": round(pct(0.90) * 1e3, 3),
                "p99_ms": round(pct(0.99) * 1e3, 3),
                "max_ms": round(durs[-1] * 1e3, 3),
            }
        return out

    def export(self, process_name: str | None = None) -> dict:
        """The buffer as a Chrome-trace dict (``{"traceEvents": [...]}``,
        ``ts``/``dur`` in microseconds relative to the tracer epoch) —
        the format ``obs.trace_merge`` and chrome://tracing read."""
        pid = os.getpid()
        # Cross-process alignment metadata: the wall-clock time of this
        # tracer's epoch (event ts are relative to it), plus the run's
        # trace id / node name / clock-offset estimate when the cluster
        # trace context is set (obs.cluster) — what tools/trace_merge.py
        # keys on to put N processes' spans on ONE timeline.
        ctx_args: dict[str, Any] = {
            "epoch_unix": time.time() - (_CLOCK() - self._epoch),
        }
        try:
            from tensorflowonspark_tpu.obs import cluster as _obs_cluster

            ctx_args.update(_obs_cluster.export_meta())
        except Exception:  # trace context is best-effort metadata
            pass
        events: list[dict] = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "args": {
                    "name": process_name or f"host: pid {pid}"
                },
            },
            {
                "ph": "M",
                "name": "trace_context",
                "pid": pid,
                "args": ctx_args,
            },
        ]
        seen_tids: set = set()
        for s in self.spans():
            if s.tid not in seen_tids:
                seen_tids.add(s.tid)
                events.append(
                    {
                        "ph": "M",
                        "name": "thread_name",
                        "pid": pid,
                        "tid": s.tid,
                        "args": {"name": s.thread_name},
                    }
                )
            ev = {
                "ph": "X",
                "pid": pid,
                "tid": s.tid,
                "name": s.name,
                "ts": round((s.ts - self._epoch) * 1e6, 3),
                "dur": round(s.dur * 1e6, 3),
            }
            if s.args:
                ev["args"] = dict(s.args)
            events.append(ev)
        return {"traceEvents": events}

    def write_chrome_trace(
        self, path: str, process_name: str | None = None
    ) -> str:
        """Write :meth:`export` as JSON (gzipped when the path ends in
        ``.gz``); returns the path."""
        data = self.export(process_name)
        op = gzip.open if path.endswith(".gz") else open
        with op(path, "wt") as f:
            json.dump(data, f)
        return path


# Process-global default tracer: hot paths (engine, feed, train step)
# record here so one export/summary sees the whole process. Components
# that need isolated percentile windows (one engine instance among
# several) construct their own SpanTracer.
_default = SpanTracer()


def get_tracer() -> SpanTracer:
    return _default


def span(name: str, **args: Any) -> _SpanCtx:
    return _default.span(name, **args)


def step_span(name: str, step_num: int, **args: Any) -> _SpanCtx:
    return _default.step_span(name, step_num, **args)


def record(name: str, dur: float, ts: float | None = None, **args) -> None:
    _default.record(name, dur, ts, **args)


def traced(name: str | None = None) -> Callable:
    return _default.traced(name)


def summary(prefix: str = "") -> dict[str, dict[str, float]]:
    return _default.summary(prefix)
