"""What every driver shares: the run's context, the benchmark's own host
spans, the profiler bracket, the device report and the compile cache."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def load_cell(cell: str) -> tuple[dict, dict, dict]:
    """A cell's workload file and the configuration and traffic mix it
    names."""
    workload = load_json("workloads", cell + ".json")
    return (workload, load_json("configs", workload["config"] + ".json"),
            load_json("traffic", workload["traffic"] + ".json"))


def process_start() -> float:
    """Epoch seconds at which this process was created (``/proc``), so
    that ``setup_s`` counts the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):  # no /proc here
        return time.time()


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where the environment sets it (JAX
    has read it; nothing is set in code), else ``<checkout>/.jax_cache``:
    a fixed path, because the path is part of the cache's key. Every
    program is written, however quick its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_report() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend
    keeps no statistics, as the CPU's)."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()
    )


class Spans:
    """The benchmark's own host spans, around its calls into each layer.
    Durations are kept in memory by name; while a trace is being taken
    each span is also a ``TraceAnnotation`` named ``pb:<name>``, so that
    the device's idle gaps can be named after it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.durations: dict[str, list[float]] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("pb:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.durations.setdefault(name, []).append(dt)

    def reset(self) -> None:
        with self._lock:
            self.durations = {}


class Trace:
    """Brackets the traced part of a ``--trace 1`` window. The trace is
    written under ``<checkout>/.perfbench_trace/<cell>``, reduced, and
    deleted."""

    def __init__(self, cell: str, spans: Spans, dump: str | None = None):
        self.dir = os.path.join(ROOT, ".perfbench_trace", cell)
        self.dump = dump
        self.spans = spans
        self.summary: dict | None = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the interpreter's calls slow the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.annotate = True

    def stop(self) -> None:
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        from perfbench import trace_reduce

        try:
            planes = trace_reduce.read_planes(trace_reduce.find_xplane(self.dir))
            self.summary = trace_reduce.summarize(planes)
            if self.dump:
                os.makedirs(os.path.dirname(os.path.abspath(self.dump)), exist_ok=True)
                with open(self.dump, "w") as f:
                    json.dump(trace_reduce.by_hand(planes), f, indent=1)
                with open(self.dump + ".planes.json", "w") as f:
                    json.dump(trace_reduce.excerpt(planes), f)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.summary


@dataclasses.dataclass
class Context:
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    spans: Spans
    tracer: Trace | None
    peak: dict | None  # perfbench.peaks entry; None in a rehearsal
    # test hook: a function the driver applies to what the timed path
    # produced, before the comparison (plants a fault underneath)
    fault: object = None


@dataclasses.dataclass
class Check:
    """One number compared beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit  # NaN fails

    def as_dict(self) -> dict:
        return {"value": self.value, "limit": self.limit, "ok": self.ok}
