"""From a profiler trace to numbers: the benchmark's own reduction.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. The ideas are those of the program's
``obs/trace_report.py`` (device lanes apart from host lanes, nesting-aware
self time), written against the planes a TPU trace really has:

- a plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
  event per executed HLO operation (a ``while`` and the operations of its
  body nest) and whose line ``XLA Modules`` holds one event per executed
  program;
- a host plane, ``/host:CPU``, with one line per thread; the benchmark's
  own spans are ``jax.profiler.TraceAnnotation`` events there, named
  ``pb:<span>``.

Busy time is the union of the ``XLA Ops`` intervals; the traced window is
from the first operation's start to the last one's end. A gap between
operations is named after the benchmark span that covers most of it.
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
SPAN_PREFIX = "pb:"


def find_xplane(root: str) -> str:
    files = sorted(
        glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(name, start_ns, dur_ns), ...]}}``."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return out


def union_seconds(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_times(events) -> "collections.Counter[str]":
    """Nesting-aware self time by name, of events of ONE line. An event's
    self time is its duration less what its direct children cover."""
    out: collections.Counter = collections.Counter()
    stack: list[list] = []  # [name, end, dur, child]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            n, _, dur, child = stack.pop()
            out[n] += max(0.0, dur - child)
        if stack:
            stack[-1][3] += max(0.0, min(s + d, stack[-1][1]) - s)
        stack.append([name, s + d, d, 0.0])
    while stack:
        n, _, dur, child = stack.pop()
        out[n] += max(0.0, dur - child)
    return out


@functools.lru_cache(maxsize=None)
def short(name: str, limit: int = 120) -> str:
    """An operation's event name is its whole HLO text: keep the name,
    the result's shapes and the kind, without layouts and operands."""
    name = re.sub(r"\{[^{}]*\}", "", name)
    head, sep, rest = name.partition(" = ")
    if sep:
        m = re.match(r"(\([^()]*\)|\S+) (\S+?)\(", rest)
        if m:
            name = f"{head} = {m.group(1)} {m.group(2)}"
    return name[:limit]


def _by_signature(self_s, top):
    """Self time summed over operations that differ only in their
    number (the same fusion in each layer), most costly first."""
    by: collections.Counter = collections.Counter()
    for n, t in self_s.items():
        by[re.sub(r"^(%[^ .]+)[.\d]*", r"\1", short(n))] += t
    return [[n, t] for n, t in by.most_common(top)]


def _name_gaps(gaps, spans, top):
    """Sum the gaps by the span that covers most of each."""
    spans = sorted(spans, key=lambda x: x[1])
    by: collections.Counter = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][1] + spans[j][2] < gs:
            j += 1
        best, best_ov = "unattributed", 0.0
        k = j
        while k < len(spans) and spans[k][1] < ge:
            n, s, d = spans[k]
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = n, ov
            k += 1
        by[best] += ge - gs
    return [[n, t * 1e-9] for n, t in by.most_common(top)]


def summarize(planes: dict, top: int = 10) -> dict | None:
    """The numbers the readers and the result line take from a trace, or
    None where no device operation was traced (a CPU rehearsal)."""
    devices = {n: l for n, l in planes.items()
               if n.startswith(DEVICE_PREFIX) and l.get(OPS_LINE)}
    if not devices:
        return None
    spans = []
    host_events = []
    for name, lines in planes.items():
        if not name.startswith(HOST_PREFIX):
            continue
        for evs in lines.values():
            for e in evs:
                if e[0].startswith(SPAN_PREFIX):
                    spans.append((e[0][len(SPAN_PREFIX):], e[1], e[2]))
                else:
                    host_events.append(e)
    busy, window = [], []
    ops_self: collections.Counter = collections.Counter()
    ops_total: dict = {}
    modules: list = []
    gaps_named: collections.Counter = collections.Counter()
    for lines in devices.values():
        ops = lines[OPS_LINE]
        b, merged = union_seconds((s, s + d) for _, s, d in ops)
        busy.append(b * 1e-9)
        window.append((merged[-1][1] - merged[0][0]) * 1e-9)
        for n, t in self_times(ops).items():
            ops_self[n] += t * 1e-9 / len(devices)
        for n, _, d in ops:
            c = ops_total.setdefault(short(n), [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
        modules += [(n, s, d) for n, s, d in lines.get(MODULES_LINE, [])]
        gaps = [(a[1], b_[0]) for a, b_ in zip(merged, merged[1:])]
        for n, t in _name_gaps(gaps, spans or host_events, 10 * top):
            gaps_named[n] += t / len(devices)
    mod_total: dict = {}
    for n, _, d in modules:
        c = mod_total.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += d * 1e-9
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(window) / len(window),
        "chips": len(devices),
        "ops": ops_total,  # short(name) -> [calls, summed seconds], nested included
        "modules": mod_total,  # program name -> [runs, summed seconds]
        "device_ops": _by_signature(ops_self, top),
        "idle_gaps": [[short(n), t] for n, t in gaps_named.most_common(top)],
    }


def by_hand(planes: dict, top: int = 60) -> dict:
    """What to look at before trusting a pattern: every plane and line
    with its event count, and each line's most costly names."""
    out: dict = {}
    for plane, lines in planes.items():
        for line, evs in lines.items():
            total: collections.Counter = collections.Counter()
            calls: collections.Counter = collections.Counter()
            for n, _, d in evs:
                total[n] += d * 1e-9
                calls[n] += 1
            out[f"{plane} | {line}"] = {
                "events": len(evs),
                "top": [[n, calls[n], t] for n, t in total.most_common(top)],
            }
    return out


def excerpt(planes: dict, seconds: float = 0.05, limit: int = 4000) -> dict:
    """The first ``seconds`` of a trace from its first device operation on,
    names shortened: small enough to keep as a recorded trace for the
    tests, in the form ``read_planes`` returns."""
    starts = [e[1] for n, l in planes.items() if n.startswith(DEVICE_PREFIX)
              for e in l.get(OPS_LINE, [])]
    if not starts:
        return {}
    t0 = min(starts)
    t1 = t0 + seconds * 1e9
    out: dict = {}
    for plane, lines in planes.items():
        for line, evs in lines.items():
            keep = [[short(n), s - t0, d] for n, s, d in evs if t0 <= s and s + d <= t1][:limit]
            if keep:
                out.setdefault(plane, {})[line] = keep
    return out
