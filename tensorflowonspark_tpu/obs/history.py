"""Windowed time-series history: bounded rings over metric scrapes.

``Registry.window()`` gives ONE delta since the previous call; a
feedback controller (the ROADMAP-2 autotune loop) and a burn-rate SLO
evaluator (``obs.slo``) both need a *series* — "the last N windows of
``engine_ttft_seconds``, with rates and percentiles derivable per
window". This module is that read substrate:

- :meth:`History.scrape_registry` pumps an in-process
  :class:`~tensorflowonspark_tpu.obs.registry.Registry` snapshot
  (serve_model's pump thread, bench drive loops);
- :meth:`History.record_families` pumps parsed Prometheus expositions —
  the shape the driver-side ``MetricsAggregator`` scrapes off every
  node (``obs.cluster`` wires this in);
- :meth:`History.series` / :meth:`rate` / :meth:`percentile` /
  :meth:`fraction_le` are the query surface, each over a trailing
  wall-clock window;
- every appended point optionally spills to JSONL
  (``spill_path``), so a run leaves its full telemetry history on
  disk, and :meth:`to_artifact` packages the rings for run
  artifacts (windowed history instead of a point snapshot).

Per-series rings are ``deque(maxlen=capacity)`` — memory is bounded by
``capacity * series-cardinality`` regardless of run length.

Point shapes (one dict per scrape, stored as ``(t_unix, entry)``):
counter/gauge ``{"value", "delta"}``; histogram ``{"count", "sum",
"delta_count", "delta_sum", "le", "buckets", "delta_buckets"}`` with
cumulative bucket counts (``count`` is the implicit ``+Inf`` bound),
exactly :meth:`Registry.window`'s entry shape.
"""

from __future__ import annotations

import json
import re
import threading
import time
from collections import deque
from typing import Any, Iterable, Mapping

from tensorflowonspark_tpu.obs.registry import Registry, _label_str

__all__ = ["History"]

_LABEL_PAIR = re.compile(r'(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:\\.|[^"\\])*)",?')


def _labels_key(labels: Mapping[str, Any] | str | None) -> str:
    """Normalize a label set to the registry-rendered ``{k="v",...}``
    string (the series key)."""
    if labels is None:
        return ""
    if isinstance(labels, str):
        return labels
    return _label_str(tuple(sorted((k, str(v)) for k, v in labels.items())))


def _parse_label_str(label_str: str) -> dict[str, str]:
    if not label_str:
        return {}
    out: dict[str, str] = {}
    for m in _LABEL_PAIR.finditer(label_str.strip("{}")):
        v = m.group("v")
        out[m.group("k")] = (
            v.replace(r"\n", "\n").replace(r"\"", '"').replace(r"\\", "\\")
        )
    return out


class History:
    """Bounded per-series rings of windowed metric scrapes."""

    def __init__(
        self,
        capacity: int = 512,
        spill_path: str | None = None,
        source: str = "",
    ):
        self.capacity = max(2, int(capacity))
        self.source = source
        self._lock = threading.Lock()
        #: (name, label_str) -> deque[(t_unix, entry)]
        self._series: dict[tuple[str, str], deque] = {}  # guarded-by: self._lock
        self._kinds: dict[str, str] = {}  # guarded-by: self._lock
        self._points = 0  # lifetime appended points  # guarded-by: self._lock
        self._spill_path = spill_path
        self._spill_f = None  # lazily opened  # guarded-by: self._lock

    # -- write surface ------------------------------------------------

    def record_point(
        self,
        name: str,
        labels: Mapping[str, Any] | str | None,
        kind: str,
        entry: Mapping[str, Any],
        t: float | None = None,
    ) -> None:
        t = time.time() if t is None else float(t)
        key = (name, _labels_key(labels))
        entry = dict(entry)
        with self._lock:
            ring = self._series.get(key)
            if ring is None:
                ring = self._series[key] = deque(maxlen=self.capacity)
            ring.append((t, entry))
            self._kinds[name] = kind
            self._points += 1
            if self._spill_path is not None:
                if self._spill_f is None:
                    self._spill_f = open(self._spill_path, "a")
                json.dump(
                    {"t": round(t, 3), "name": name, "labels": key[1],
                     "kind": kind, **entry},
                    self._spill_f,
                )
                self._spill_f.write("\n")

    def scrape_registry(self, registry: Registry, t: float | None = None) -> int:
        """One ``registry.window()`` snapshot into the rings; returns
        the number of points appended. NOTE: ``window()`` deltas are
        relative to the registry's previous ``window()`` call — give
        each registry ONE pumping History or the windows interleave."""
        t = time.time() if t is None else float(t)
        n = 0
        for name, fam in registry.window().items():
            for label_str, entry in fam["series"].items():
                self.record_point(name, label_str, fam["kind"], entry, t=t)
                n += 1
        return n

    def record_families(
        self,
        families: Mapping[str, Mapping[str, Any]],
        extra_labels: Mapping[str, str] | None = None,
        t: float | None = None,
    ) -> int:
        """Parsed Prometheus expositions (``parse_prometheus_text``'s
        ``{family: {"type", "samples": {(sample, label_items): v}}}``)
        into the rings — the driver aggregator's per-node scrapes.
        Histogram families are regrouped (``_bucket``/``_sum``/
        ``_count`` samples under one entry per label set); deltas are
        computed against each series' previous point. ``extra_labels``
        (e.g. ``{"node": "3"}``) joins every sample's label set."""
        t = time.time() if t is None else float(t)
        extra = tuple(sorted((extra_labels or {}).items()))
        n = 0
        for fam_name, data in families.items():
            kind = data.get("type") or "untyped"
            samples = data.get("samples") or {}
            if kind == "histogram":
                # label-set (minus le) -> {"le": {bound: v}, "sum", "count"}
                grouped: dict[tuple, dict[str, Any]] = {}
                for (sname, label_items), value in samples.items():
                    items = tuple(
                        (k, v) for k, v in label_items if k != "le"
                    ) + extra
                    g = grouped.setdefault(
                        items, {"le": {}, "sum": 0.0, "count": 0}
                    )
                    if sname.endswith("_bucket"):
                        bound = dict(label_items).get("le", "+Inf")
                        g["le"][bound] = value
                    elif sname.endswith("_sum"):
                        g["sum"] = value
                    elif sname.endswith("_count"):
                        g["count"] = int(value)
                for items, g in grouped.items():
                    finite = sorted(
                        (float(b), v)
                        for b, v in g["le"].items()
                        if b not in ("+Inf", "inf")
                    )
                    entry = {
                        "count": g["count"],
                        "sum": g["sum"],
                        "le": [b for b, _ in finite],
                        "buckets": [int(v) for _, v in finite],
                    }
                    label_str = _label_str(tuple(sorted(items)))
                    prev = self._last_entry(fam_name, label_str)
                    pb = (prev or {}).get("buckets") or [0] * len(finite)
                    if len(pb) != len(finite):
                        pb = [0] * len(finite)
                    entry["delta_count"] = entry["count"] - (
                        (prev or {}).get("count") or 0
                    )
                    entry["delta_sum"] = entry["sum"] - (
                        (prev or {}).get("sum") or 0.0
                    )
                    entry["delta_buckets"] = [
                        b - p for b, p in zip(entry["buckets"], pb)
                    ]
                    self.record_point(fam_name, label_str, kind, entry, t=t)
                    n += 1
            else:
                for (sname, label_items), value in samples.items():
                    label_str = _label_str(tuple(sorted(label_items + extra)))
                    prev = self._last_entry(sname, label_str)
                    entry = {
                        "value": value,
                        "delta": value - ((prev or {}).get("value") or 0.0),
                    }
                    self.record_point(sname, label_str, kind, entry, t=t)
                    n += 1
        return n

    def _last_entry(self, name: str, label_str: str) -> dict | None:
        with self._lock:
            ring = self._series.get((name, label_str))
            return dict(ring[-1][1]) if ring else None

    # -- query surface ------------------------------------------------

    def names(self) -> list[str]:
        with self._lock:
            return sorted({name for name, _ in self._series})

    def labels_of(self, name: str) -> list[str]:
        with self._lock:
            return sorted(ls for n, ls in self._series if n == name)

    def series(
        self,
        name: str,
        labels: Mapping[str, Any] | str | None = None,
        last_n: int | None = None,
    ) -> list[tuple[float, dict[str, Any]]]:
        """The ring for one series, oldest first — THE read substrate
        the autotune controller consumes. ``labels`` is a dict or the
        rendered ``{k="v"}`` string; ``last_n`` trims to the newest N
        points."""
        key = (name, _labels_key(labels))
        with self._lock:
            ring = self._series.get(key)
            pts = [(t, dict(e)) for t, e in ring] if ring else []
        return pts[-last_n:] if last_n else pts

    def _matching_keys(
        self, name: str, labels: Mapping[str, Any] | str | None
    ) -> list[str]:
        """Series keys for a selector: ``None`` matches every label
        set of ``name`` (Prometheus-style sum), a string is the exact
        rendered key, and a dict is a label-SUBSET filter (``{"route":
        "generate"}`` matches every series carrying that pair)."""
        with self._lock:
            all_ls = [ls for n, ls in self._series if n == name]
        if labels is None:
            return sorted(all_ls)
        if isinstance(labels, str):
            return [labels] if labels in all_ls else []
        want = {(k, str(v)) for k, v in labels.items()}
        return sorted(
            ls
            for ls in all_ls
            if want <= set(_parse_label_str(ls).items())
        )

    def _window_points(
        self, name, label_str, window_s, now
    ) -> list[tuple[float, dict[str, Any]]]:
        now = time.time() if now is None else now
        pts = self.series(name, label_str)
        if window_s is None:
            return pts
        lo = now - float(window_s)
        return [p for p in pts if p[0] >= lo]

    def rate(
        self,
        name: str,
        labels: Mapping[str, Any] | str | None = None,
        window_s: float | None = 60.0,
        now: float | None = None,
    ) -> float | None:
        """Per-second increase of a counter (or histogram ``count``)
        over the trailing window, summed over matching series; None
        without any series holding >= 2 in-window points."""
        total = None
        for ls in self._matching_keys(name, labels):
            pts = self._window_points(name, ls, window_s, now)
            if len(pts) < 2:
                continue
            (t0, e0), (t1, e1) = pts[0], pts[-1]
            if t1 <= t0:
                continue
            v0 = e0.get("value", e0.get("count", 0.0))
            v1 = e1.get("value", e1.get("count", 0.0))
            total = (total or 0.0) + (v1 - v0) / (t1 - t0)
        return total

    def delta(
        self,
        name: str,
        labels: Mapping[str, Any] | str | None = None,
        window_s: float | None = 60.0,
        now: float | None = None,
    ) -> float:
        """Total increase over the window (sum of point deltas across
        matching series — robust to ring eviction mid-window). 0.0
        with no points."""
        out = 0.0
        for ls in self._matching_keys(name, labels):
            pts = self._window_points(name, ls, window_s, now)
            out += sum(
                e.get("delta", e.get("delta_count", 0.0)) for _, e in pts
            )
        return float(out)

    def delta_sum(
        self,
        name: str,
        labels: Mapping[str, Any] | str | None = None,
        window_s: float | None = 60.0,
        now: float | None = None,
    ) -> float:
        """Total increase of a histogram's ``sum`` over the window
        (seconds spent, bytes moved, ...), summed across matching
        series — the time-share complement of :meth:`delta`'s count
        view; autotune overhead hints read this. 0.0 with no points."""
        out = 0.0
        for ls in self._matching_keys(name, labels):
            pts = self._window_points(name, ls, window_s, now)
            out += sum(e.get("delta_sum", 0.0) for _, e in pts)
        return float(out)

    def _bucket_deltas(
        self, name, labels, window_s, now
    ) -> tuple[list[float], list[float], float] | None:
        """Summed (le, delta_buckets, delta_count) over the window and
        matching series; None when nothing histogram-shaped matched."""
        le: list[float] | None = None
        acc: list[float] = []
        total = 0.0
        for ls in self._matching_keys(name, labels):
            for _, e in self._window_points(name, ls, window_s, now):
                if "delta_buckets" not in e:
                    continue
                if le is None:
                    le = list(e.get("le") or [])
                    acc = [0.0] * len(le)
                if list(e.get("le") or []) != le:
                    continue  # bucket layout changed mid-window: skip
                for i, d in enumerate(e["delta_buckets"]):
                    acc[i] += d
                total += e.get("delta_count", 0.0)
        if le is None:
            return None
        return le, acc, total

    def fraction_le(
        self,
        name: str,
        bound: float,
        labels: Mapping[str, Any] | str | None = None,
        window_s: float | None = 60.0,
        now: float | None = None,
    ) -> float | None:
        """Fraction of the window's observations <= ``bound`` (linear
        interpolation inside the straddling bucket) — the latency-SLO
        compliance ratio. None with no observations in the window."""
        bd = self._bucket_deltas(name, labels, window_s, now)
        if bd is None:
            return None
        le, acc, total = bd
        if total <= 0:
            return None
        prev_edge = 0.0
        prev_cum = 0.0
        for edge, cum in zip(le, acc):
            if bound <= edge:
                if edge <= prev_edge:
                    return min(1.0, cum / total)
                frac_in = (bound - prev_edge) / (edge - prev_edge)
                est = prev_cum + (cum - prev_cum) * max(0.0, min(1.0, frac_in))
                return min(1.0, est / total)
            prev_edge, prev_cum = edge, cum
        return 1.0 if bound >= (le[-1] if le else 0.0) else min(
            1.0, prev_cum / total
        )

    def percentile(
        self,
        name: str,
        q: float,
        labels: Mapping[str, Any] | str | None = None,
        window_s: float | None = 60.0,
        now: float | None = None,
    ) -> float | None:
        """The q-quantile (0..1) of the window's observations, linearly
        interpolated over cumulative bucket deltas; observations above
        the top finite bucket clamp to it (Prometheus convention)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        bd = self._bucket_deltas(name, labels, window_s, now)
        if bd is None:
            return None
        le, acc, total = bd
        if total <= 0 or not le:
            return None
        want = q * total
        prev_edge = 0.0
        prev_cum = 0.0
        for edge, cum in zip(le, acc):
            if cum >= want:
                if cum <= prev_cum:
                    return edge
                return prev_edge + (edge - prev_edge) * (
                    (want - prev_cum) / (cum - prev_cum)
                )
            prev_edge, prev_cum = edge, cum
        return le[-1]

    # -- export -------------------------------------------------------

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "series": len(self._series),
                "points": self._points,
                "capacity": self.capacity,
            }

    def to_artifact(
        self,
        last_n: int | None = None,
        names: Iterable[str] | None = None,
    ) -> dict[str, Any]:
        """The rings as a JSON-safe artifact — what bench commits
        instead of a point snapshot."""
        want = set(names) if names is not None else None
        with self._lock:
            keys = sorted(self._series)
            kinds = dict(self._kinds)
            series = []
            for name, label_str in keys:
                if want is not None and name not in want:
                    continue
                pts = list(self._series[(name, label_str)])
                if last_n:
                    pts = pts[-last_n:]
                series.append(
                    {
                        "name": name,
                        "labels": label_str,
                        "kind": kinds.get(name, "untyped"),
                        "points": [
                            {"t": round(t, 3), **e} for t, e in pts
                        ],
                    }
                )
        return {
            "history_version": 1,
            "source": self.source,
            "capacity": self.capacity,
            "series": series,
        }

    def close(self) -> None:
        with self._lock:
            f, self._spill_f = self._spill_f, None
        if f is not None:
            f.close()
