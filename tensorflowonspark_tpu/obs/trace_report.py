"""Trace attribution: where a captured profiler trace's time goes.

Promoted from ``benchmarks/trace_summary.py`` (which remains as an
import shim): the profiler (``benchmarks/real_chip.py --profile DIR``,
``bench.py --trace``, or any ``jax.profiler.trace``) writes a
TensorBoard-readable run under ``DIR/plugins/profile/<run>/`` containing
a Chrome-trace export ``*.trace.json.gz``. TensorBoard isn't part of
this environment's loop, so this module answers the questions the trace
was captured for directly:

1. **Self time** (:func:`self_times`): per-(lane, op) nesting-aware
   durations — events that overlap hierarchically within one thread
   (XLA module > fusion > op) would double-count if summed naively, so
   each event's self time subtracts its nested children.
2. **Attribution** (:func:`attribution`): every op classified into
   MXU/matmul, vector/fusion, copy/layout, infeed/outfeed, collective,
   or host — the breakdown that turns "an MFU with a large non-MXU
   residual" from a mystery into a table.
3. **Report artifact** (:func:`build_report` / :func:`write_report`):
   one JSON dict with lane totals, top ops, and the attribution table —
   what ``bench.py`` commits under ``benchmarks/results/`` on every
   traced run so build-but-don't-measure is structurally impossible.

CLI (also exposed as ``python -m tensorflowonspark_tpu.tools.trace_report``)::

    python -m tensorflowonspark_tpu.tools.trace_report /tmp/profile \
        [--top 30] [--lane TPU] [--json report.json]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import warnings

__all__ = [
    "find_trace_files",
    "load_events",
    "self_times",
    "classify_op",
    "is_device_lane",
    "attribution",
    "build_report",
    "write_report",
    "main",
]

# Classifier categories, in report order. Patterns target XLA/HLO op
# names as they appear in trace event names (``fusion.123``,
# ``%dot.45``, ``copy-start``, ``all-reduce.7``, ``infeed`` ...); the
# first matching category wins, so transfer/copy names are tested
# before the broad vector fallback. ``weight_update`` is tested first
# of all: ops lowered under the train step's
# ``jax.named_scope("train.weight_update")`` (the optimizer update —
# Adam moments, masters, and the ZeRO reduce-scatter/all-gather pair)
# carry the scope in their metadata-derived names, and the optimizer
# fraction of step time is exactly what the ``bench.py --zero`` A/B
# reads out of a committed ``*_trace_report.json``.
CATEGORIES = (
    "weight_update", "mxu", "vector", "copy", "infeed", "collective",
    "host",
)

_PATTERNS = (
    # the train step's optimizer scope (see compute/train.make_step_fn)
    ("weight_update", re.compile(r"train\.weight_update", re.I)),
    # device-to-device / host-device data movement and layout changes
    ("infeed", re.compile(
        r"infeed|outfeed|host-to-device|device-to-host|"
        r"\btransfer|send|recv", re.I)),
    ("collective", re.compile(
        r"all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective|ppermute|permute", re.I)),
    ("mxu", re.compile(
        r"\bdot\b|dot[._-]|conv(?:olution)?[._-]|\bconv\b|einsum|"
        r"matmul|\bgemm\b|cublas|mxu", re.I)),
    ("copy", re.compile(
        r"copy|transpose|bitcast|reshape|broadcast|concatenate|"
        r"\bslice\b|slice[._-]|dynamic-slice|dynamic-update-slice|"
        r"\bpad\b|pad[._-]|gather[._-]|\bgather\b|scatter", re.I)),
)


def classify_op(name: str, device: bool = True) -> str:
    """Category for one op name. Host-lane events are all ``host`` —
    attribution contrasts device-side MXU vs residual against host
    glue, not host function names against each other."""
    if not device:
        return "host"
    for cat, pat in _PATTERNS:
        if pat.search(name):
            return cat
    return "vector"


def is_device_lane(lane_name: str) -> bool:
    """Heuristic over trace process-lane names: TPU/GPU/XLA device
    lanes hold op activity; everything else (python, TSL, plugins) is
    host."""
    n = lane_name.lower()
    return any(
        key in n for key in ("/device:", "tpu", "gpu", "xla:", "stream")
    ) and "host" not in n


def find_trace_files(root: str) -> list[str]:
    pats = [
        os.path.join(root, "**", "*.trace.json.gz"),
        os.path.join(root, "**", "*.trace.json"),
        # flight-recorder dumps (obs.flightrec) embed a trace export
        os.path.join(root, "**", "flightrec-*.json"),
    ]
    out: list[str] = []
    for p in pats:
        out.extend(glob.glob(p, recursive=True))
    return sorted(out)


def resolve_inputs(paths) -> list[str]:
    """Expand a path — or a list of paths — into trace files: a
    directory contributes every trace/flightrec file under it, a file
    is taken as-is. Order is deterministic (input order, dirs sorted
    within)."""
    if isinstance(paths, str):
        paths = [paths]
    out: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(find_trace_files(p))
        else:
            out.append(p)
    return out


def load_events(path: str) -> dict:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        data = json.load(f)
    if "traceEvents" not in data and isinstance(data.get("spans"), dict):
        # flight-recorder dump (obs.flightrec): the span export is the
        # trace — per-node postmortems read like any captured profile
        data = data["spans"]
    return data


def self_times(events: list[dict]) -> "collections.Counter[tuple]":
    """Per-(pid, tid) nesting-aware self time, keyed by (pid, name).

    Chrome-trace complete events within one thread nest like a call
    stack. Sort by (start, -dur); maintain a stack of open intervals; an
    event's self time is its duration minus the portions of its direct
    children that fall INSIDE it.

    Real call stacks nest strictly. Events that only PARTIALLY overlap
    violate that model; naively subtracting each child's full duration
    then yields negative self time, which a summed report silently
    launders into plausible-looking wrong totals. So: a child only
    charges its parent for the overlapping portion, per-event self time
    is clamped at zero, and detection of non-nested overlap raises a
    ``RuntimeWarning`` — the trace is malformed and its attribution is
    approximate. Lanes holding externally-measured intervals
    (``tid="interval:<name>"``, from ``obs.spans`` ``record()``) are
    not call stacks at all: they skip nesting attribution and each
    event simply owns its full duration.
    """
    per_thread: dict = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        per_thread[(e.get("pid"), e.get("tid"))].append(e)

    self_us: "collections.Counter[tuple]" = collections.Counter()
    non_nested = 0
    for (pid, tid), evs in per_thread.items():
        if isinstance(tid, str) and tid.startswith("interval:"):
            # externally-measured intervals (``SpanTracer.record``):
            # independent durations, not a call stack — concurrent
            # requests' queue waits overlap freely and each owns its
            # full duration; nesting attribution does not apply
            for e in evs:
                self_us[(pid, e["name"])] += e["dur"]
            continue
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: list[dict] = []  # open events, each with _child_us accumulator
        for e in evs:
            ts, dur = e["ts"], e["dur"]
            while stack and ts >= stack[-1]["_end"]:
                done = stack.pop()
                self_us[(pid, done["name"])] += max(
                    0, done["dur"] - done["_child_us"]
                )
            if stack:
                inside = min(ts + dur, stack[-1]["_end"]) - ts
                if inside < dur:
                    non_nested += 1
                stack[-1]["_child_us"] += max(0, inside)
            e = dict(e, _child_us=0, _end=ts + dur)
            stack.append(e)
        while stack:
            done = stack.pop()
            self_us[(pid, done["name"])] += max(
                0, done["dur"] - done["_child_us"]
            )
    if non_nested:
        warnings.warn(
            f"{non_nested} trace event(s) overlap a same-lane event "
            "without nesting inside it (call-stack events must nest "
            "strictly); self-time attribution clamped the overlap — "
            "treat per-op self times on the affected lanes as "
            "approximate",
            RuntimeWarning,
            stacklevel=2,
        )
    return self_us


def lane_names(events: list[dict]) -> dict:
    """pid -> process lane name, from the trace's metadata events."""
    names: dict = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e.get("pid")] = e.get("args", {}).get("name", "")
    return names


def attribution(
    self_us: "collections.Counter[tuple]", pid_names: dict
) -> dict:
    """Classify per-op self time into the category table.

    Returns ``{"categories": {cat: {"us": int, "pct": float}},
    "device_total_us": int, "host_total_us": int,
    "mxu_fraction": float}`` where ``pct`` and ``mxu_fraction`` are
    relative to DEVICE self time (the MFU question); host time is
    reported beside it, not mixed in.
    """
    cat_us: "collections.Counter[str]" = collections.Counter()
    device_total = 0
    host_total = 0
    for (pid, name), us in self_us.items():
        device = is_device_lane(pid_names.get(pid, str(pid)))
        cat = classify_op(name, device=device)
        cat_us[cat] += us
        if device:
            device_total += us
        else:
            host_total += us
    cats = {
        c: {
            "us": int(cat_us.get(c, 0)),
            "pct": round(
                100.0 * cat_us.get(c, 0) / device_total, 2
            )
            if device_total and c != "host"
            else (0.0 if c != "host" else None),
        }
        for c in CATEGORIES
    }
    # host pct is relative to (device + host): "of all measured self
    # time, how much never touched the chip"
    total = device_total + host_total
    cats["host"]["pct"] = (
        round(100.0 * host_total / total, 2) if total else 0.0
    )
    return {
        "categories": cats,
        "device_total_us": int(device_total),
        "host_total_us": int(host_total),
        "mxu_fraction": (
            round(cat_us.get("mxu", 0) / device_total, 4)
            if device_total
            else 0.0
        ),
        # the optimizer fraction of device time — the number the ZeRO
        # cross-replica weight update (bench.py --zero) exists to shrink
        "weight_update_fraction": (
            round(cat_us.get("weight_update", 0) / device_total, 4)
            if device_total
            else 0.0
        ),
    }


def build_report(trace_dir, top: int = 30) -> dict:
    """Aggregate trace inputs into one report dict: per-file lanes +
    top ops by self time, and a combined attribution table.

    ``trace_dir`` is a directory (every trace/flightrec file under it),
    a single file, or a LIST of directories/files — one merged report
    over a driver trace plus N per-node flight-recorder dumps is
    ``build_report(["driver.trace.json", *glob("logs/flightrec-*")])``.
    Raises FileNotFoundError when no input resolves to a trace file
    (callers decide whether that's fatal)."""
    inputs = trace_dir if isinstance(trace_dir, (list, tuple)) else [trace_dir]
    files = resolve_inputs(inputs)
    if not files:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] / flightrec-*.json under {inputs}"
        )
    first = str(inputs[0])
    rel_root = first if os.path.isdir(first) else os.path.dirname(first)
    combined: "collections.Counter[tuple]" = collections.Counter()
    combined_names: dict = {}
    file_reports = []
    for path in files:
        events = load_events(path).get("traceEvents", [])
        pid_names = lane_names(events)
        self_us = self_times(events)
        # pids can collide across files; prefix with the file index
        idx = len(file_reports)
        for (pid, name), us in self_us.items():
            combined[((idx, pid), name)] += us
        for pid, nm in pid_names.items():
            combined_names[(idx, pid)] = nm
        lane_total: "collections.Counter" = collections.Counter()
        for (pid, _name), us in self_us.items():
            lane_total[pid] += us
        lanes = []
        for pid, total in lane_total.most_common():
            ops = sorted(
                (
                    (n, us)
                    for (p, n), us in self_us.items()
                    if p == pid
                ),
                key=lambda kv: -kv[1],
            )
            lanes.append(
                {
                    "pid": pid,
                    "name": pid_names.get(pid, str(pid)),
                    "device": is_device_lane(
                        pid_names.get(pid, str(pid))
                    ),
                    "total_us": int(total),
                    "top_ops": [
                        {
                            "name": n,
                            "us": int(us),
                            "category": classify_op(
                                n,
                                device=is_device_lane(
                                    pid_names.get(pid, str(pid))
                                ),
                            ),
                        }
                        for n, us in ops[:top]
                    ],
                }
            )
        under_root = os.path.abspath(path).startswith(
            os.path.abspath(rel_root) + os.sep
        )
        file_reports.append(
            {
                "file": (
                    os.path.relpath(path, rel_root) if under_root else path
                ),
                "lanes": lanes,
            }
        )
    return {
        "trace_dir": os.path.abspath(first),
        "inputs": [str(p) for p in inputs],
        "files": file_reports,
        "attribution": attribution(combined, combined_names),
    }


def write_report(
    trace_dir: str, out_path: str, top: int = 30, report: dict | None = None
) -> dict:
    """Write the JSON report (building it from ``trace_dir`` unless a
    prebuilt ``report`` is passed — callers that already hold one must
    not re-parse the trace files); returns the report dict."""
    if report is None:
        report = build_report(trace_dir, top=top)
    parent = os.path.dirname(os.path.abspath(out_path))
    os.makedirs(parent, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return report


def _print_attribution(att: dict, out) -> None:
    print("\n== attribution (device self time)", file=out)
    for cat in CATEGORIES:
        row = att["categories"][cat]
        pct = row["pct"]
        pct_s = f"{pct:5.1f}%" if pct is not None else "     -"
        print(f"  {cat:<10} {row['us']/1e3:10.3f} ms  {pct_s}", file=out)
    print(
        f"  device total {att['device_total_us']/1e3:.3f} ms, host "
        f"total {att['host_total_us']/1e3:.3f} ms, MXU fraction "
        f"{att['mxu_fraction']:.3f}",
        file=out,
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report")
    ap.add_argument(
        "trace_dir",
        nargs="+",
        help="profile directory, trace file(s), and/or flight-recorder "
        "dump(s) — multiple inputs merge into one report",
    )
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument(
        "--lane",
        default=None,
        help="only lanes whose name contains this substring (e.g. 'TPU')",
    )
    ap.add_argument(
        "--json",
        default=None,
        help="also write the full report dict to this path",
    )
    args = ap.parse_args(argv)

    # Parse the (potentially tens-of-MB gzipped) trace files ONCE; the
    # lane tables, attribution, and --json artifact all print from the
    # same report dict.
    try:
        report = build_report(args.trace_dir, top=args.top)
    except FileNotFoundError as e:
        print(e, file=sys.stderr)
        return 1

    for fr in report["files"]:
        print(f"== {fr['file']}")
        for lane in fr["lanes"]:
            if args.lane and args.lane.lower() not in lane["name"].lower():
                continue
            total = lane["total_us"]
            print(
                f"\n-- lane pid={lane['pid']} {lane['name']!r}: "
                f"total self-time {total/1e3:.2f} ms"
            )
            for op in lane["top_ops"]:
                pct = 100.0 * op["us"] / total if total else 0.0
                print(
                    f"  {op['us']/1e3:10.3f} ms  {pct:5.1f}%  "
                    f"{op['name'][:120]}"
                )

    _print_attribution(report["attribution"], sys.stdout)
    if args.json:
        write_report(args.trace_dir, args.json, report=report)
        print(f"report written to {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
