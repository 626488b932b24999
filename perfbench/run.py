#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process that owns the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: ``perfbench/workloads/<cell>.json`` names the
driver kind (a module of ``perfbench/drivers``), the configuration
(``perfbench/configs/<config>.json``) and the traffic mix
(``perfbench/traffic/<traffic>.json``); every ``perfbench/metrics/*.json``
that lists the cell, or that the cell lists, is a per-layer metric read by
the module of ``perfbench/readers`` it names. Adding a cell, a
configuration, a mix or a metric is adding files.

Set-up (imports, weights, compiles or cache reads, warm-up, the proof
steps) is ``setup_s``; then the window of ``--seconds``; then the program's
state is freed and the plain reference decides ``correct``. The numbers
compared are printed beside their limits as the last lines of standard
error; the last line of standard output is the result.

Without a TPU the run fails. ``--rehearse`` lets the flow run on whatever
JAX finds (the CPU, at a tiny configuration); its numbers are printed under
``rehearsal.<name>``, never under a metric's own name.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def per_layer_metrics(cell: str, workload: dict) -> list[dict]:
    """The metric files that list the cell, and those the cell lists."""
    out = []
    for path in sorted(glob.glob(os.path.join(ROOT, "perfbench", "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if cell in m.get("workloads", ()) or m["name"] in workload.get("per_layer", ()):
            out.append(m)
    return out


def main(argv=None, fault=None) -> int:
    t_start = harness.process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU; numbers go under rehearsal.<name>")
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1: write the planes, lines and most "
                    "costly names of the trace there, to be read by hand")
    a = ap.parse_args(argv)

    workload, config, traffic = harness.load_cell(a.workload)

    # fails here, before anything starts, where the program is absent
    import tensorflowonspark_tpu  # noqa: F401

    import jax

    harness.enable_compile_cache()
    device = harness.device_report()
    chips = workload.get("chips", 1)
    if not a.rehearse and (device["platform"] != "tpu" or device["count"] < chips):
        print(
            f"perfbench: {a.workload} needs {chips} TPU chip(s); JAX found "
            f"{device['count']} x {device['platform']} ({device['kind']}). "
            "No chip, no number (--rehearse runs the flow without one).",
            file=sys.stderr,
        )
        return 3
    peak = None
    if device["platform"] == "tpu":
        from perfbench import peaks

        peak = peaks.peak_for(device["kind"])  # an unknown kind is an error

    spans = harness.Spans()
    ctx = harness.Context(
        workload=workload, config=config, traffic=traffic,
        seed=a.seed, seconds=a.seconds, trace=bool(a.trace), t_start=t_start, spans=spans,
        tracer=harness.Trace(a.workload, spans, a.dump_trace) if a.trace else None,
        peak=peak, fault=fault,
    )
    driver = importlib.import_module("perfbench.drivers." + workload["driver"])
    out = driver.run(ctx)

    if a.trace:
        values = {}
        for m in per_layer_metrics(a.workload, workload):
            reader = importlib.import_module("perfbench.readers." + m["reader"])
            v = reader.read(out["record"], m.get("params", {}))
            if v is not None:  # nothing to read: the metric is left out
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        units = workload["end_to_end"]
        values = {"setup_s": {"value": out["setup_s"], "unit": "s"}}
        values.update(
            {k: {"value": out["end_to_end"][k], "unit": u} for k, u in units.items()}
        )
    if a.rehearse:
        values = {"rehearsal." + k: v for k, v in values.items()}

    checks = out["checks"]
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {
        "correct": bool(checks) and all(c.ok for c in checks) and out["failed"] == 0,
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": values,
        "device": device,
    }
    trace = out["record"].get("trace")
    if a.trace and trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    result["workload"] = a.workload
    result["seed"] = a.seed
    result["notes"] = out["notes"]
    result["compared"] = {c.name: c.as_dict() for c in checks}

    sys.stdout.flush()
    for c in checks:
        print(f"compared {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (emitters, prefetchers) must not keep
    # the interpreter from ending once the result is out
    os._exit(rc)
