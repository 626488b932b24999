#!/usr/bin/env python3
"""Read, on the chip and in one process, what a cell's limits are set
from: the program's numbers against the reference on many seeds (the lower
reading is their largest) and the control's and the planted faults' on the
first few (the upper reading is their smallest).

    python3 perfbench/tools/limits.py --workload <cell> --seeds 12 \
        --controls 3 [--seconds 8] [--first-seed 1000] [--rehearse]

Prints one JSON line per seed and a summary last. The benchmark's own runs
never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    workload, config, traffic = harness.load_cell(a.workload)
    import jax

    harness.enable_compile_cache()
    if jax.devices()[0].platform != "tpu" and not a.rehearse:
        print("limits: no TPU", file=sys.stderr)
        return 3
    driver = importlib.import_module("perfbench.drivers." + workload["driver"])
    rows = []
    for n in range(a.seeds):
        seed = a.first_seed + 7919 * n
        ctx = harness.Context(
            workload=workload, config=config, traffic=traffic, seed=seed,
            seconds=a.seconds, trace=False, t_start=time.time(),
            spans=harness.Spans(), tracer=None, peak=None,
        )
        t0 = time.time()
        row = driver.limit_readings(ctx, n < a.controls)
        row.update(seed=seed, seconds=round(time.time() - t0, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary: dict = {}
    for side, pick in (("program", max), ("control_fp8", min), ("fault_half_batch", min)):
        have = [r[side] for r in rows if side in r]
        if have:
            summary[side + ("_max" if pick is max else "_min")] = {
                k: pick(h[k] for h in have) for k in have[0]
            }
    print(json.dumps({"summary": summary, "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
