"""A kernel family's share of its roofline, from the device trace.

``params["kernels"]`` lists ``{"pattern": <regex on the operation's short
name, ``trace_reduce.short``: name, result shapes and kind>,
"which": <key of counts.FLASH_MATMULS>}``. Each call's least time is the
larger of its FLOPs over the peak and its bytes over the bandwidth, from
``perfbench/counts.py`` at the traced steps' mean batch; the share is the
sum of least times over the sum of the device times of the matching
events. Nothing matching: nothing returned.
"""

import re

from perfbench import counts


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    if not t or not traced or not peak:
        return None
    cfg = record["cfg"]
    pairs = traced["pairs"] / traced["steps"]
    positions = traced["positions"] // traced["steps"]
    least = spent = 0.0
    for k in params["kernels"]:
        pat = re.compile(k["pattern"])
        calls = sum(c for n, (c, _) in t["ops"].items() if pat.search(n))
        spent += sum(s for n, (_, s) in t["ops"].items() if pat.search(n))
        least += calls * max(
            counts.flash_call_flops(cfg, k["which"], pairs) / peak["flops"],
            counts.flash_call_bytes(cfg, k["which"], positions) / peak["hbm_bytes_per_s"],
        )
    return 100.0 * least / spent if spent else None
