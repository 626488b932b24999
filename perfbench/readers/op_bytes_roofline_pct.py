"""A family of operations' share of the HBM roofline, from the device
trace: the bytes the traced decode steps' work must move, whatever
implements it, over the published bandwidth, against the summed device
time of the operations whose short name (``trace_reduce.short``: name,
result shapes and kind) matches ``params["pattern"]``.

``params["count"]`` names the function of ``perfbench/counts_falcon_h1.py``
that gives one decode step's bytes from the configuration and the
engine's slots. Nothing matching: nothing returned.
"""

import re

from perfbench import counts_falcon_h1


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    if not t or not traced or not peak or not traced.get("steps"):
        return None
    if "mamba_d_state" not in record.get("cfg", {}):
        return None
    pat = re.compile(params["pattern"])
    spent = sum(s for n, (_, s) in t["ops"].items() if pat.search(n))
    if not spent:
        return None
    step = getattr(counts_falcon_h1, params["count"])(record["cfg"], record["slots"])
    return 100.0 * traced["steps"] * step / peak["hbm_bytes_per_s"] / spent
