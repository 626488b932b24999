"""The cached-decode program's share of the HBM roofline.

What the traced decode steps must read (every weight once a step and the
cache rows of the live contexts, ``counts.decode_step_bytes``) over the
published bandwidth, against the summed device time of the programs whose
name matches ``params["module_pattern"]`` in the trace's ``XLA Modules``.
"""

import re

from perfbench import counts


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    if not t or not traced or not peak or not traced.get("steps"):
        return None
    pat = re.compile(params["module_pattern"])
    spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
    if not spent:
        return None
    need = traced["steps"] * counts.decode_step_bytes(record["cfg"], traced["live_kv_tokens"])
    return 100.0 * need / peak["hbm_bytes_per_s"] / spent
