"""The cached-decode program's share of the HBM roofline, for a model
that carries recurrent state beside K/V.

What the traced decode steps must move (``counts_falcon_h1.
decode_step_bytes``: every weight once a step, the K/V rows of the live
contexts, and the recurrent state and convolution window of every slot
read once and written once) over the published bandwidth, against the
summed device time of the programs whose name matches
``params["module_pattern"]`` in the trace's ``XLA Modules``.
"""

import re

from perfbench import counts_falcon_h1


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    if not t or not traced or not peak or not traced.get("steps"):
        return None
    if "mamba_d_state" not in record.get("cfg", {}):
        return None
    pat = re.compile(params["module_pattern"])
    spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
    if not spent:
        return None
    need = traced["steps"] * counts_falcon_h1.decode_step_bytes(
        record["cfg"], traced["live_kv_tokens"], record["slots"])
    return 100.0 * need / peak["hbm_bytes_per_s"] / spent
