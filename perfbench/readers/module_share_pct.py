"""A family of programs' share of the device's time, from the trace: the
summed device time of the ``XLA Modules`` events whose name matches
``params["module_pattern"]``, over the traced window of every chip."""

import re


def read(record: dict, params: dict):
    t = record.get("trace")
    if not t or not t["window_s"]:
        return None
    pat = re.compile(params["module_pattern"])
    spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
    return 100.0 * spent / (t["window_s"] * t["chips"]) if spent else None
