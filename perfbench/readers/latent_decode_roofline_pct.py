"""The latent decode-attention kernel's share of its roofline, from the
device trace.

Per call the least time is the larger of its FLOPs over the peak and its
bytes over the bandwidth, for the rows' *live* positions whatever
implements it (``counts_pangu_moe.latent_call_least_s``: 278,528 FLOPs and
1,152 B a cached position at the published widths, the mean summed
context of the decoding requests over the traced interval); the share is
the calls' least time over the summed device time of the operations whose
short name (``trace_reduce.short``) matches ``params["pattern"]``. Nothing
matching, or a model without a latent cache: nothing returned.
"""

import re

from perfbench import counts_pangu_moe


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    cfg = record.get("cfg", {})
    if not t or not traced or not peak or "kv_lora_rank" not in cfg:
        return None
    pat = re.compile(params["pattern"])
    calls = sum(c for n, (c, _) in t["ops"].items() if pat.search(n))
    spent = sum(s for n, (_, s) in t["ops"].items() if pat.search(n))
    if not spent:
        return None
    least = counts_pangu_moe.latent_call_least_s(
        cfg, traced["live_kv_tokens"], record["slots"], peak)
    return 100.0 * calls * least / spent
