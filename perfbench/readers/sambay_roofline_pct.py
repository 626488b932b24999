"""Shares of a roofline for the SambaY model (Phi-4-mini-flash), from the
device trace and the window's counters. ``params["what"]``:

- ``decode``: all a decode step must move (``counts_phi4_flash.
  decode_step_parts``: every weight, the shared plane's live rows for the
  full layer and each cross layer, the rings' written positions, the
  recurrent leaves of the live slots in and out, which the program counts
  itself: ``engine_recurrent_state_bytes_total`` a decode step) over the
  published bandwidth, against the summed device time of the programs
  matching ``params["module_pattern"]``. The steps are those the trace
  holds: the calls of the operations matching ``params["step_pattern"]``
  (``{slots}`` standing for the engine's slots: the decode-attention
  kernel's, one a step for each ring, the full layer and each cross
  layer);
- ``step``: every Mamba layer's scan state of every slot read once and
  written once (``mamba_step_bytes``) a traced step, against the operations
  matching ``params["pattern"]`` (``{slots}`` as above): those that
  produce the new state;
- ``scan``: the prefill scan kernel's calls matching ``params["pattern"]``,
  each one's least time the larger of its FLOPs over the peak and its
  bytes over the bandwidth (``scan_call``, its rows and width read from
  the call's result shape), against their summed device time.

Nothing matching, no such counter, or another model: nothing returned.
"""

import re

from perfbench import counts_phi4_flash as counts


def _delta(reg: dict, name: str):
    m = reg.get(name)
    return m["series"][""]["delta"] if m and "" in m["series"] else None


def _ops(t: dict, pattern: str, slots: int):
    pat = re.compile(pattern.replace("{slots}", str(slots)))
    return [(n, c, s) for n, (c, s) in t["ops"].items() if pat.search(n)]


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    cfg, reg = record.get("cfg", {}), record.get("registry") or {}
    if not t or not traced or not peak or cfg.get("model_type") != "phi4flash":
        return None
    slots, what = record["slots"], params["what"]
    if what == "scan":
        least = spent = 0.0
        for name, calls, secs in _ops(t, params["pattern"], slots):
            rows, width = (int(x) for x in re.search(r"f32\[(\d+),(\d+),", name).groups())
            flops, moved = counts.scan_call(cfg, rows, width)
            least += calls * max(flops / peak["flops"], moved / peak["hbm_bytes_per_s"])
            spent += secs
        return 100.0 * least / spent if spent else None
    # a decode step's kernel calls: each ring, the full layer, each cross layer
    calls = counts.n_of(cfg, "swa") + 1 + counts.n_of(cfg, "cross")
    steps = sum(c for _, c, _ in _ops(t, params["step_pattern"], slots)) / calls
    if what == "step":
        spent = sum(s for _, _, s in _ops(t, params["pattern"], slots))
        need = counts.mamba_step_bytes(cfg, slots)
    else:
        pat = re.compile(params["module_pattern"])
        spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
        decode_steps = _delta(reg, "engine_decode_steps_total")
        recurrent = _delta(reg, "engine_recurrent_state_bytes_total")
        window = traced.get("live_window_tokens")
        if not decode_steps or recurrent is None or window is None:
            return None
        need = sum(counts.decode_step_parts(
            cfg, traced["live_kv_tokens"], window, recurrent / decode_steps).values())
    if not spent or not steps:
        return None
    return 100.0 * steps * need / peak["hbm_bytes_per_s"] / spent
