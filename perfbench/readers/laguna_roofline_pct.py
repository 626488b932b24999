"""Shares of a roofline for the Laguna model, from the device trace and
the window's counters. The decode steps are those the trace itself holds:
the calls of the decode-attention kernel over all slots, one a layer and
step (``params["step_pattern"]``, ``{slots}`` standing for the engine's
slots). What a step read and routed is the window's mean, from the
program's counters a decode step: the K/V positions the full layers' planes
and the windowed layers' rings were read at
(``engine_decode_kv_positions_read_by_kind_total``), the experts reached
(``engine_moe_experts_reached_total``) and the pairs routed to them
(``engine_moe_local_assignments_total``). ``params["what"]``:

- ``decode``: all a step must move (``counts_laguna.decode_step_parts``:
  every weight but the routed banks, the banks reached with their rows,
  K/V at the positions read) over the bandwidth, against the summed device
  time of the programs matching ``params["module_pattern"]``;
- ``plane``: the full layers' K/V at the positions read, against the
  kernel calls matching ``params["pattern"]`` (``{heads}`` standing for
  the full layers' query heads, which tell their calls from the windowed
  layers');
- ``experts``: the banks reached and their rows, against the grouped
  products matching ``params["pattern"]`` (``{rows}`` standing for a
  decode step's pairs, slots x experts a token, in whole tiles of 128);
- ``prefill``: the model FLOPs of the prefills whose first token came
  inside the traced interval, as many as the trace ran of the programs
  matching ``params["module_pattern"]``, over the peak, against those
  programs' device time.

Nothing matching, no such counter, or another model: nothing returned.
"""

import re

from perfbench import counts_laguna as counts


def _delta(reg: dict, name: str, label: str = ""):
    m = reg.get(name)
    return m["series"][label]["delta"] if m and label in m["series"] else None


def _ops(t: dict, pattern: str, **fill):
    for k, v in fill.items():
        pattern = pattern.replace("{%s}" % k, str(v))
    pat = re.compile(pattern)
    return [(c, s) for n, (c, s) in t["ops"].items() if pat.search(n)]


def _modules(t: dict, pattern: str):
    pat = re.compile(pattern)
    return [(c, s) for n, (c, s) in t["modules"].items() if pat.search(n)]


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    cfg, reg = record.get("cfg", {}), record.get("registry") or {}
    if not t or not traced or not peak or cfg.get("model_type") != "laguna":
        return None
    what, slots = params["what"], record["slots"]
    if what == "prefill":
        done = traced.get("prefill_flops")
        runs = _modules(t, params["module_pattern"])
        spent = sum(s for _, s in runs)
        if not done or not done["count"] or not spent:
            return None
        flops = done["flops"] / done["count"] * sum(c for c, _ in runs)
        return 100.0 * flops / peak["flops"] / spent
    steps = _delta(reg, "engine_decode_steps_total")
    kind = "engine_decode_kv_positions_read_by_kind_total"
    full = _delta(reg, kind, '{kind="full"}')
    window = _delta(reg, kind, '{kind="window"}')
    reached = _delta(reg, "engine_moe_experts_reached_total")
    pairs = _delta(reg, "engine_moe_local_assignments_total")
    if not steps or None in (full, window, reached, pairs):
        return None
    parts = counts.decode_step_parts(cfg, full / steps, window / steps, reached / steps,
                                     pairs / steps)
    traced_steps = (sum(c for c, _ in _ops(t, params["step_pattern"], slots=slots))
                    / cfg["num_hidden_layers"])
    if what == "decode":
        spent = sum(s for _, s in _modules(t, params["module_pattern"]))
        need = sum(parts.values())
    elif what == "plane":
        calls = _ops(t, params["pattern"], slots=slots, heads=cfg["num_attention_heads"])
        spent = sum(s for _, s in calls)
        need = parts["planes"]
    else:
        k = cfg["num_experts_per_tok"]
        spent = sum(s for _, s in _ops(t, params["pattern"], rows=-(-slots * k // 128) * 128))
        need = parts["experts"]
    if not spent or not traced_steps:
        return None
    return 100.0 * traced_steps * need / peak["hbm_bytes_per_s"] / spent
