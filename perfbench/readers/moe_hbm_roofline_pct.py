"""A share of the HBM roofline for a model with routed experts and a
latent cache, from the device trace and the window's counters.

The decode steps are those the trace itself holds: the calls of the
operations matching ``params["step_pattern"]`` (one a layer and step)
over the layers. What a step routed is the window's mean, from the
program's counters: held experts reached (``engine_moe_experts_reached_
total``) and pairs routed to them (``engine_moe_local_assignments_total``)
a decode step. ``params["what"]``:

- ``experts``: the bytes of the banks of the experts a step's rows reach
  (never of an expert nothing was routed to) and of the rows in and out
  (``counts_pangu_moe.experts_step_bytes``), against the summed device
  time of the operations matching ``params["pattern"]`` (the grouped
  products);
- ``step``: all a step must move (``decode_step_bytes``: every other
  weight, the reached experts, the live latent rows), against the summed
  device time of the programs matching ``params["module_pattern"]``.

Nothing matching, no such counter, or another model: nothing returned.
"""

import re

from perfbench import counts_pangu_moe


def _delta(reg: dict, name: str):
    m = reg.get(name)
    return m["series"][""]["delta"] if m and "" in m["series"] else None


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    cfg, reg = record.get("cfg", {}), record.get("registry") or {}
    if not t or not traced or not peak or "kv_lora_rank" not in cfg:
        return None
    steps = _delta(reg, "engine_decode_steps_total")
    reached = _delta(reg, "engine_moe_experts_reached_total")
    pairs = _delta(reg, "engine_moe_local_assignments_total")
    if not steps or reached is None or pairs is None:
        return None
    step_pat = re.compile(params["step_pattern"])
    traced_steps = (sum(c for n, (c, _) in t["ops"].items() if step_pat.search(n))
                    / cfg["num_hidden_layers"])
    if params["what"] == "experts":
        pat = re.compile(params["pattern"])
        spent = sum(s for n, (_, s) in t["ops"].items() if pat.search(n))
        need = counts_pangu_moe.experts_step_bytes(cfg, reached / steps, pairs / steps)
    else:
        pat = re.compile(params["module_pattern"])
        spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
        need = counts_pangu_moe.decode_step_bytes(
            cfg, traced["live_kv_tokens"], reached / steps, pairs / steps)
    if not spent or not traced_steps:
        return None
    return 100.0 * traced_steps * need / peak["hbm_bytes_per_s"] / spent
