"""A share of the HBM roofline for a model with delta-rule linear-attention
layers and routed experts, from the device trace and the window's
counters.

The decode steps are those the trace itself holds: the calls of the
operations matching ``params["step_pattern"]`` (one a linear layer and
step) over the linear layers. ``params["what"]``:

- ``state``: every linear layer's state of every slot read once and
  written once (``counts_solar_open2.kda_step_bytes``), against the summed
  device time of the operations matching ``params["pattern"]``: those that
  produce the new state;
- ``experts``: the banks of the experts a step's rows reach and the rows in
  and out (``experts_step_bytes``, from ``engine_moe_experts_reached_total``
  and ``engine_moe_local_assignments_total`` a decode step), against the
  grouped products matching ``params["pattern"]``;
- ``step``: all a step must move (``decode_step_parts``: the fixed
  weights, the reached experts, the live K/V, and the recurrent leaves of
  the live slots once in and once out, which the program counts itself:
  ``engine_recurrent_state_bytes_total`` a decode step), against the summed
  device time of the programs matching ``params["module_pattern"]``.

Nothing matching, no such counter, or another model: nothing returned.
"""

import re

from perfbench import counts_solar_open2 as counts


def _delta(reg: dict, name: str):
    m = reg.get(name)
    return m["series"][""]["delta"] if m and "" in m["series"] else None


def read(record: dict, params: dict):
    t, traced, peak = record.get("trace"), record.get("traced"), record.get("peak")
    cfg, reg = record.get("cfg", {}), record.get("registry") or {}
    if not t or not traced or not peak or "linear_attn_config" not in cfg:
        return None
    step_pat = re.compile(params["step_pattern"])
    traced_steps = (sum(c for n, (c, _) in t["ops"].items() if step_pat.search(n))
                    / counts.n_layers(cfg)[1])
    what = params["what"]
    if what == "step":
        pat = re.compile(params["module_pattern"])
        spent = sum(s for n, (_, s) in t["modules"].items() if pat.search(n))
    else:
        pat = re.compile(params["pattern"])
        spent = sum(s for n, (_, s) in t["ops"].items() if pat.search(n))
    if not spent or not traced_steps:
        return None
    if what == "state":
        need = counts.kda_step_bytes(cfg, record["slots"])
    else:
        steps = _delta(reg, "engine_decode_steps_total")
        reached = _delta(reg, "engine_moe_experts_reached_total")
        pairs = _delta(reg, "engine_moe_local_assignments_total")
        if not steps or reached is None or pairs is None:
            return None
        if what == "experts":
            need = counts.experts_step_bytes(cfg, reached / steps, pairs / steps)
        else:
            recurrent = _delta(reg, "engine_recurrent_state_bytes_total")
            if recurrent is None:
                return None
            parts = counts.decode_step_parts(
                cfg, traced["live_kv_tokens"], record["slots"], reached / steps,
                pairs / steps)
            need = (parts["fixed"] + parts["experts"] + parts["kv"]
                    + 2 * recurrent / steps)
    return 100.0 * traced_steps * need / peak["hbm_bytes_per_s"] / spent
