#!/usr/bin/env python3
"""Plant one fault underneath the timed path of the Laguna serve cell and
run the cell as ``perfbench/run.py`` does: ``correct`` has to come out
false.

    python3 perfbench/tools/faults_laguna.py --fault <name> \
        --workload <cell> --seed <n> --seconds <s> [--rehearse]

- ``yarn_left_out``: the full layers rotate at plain RoPE's frequencies,
  cos and sin unscaled;
- ``rotary_whole_head``: the full layers rotate all of a head's channels;
- ``gate_left_out``: no layer gates its heads;
- ``window_ignored``: a prefill's windowed layers attend every position
  before, as the full ones do (decode steps read the rings as before);
- ``scaling_left_out``: the routed weights are not multiplied by 2.5;
- ``shared_left_out``: the model is built without its shared expert.

``--faults a,b,c`` runs several, one after the other in this process (one
machine, one start-up), each under a seed of its own (``--seed`` plus
7919 a fault). ``--sensitivity`` instead reads each fault in the reference
alone, at the cell's size, on one row of ``--tokens`` random tokens (the
cell's longest row by default), and the float8 control beside them: the
faulted (or float8) reference plays the program, greedy at the row's last
``check["tokens"]`` positions, and is held to the float32 reference by
the cell's own four numbers. No engine runs, so no program is compiled
again for each fault. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def _patched(module, name, new):
    orig = getattr(module, name)
    setattr(module, name, new(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _config_override(**over):
    from tensorflowonspark_tpu.models import laguna

    return _patched(laguna, "from_hf_config",
                    lambda orig: lambda hf, **kw: orig(hf, **{**kw, **over}))


def yarn_left_out():
    return _config_override(full_rope_scaling=None)


def rotary_whole_head():
    return _config_override(full_rotary_factor=1.0)


def scaling_left_out():
    return _config_override(routed_scaling_factor=1.0)


def shared_left_out():
    return _config_override(shared_expert_intermediate_size=0)


def gate_left_out():
    from tensorflowonspark_tpu.models import laguna

    return _patched(laguna.LagunaConfig, "attention", lambda orig: lambda self, layer: (
        dataclasses.replace(orig(self, layer), attention_output_gate=False)))


def window_ignored():
    from tensorflowonspark_tpu.models import llama

    def wrap(orig):
        def call(*a, window=None, **kw):
            return orig(*a, window=None, **kw)
        return call

    return _patched(llama, "dot_product_attention", wrap)


FAULTS = {f.__name__: f for f in (
    yarn_left_out, rotary_whole_head, gate_left_out, window_ignored, scaling_left_out,
    shared_left_out)}


FULL = "full_attention"
# each fault as the reference computes it: a change of the published keys,
# or of a leaf
IN_REFERENCE = {
    "yarn_left_out": lambda c: {**c, "rope_parameters": {**c["rope_parameters"], FULL: {
        "rope_type": "default", "rope_theta": c["rope_parameters"][FULL]["rope_theta"],
        "partial_rotary_factor": c["rope_parameters"][FULL]["partial_rotary_factor"]}}},
    "rotary_whole_head": lambda c: {**c, "rope_parameters": {**c["rope_parameters"], FULL: {
        **c["rope_parameters"][FULL], "partial_rotary_factor": 1.0}}},
    "gate_left_out": lambda c: {**c, "gating": False},
    "window_ignored": lambda c: {**c, "sliding_window": 1 << 30},
    "scaling_left_out": lambda c: {**c, "moe_routed_scaling_factor": 1.0},
    "shared_left_out": "moe/shared_down/kernel",
}


def sensitivity(cell: str, seed: int, tokens: int | None, names: list[str]) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from perfbench import harness, reference_laguna, weights_laguna
    from perfbench.drivers import _llama, serve_laguna

    workload, config, _ = harness.load_cell(cell)
    harness.enable_compile_cache()
    check = workload["check"]
    cfg = _llama.model_keys(config)
    key = weights_laguna.seed_key(seed)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    length = tokens or max(check["lengths"])
    width = min(check["tokens"], length)
    seqs = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=(1, length), dtype=np.int32)
    at = np.arange(length - width, length, dtype=np.int32)[None, :]
    blocks, vb = check.get("blocks", 8), check.get("vocab_blocks", 8)

    def readings(c, edit, toks, mm="highest"):
        get_leaf = serve_laguna.reference_leaves(cfg, key, dtype, edit)
        return [np.asarray(x) for x in reference_laguna.serve_readings(
            c, get_leaf, seqs, at, toks, mm=mm, blocks=blocks, vocab_blocks=vb)]

    def edit(change):
        if not isinstance(change, str):
            return None
        return lambda n, leaf: jnp.zeros_like(leaf) if n.endswith(change) else leaf

    # each variant greedy at the read positions: its best token and that
    # token's log-probability under it
    played, own = {}, {}
    for name in names + ["control_fp8"]:
        change = IN_REFERENCE.get(name)
        best, top, lse, _ = readings(
            change(cfg) if callable(change) else cfg, edit(change),
            np.zeros((1, width, 1), np.int32), "fp8" if change is None else "highest")
        played[name], own[name] = top, best - lse
    # the float32 reference's readings of every variant's tokens
    best, _, lse, got = readings(cfg, None, np.stack(list(played.values()), axis=-1))
    out = {"tokens": length, "compared": width, "seed": seed}
    for i, name in enumerate(played):
        diff = np.abs(own[name] - (got[..., i] - lse))[0]
        out[name] = {
            "logit_gap": float((best - got[..., i]).max()),
            "logprob_diff": float(diff.max()),
            "logprob_diff_p50": float(np.median(diff)),
            "logprob_off_pct": float(100.0 * np.mean(diff > check["off_by"])),
        }
        out[name]["fails_by"] = [k for k, v in check["limits"].items() if out[name][k] > v]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--faults", help="several, comma-separated, one after the other")
    ap.add_argument("--sensitivity", action="store_true")
    ap.add_argument("--tokens", type=int)
    a, rest = ap.parse_known_args()
    if a.sensitivity:
        sp = argparse.ArgumentParser()
        sp.add_argument("--workload", required=True)
        sp.add_argument("--seed", type=int, default=0)
        s, _ = sp.parse_known_args(rest)
        names = a.faults.split(",") if a.faults else sorted(IN_REFERENCE)
        print(json.dumps({"sensitivity": sensitivity(s.workload, s.seed, a.tokens, names)}),
              flush=True)
        return 0
    names = a.faults.split(",") if a.faults else [a.fault]
    if not names[0]:
        ap.error("--fault or --faults")
    from perfbench import run

    rc = 0
    at = rest.index("--seed") + 1 if "--seed" in rest else None
    for i, name in enumerate(names):
        argv = list(rest)
        if at and i:
            argv[at] = str(int(rest[at]) + 7919 * i)
        print(f"fault {name}", file=sys.stderr, flush=True)
        with FAULTS[name]():
            rc = run.main(argv) or rc
    return rc


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
