#!/usr/bin/env python3
"""Plant one fault underneath the timed path of the delta-rule expert serve
cell and run the cell as ``perfbench/run.py`` does: ``correct`` has to
come out false.

    python3 perfbench/tools/faults_solar_open2.py --fault <name> \
        --workload <cell> --seed <n> --seconds <s> [--rehearse]

- ``decay_left_out``: the delta rule runs with ``alpha = 1`` (the state
  never forgets);
- ``beta_not_doubled``: the model is built with ``kda_allow_neg_eigval``
  false;
- ``l2norm_left_out``: queries and keys of the linear layers are not
  normalised a head;
- ``conv_left_out``: the three convolutions hand their input on;
- ``state_bfloat16``: the delta-rule state is rounded to bfloat16 after
  every call, as a cache in the model's dtype would hold it;
- ``output_gate_left_out``: the attention is built without its gate;
- ``rope_applied``: the attention rotates queries and keys (``use_rope``
  true);
- ``shared_left_out``: the model is built without its shared expert;
- ``expert_tokens_dropped``: the pairs routed to the fourth held expert
  are dropped before the dispatch (``faults_pangu_moe``'s).

``--faults a,b,c`` runs several, one after the other in this process (one
machine, one start-up), each under a seed of its own (``--seed`` plus
7919 a fault). ``--sensitivity`` instead reads, in the reference
alone on one row of random tokens, how far changing a part moves the logit
of each position's best token (median and 5th percentile over positions):
the linear layers, the attention layer, the shared expert or the held
experts zeroed, ``alpha = 1``, ``beta`` left undoubled.

The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.tools.faults_pangu_moe import expert_tokens_dropped  # noqa: E402


@contextlib.contextmanager
def _patched(module, name, new):
    orig = getattr(module, name)
    setattr(module, name, new(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _config_override(**over):
    from perfbench.drivers import serve_solar_open2

    return _patched(serve_solar_open2, "from_hf_config",
                    lambda orig: lambda hf, **kw: orig(hf, **{**kw, **over}))


def beta_not_doubled():
    return _config_override(kda_allow_neg_eigval=False)


def output_gate_left_out():
    return _config_override(attention_output_gate=False)


def rope_applied():
    return _config_override(use_rope=True)


def shared_left_out():
    return _config_override(n_shared_experts=0)


@contextlib.contextmanager
def _delta_rule(step, chunked):
    """The model's two calls into ``ops/kda.py`` wrapped: ``step(orig)``
    and ``chunked(orig)`` return what stands in their place."""
    from tensorflowonspark_tpu.models import solar_open2

    with _patched(solar_open2, "kda_step", step), _patched(solar_open2, "kda_chunked", chunked):
        yield


def decay_left_out():
    import jax.numpy as jnp

    return _delta_rule(
        lambda orig: lambda S, q, k, v, alpha, beta: orig(S, q, k, v, jnp.ones_like(alpha), beta),
        lambda orig: lambda q, k, v, g, beta, **kw: orig(q, k, v, jnp.zeros_like(g), beta, **kw),
    )


def state_bfloat16():
    import jax.numpy as jnp

    def rounded(orig):
        def call(*a, **kw):
            o, S = orig(*a, **kw)
            return o, S.astype(jnp.bfloat16).astype(jnp.float32)
        return call

    return _delta_rule(rounded, rounded)


def conv_left_out():
    from tensorflowonspark_tpu.models import solar_open2

    return _patched(solar_open2, "causal_conv1d",
                    lambda orig: lambda x, w, b, window=None, valid=None: (x, window))


@contextlib.contextmanager
def l2norm_left_out():
    import jax.numpy as real

    from tensorflowonspark_tpu.models import solar_open2

    class Jnp:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def sum(x, *a, **kw):  # the model's only sum: the squared norm a head
            return real.ones_like(real.sum(x, *a, **kw))

    solar_open2.jnp = Jnp()
    try:
        yield
    finally:
        solar_open2.jnp = real


FAULTS = {f.__name__: f for f in (
    decay_left_out, beta_not_doubled, l2norm_left_out, conv_left_out, state_bfloat16,
    output_gate_left_out, rope_applied, shared_left_out, expert_tokens_dropped)}

ZEROED = {
    "linear_layers": lambda n: n.endswith("mixer/o_proj/kernel"),
    "attention_layer": lambda n: n.endswith("attn/o_proj/kernel"),
    "shared_expert": lambda n: n.endswith("moe/shared_down/kernel"),
    "held_experts": lambda n: n.endswith("moe/w_down"),
    "alpha_one": lambda n: n.endswith("mixer/A_log"),
    "beta_undoubled": lambda n: False,
}


def sensitivity(cell: str, seed: int, tokens: int) -> dict:
    import numpy as np

    from perfbench import harness, reference_solar_open2, weights_solar_open2
    from perfbench.drivers import serve_solar_open2

    import jax.numpy as jnp

    _, config, _ = harness.load_cell(cell)
    harness.enable_compile_cache()
    cfg = serve_solar_open2.model_keys(config)
    key = weights_solar_open2.seed_key(seed)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    rng = np.random.default_rng(seed)
    seqs = rng.integers(0, cfg["vocab_size"], size=(1, tokens), dtype=np.int32)
    at = np.arange(tokens, dtype=np.int32)[None, :]

    def readings(part, toks):
        def edit(name, leaf):
            if part is None or not ZEROED[part](name):
                return leaf
            # exp(A_log) = 0: no decay; every other part: nothing comes out
            return jnp.full_like(leaf, -1e9) if part == "alpha_one" else jnp.zeros_like(leaf)

        get_leaf = serve_solar_open2.reference_leaves(cfg, key, dtype, edit)
        c = dict(cfg, kda_allow_neg_eigval=False) if part == "beta_undoubled" else cfg
        return [np.asarray(x) for x in reference_solar_open2.serve_readings(
            c, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=8)]

    best, top, _, _ = readings(None, np.zeros((1, tokens, 1), np.int32))
    out = {"tokens": tokens, "seed": seed}
    for part in ZEROED:
        _, _, _, got = readings(part, top[..., None])
        moved = np.abs(best - got[..., 0])[0, tokens // 8:]  # past the first few positions
        out[part] = {"median": float(np.median(moved)), "p5": float(np.percentile(moved, 5))}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--faults", help="several, comma-separated, one after the other")
    ap.add_argument("--sensitivity", action="store_true")
    ap.add_argument("--tokens", type=int, default=1024)
    a, rest = ap.parse_known_args()
    if a.sensitivity:
        sp = argparse.ArgumentParser()
        sp.add_argument("--workload", required=True)
        sp.add_argument("--seed", type=int, default=0)
        s, _ = sp.parse_known_args(rest)
        print(json.dumps({"sensitivity": sensitivity(s.workload, s.seed, a.tokens)}), flush=True)
        return 0
    names = a.faults.split(",") if a.faults else [a.fault]
    if not names[0]:
        ap.error("--fault, --faults or --sensitivity")
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
