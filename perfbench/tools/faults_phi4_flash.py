#!/usr/bin/env python3
"""Plant one fault underneath the timed path of the SambaY serve cell and
run the cell as ``perfbench/run.py`` does: ``correct`` has to come out
false.

    python3 perfbench/tools/faults_phi4_flash.py --fault <name> \
        --workload <cell> --seed <n> --seconds <s> [--rehearse]

- ``second_map_left_out``: lambda = 0, the second softmax map of every
  differential head is not taken away;
- ``subln_left_out``: the differential heads' RMSNorm is not applied (its
  scale and ``1 - lambda_init`` are);
- ``window_ignored``: the windowed layers' prefill attends every position
  before (``dot_product_attention`` without its window); decode keeps
  the ring;
- ``cross_reads_ring``: the cross layers attend the last windowed layer's
  K/V (its ring, in decode) in place of the full layer's;
- ``gmu_memory_zeroed``: the GMUs' memory is zeros;
- ``scan_D_left_out``: the scan runs with ``D = 0``, no skip term;
- ``rope_applied``: the attention layers rotate queries and keys
  (``use_rope`` true, theta 10000);
- ``state_bfloat16``: the scan state is rounded to bfloat16 after every
  call, as a cache in the model's dtype would hold it.

``--faults a,b,c`` runs several, one after the other in this process (one
machine, one start-up), each under a seed of its own (``--seed`` plus
7919 a fault). ``--sensitivity`` instead reads, in the reference alone on
one row of random tokens, how far zeroing a part moves the logit of each
position's best token (median and 5th percentile over positions): the
scan's own term, the windowed layers, the full layer, the cross layers,
the GMUs, the lambdas' learned part.

The benchmark's own runs never call this.
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
def _patched(owner, name, new):
    orig = getattr(owner, name)
    setattr(owner, name, new(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def second_map_left_out():
    from tensorflowonspark_tpu.models import llama

    def first_maps_only(orig):
        def call(self, out):  # rows 2i + 1 hold the second maps
            return orig(self, out.at[:, :, 1::2].set(0))
        return call

    return _patched(llama.Attention, "_differential", first_maps_only)


@contextlib.contextmanager
def subln_left_out():
    import jax as real

    from tensorflowonspark_tpu.models import llama

    class Lax:
        def __getattr__(self, name):
            return getattr(real.lax, name)

        @staticmethod
        def rsqrt(x):  # the model's only rsqrt in llama.py: the subln's
            return real.numpy.ones_like(x)

    class Jax:
        lax = Lax()

        def __getattr__(self, name):
            return getattr(real, name)

    llama.jax = Jax()
    try:
        yield
    finally:
        llama.jax = real


def window_ignored():
    from tensorflowonspark_tpu.models import llama

    return _patched(llama, "dot_product_attention",
                    lambda orig: lambda *a, window=None, **kw: orig(*a, **kw))


def _attention_proxy(wrap):
    """``phi4_flash.Attention`` replaced by a maker of the same module whose
    calls go through ``wrap(view, module, args, kwargs)``."""
    from tensorflowonspark_tpu.models import phi4_flash

    def make(orig):
        def build(view, name):
            module = orig(view, name=name)
            return lambda *a, **kw: wrap(view, module, a, kw)
        return build

    return _patched(phi4_flash, "Attention", make)


def cross_reads_ring():
    ring = {}

    def wrap(view, module, a, kw):
        if view.sliding_window is not None:
            out, ring["kv"] = module(*a, **kw, return_kv=True)
            return out
        if kw.get("shared_kv") is not None:
            kw = dict(kw, shared_kv=ring["kv"])
        return module(*a, **kw)

    return _attention_proxy(wrap)


def gmu_memory_zeroed():
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import phi4_flash

    def make(orig):
        def build(cfg, name):
            module = orig(cfg, name=name)

            def call(*a, **kw):
                out, y = module(*a, **kw)
                return out, jnp.zeros_like(y)
            return call
        return build

    return _patched(phi4_flash, "Mamba", make)


@contextlib.contextmanager
def _scan(step, scan):
    """The model's two calls into ``ops/selective_scan.py`` wrapped:
    ``step(orig)`` and ``scan(orig)`` return what stands in their place."""
    from tensorflowonspark_tpu.models import phi4_flash

    with _patched(phi4_flash, "selective_scan_step", step), \
            _patched(phi4_flash, "selective_scan", scan):
        yield


def scan_D_left_out():  # noqa: N802 (the fault's name)
    import jax.numpy as jnp

    return _scan(
        lambda orig: lambda S, u, dt, A, B, C, D: orig(S, u, dt, A, B, C, jnp.zeros_like(D)),
        lambda orig: lambda u, dt, A, B, C, D, *a: orig(u, dt, A, B, C, jnp.zeros_like(D), *a),
    )


def state_bfloat16():
    import jax

    def rounded(orig):
        def call(*a, **kw):
            y, S = orig(*a, **kw)
            # bfloat16's bits, kept: XLA may drop a cast there and back
            return y, jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return call

    return _scan(rounded, rounded)


def rope_applied():
    from tensorflowonspark_tpu.models import phi4_flash

    return _patched(
        phi4_flash.Phi4FlashConfig, "attention",
        lambda orig: lambda self, layer: dataclasses.replace(orig(self, layer), use_rope=True))


FAULTS = {f.__name__: f for f in (
    second_map_left_out, subln_left_out, window_ignored, cross_reads_ring,
    gmu_memory_zeroed, scan_D_left_out, rope_applied, state_bfloat16)}

ZEROED = {
    "scan": lambda n: n.endswith("mamba/x_proj/kernel"),  # B and C: the scan's term
    "windowed_layers": lambda n: n.endswith("attn/o_proj/kernel") and _kind(n) == "swa",
    "full_layer": lambda n: n.endswith("attn/o_proj/kernel") and _kind(n) == "full",
    "cross_layers": lambda n: n.endswith("attn/o_proj/kernel") and _kind(n) == "cross",
    "gmus": lambda n: n.endswith("gmu_out/kernel"),
    "lambdas": lambda n: "/attn/lambda_" in n,
}
_KINDS: list = []


def _kind(name: str) -> str:
    return _KINDS[int(name.split("/")[0][len("layer"):])]


def sensitivity(cell: str, seed: int, tokens: int) -> dict:
    import numpy as np

    from perfbench import counts_phi4_flash, harness, reference_phi4_flash, weights_phi4_flash
    from perfbench.drivers import _llama, serve_phi4_flash

    import jax.numpy as jnp

    _, config, _ = harness.load_cell(cell)
    harness.enable_compile_cache()
    cfg = _llama.model_keys(config)
    _KINDS[:] = counts_phi4_flash.layer_kinds(cfg)
    key = weights_phi4_flash.seed_key(seed)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    rng = np.random.default_rng(seed)
    seqs = rng.integers(0, cfg["vocab_size"], size=(1, tokens), dtype=np.int32)
    at = np.arange(tokens, dtype=np.int32)[None, :]
    r = counts_phi4_flash.mamba(cfg)[3]

    def readings(part, toks):
        def edit(name, leaf):
            if part is None or not ZEROED[part](name):
                return leaf
            if part == "scan":  # B's and C's columns, dt's kept
                return leaf.at[:, r:].set(0.0)
            return jnp.zeros_like(leaf)

        get_leaf = serve_phi4_flash.reference_leaves(cfg, key, dtype, edit)
        return [np.asarray(x) for x in reference_phi4_flash.serve_readings(
            cfg, get_leaf, seqs, at, toks, blocks=2, vocab_blocks=8)]

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
