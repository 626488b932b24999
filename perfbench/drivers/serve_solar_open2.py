"""Driver kind ``serve_solar_open2``: the continuous-batching engine over
Solar-Open2, one chip's share of an expert-parallel deployment, in this
process.

The window, the clients' stamps, the sample and the comparison are those
of ``drivers/serve.py`` (``drive``, ``end_to_end``, ``live_kv_tokens``,
``sample``, ``compare``), through ``drivers/serve_pangu_moe.py``'s
``drive``: every seed serves the pool from its first request on
(``pool_start_seed``, PERF.md section 6, PR 31), and the checks that a few
far-off tokens cannot move (``robust_checks``) beside the two largest, for
that driver's reason: where bfloat16 and float32 choose another 8th
expert, a held expert's whole contribution appears or vanishes. The
``record`` carries the same keys, so that the serve cells' readers read
it. Its own: the model and its seeded weights (``weights_solar_open2``),
the FLOPs of the window's work done here (``counts_solar_open2``), and the
reference's side of ``correct`` (``reference_solar_open2``: the delta rule
a position at a time, the full causal softmax, a loop over the held
experts given the same shard; its head reduced block by block).
"""

from __future__ import annotations

# first thing, before any weight is made: a program without the model
# fails here, at once
from tensorflowonspark_tpu.models.solar_open2 import SolarOpen2, from_hf_config

import functools
import gc
import time

import numpy as np

from perfbench import counts_solar_open2, reference_solar_open2, weights_solar_open2
from perfbench.drivers import serve, serve_pangu_moe
from perfbench.drivers.serve_pangu_moe import (  # model_keys: the same shard keys
    diff_notes,
    model_keys,
    robust_checks,
    routed_notes,
    served_diff,
    window_notes,
)
from perfbench.harness import Check, memory_peak_bytes


def build_model(config: dict, cfg: dict) -> SolarOpen2:
    import jax.numpy as jnp

    run = config["run"]
    return SolarOpen2(from_hf_config(
        config, n_routed_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"], first_expert=cfg["first_expert"],
        max_seq_len=run["max_seq_len"], dtype=jnp.dtype(run["compute_dtype"]),
    ))


class Program:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from tensorflowonspark_tpu.serving.engine import ContinuousBatcher

        self.cfg = model_keys(ctx.config)
        self.run = ctx.config["run"]
        self.key = weights_solar_open2.seed_key(ctx.seed)
        self.dtype = jnp.dtype(self.run["param_dtype"])
        self.model = build_model(ctx.config, self.cfg)
        params = jax.jit(
            lambda k: weights_solar_open2.make_params(self.cfg, k, self.dtype)
        )(self.key)
        # every option the configuration does not name stays at the
        # constructor's default
        self.engine = ContinuousBatcher(
            self.model, params, slots=self.run["slots"],
            prompt_widths=tuple(self.run["prompt_widths"]),
        )
        self.engine.warmup()

    def release(self) -> None:
        self.engine.close()
        self.engine = None
        gc.collect()


def reference_leaves(cfg: dict, key, store_dtype, edit=None):
    """``get_leaf(name)`` for the reference: the same leaf from the same
    key, rounded to the dtype it is stored in, as float32. ``edit(name,
    leaf)`` (the sensitivity tool's) may change it."""
    import jax
    import jax.numpy as jnp

    specs = {"/".join(p): (n, s, k)
             for n, (p, s, k) in enumerate(weights_solar_open2.leaf_specs(cfg))}

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def make(k, index, shape, kind):
        return weights_solar_open2.make_leaf(k, index, shape, kind, store_dtype).astype(jnp.float32)

    def get_leaf(name: str):
        n, shape, kind = specs[name]
        leaf = make(key, np.int32(n), shape, kind)
        return leaf if edit is None else edit(name, leaf)

    get_leaf.names = list(specs)
    return get_leaf


def reference_gaps(prog: Program, picked: list[dict], check: dict, mm: str = "highest",
                   also=None) -> dict:
    """One full forward pass of the reference over each sampled request:
    ``serve_pangu_moe.reference_gaps``'s readings, from this model's
    reference and leaves."""
    n = check["sample"]
    rows = (picked * n)[:n]  # a short sample is repeated: one shape, one program
    need = max(len(r["prompt"]) + len(r["tokens"]) for r in rows)
    length = next(b for b in sorted(check["lengths"]) if b >= need)
    width = check["tokens"]
    seqs = np.zeros((n, length), np.int32)
    at = np.zeros((n, width), np.int32)
    tok = np.zeros((n, width), np.int32)
    valid = np.zeros((n, width), bool)
    for i, r in enumerate(rows):
        row = r["prompt"] + r["tokens"]
        seqs[i, : len(row)] = row
        k = len(r["tokens"])
        at[i, :k] = len(r["prompt"]) - 1 + np.arange(k)
        tok[i, :k] = r["tokens"]
        valid[i, :k] = i < len(picked)
    toks = tok[..., None] if also is None else np.stack([tok, also], axis=-1)
    best, top, lse, got = (np.asarray(x) for x in reference_solar_open2.serve_readings(
        prog.cfg, reference_leaves(prog.cfg, prog.key, prog.dtype), seqs, at, toks,
        mm=mm, blocks=check.get("blocks", 4), vocab_blocks=check.get("vocab_blocks", 8),
    ))
    return {"gap": (best - got[..., 0])[valid], "logp": (got[..., 0] - lse)[valid],
            "top": top, "best": best, "valid": valid,
            "also_logit": None if also is None else got[..., 1]}


def run(ctx) -> dict:
    prog = Program(ctx)
    ctx.spans.reset()
    out = serve_pangu_moe.drive(ctx, prog)
    records, t0, t1 = out["records"], out["t0"], out["t1"]
    peak_bytes = memory_peak_bytes()
    prog.release()

    if ctx.fault is not None:
        ctx.fault(records)
    check = ctx.workload["check"]
    t_check = time.perf_counter()
    picked = serve.sample(records, ctx.seed, check["sample"])
    failed = sum(bool(r["error"]) or len(r["tokens"]) != r["n_out"] for r in records)
    failed += out["unfinished"]
    if picked:
        ref = reference_gaps(prog, picked, check)
        diff = served_diff(picked, ref)
        checks = serve.compare(picked, ref, check["limits"])
        checks += robust_checks(diff, check, check["limits"])
        compared, spread = int(ref["valid"].sum()), diff_notes(diff)
    else:
        checks, compared = [Check("logit_gap", float("nan"), check["limits"]["logit_gap"])], 0
        spread = None
    checks.append(Check("failed_requests", float(failed), 0.0))
    check_s = time.perf_counter() - t_check

    window = t1 - t0
    # the work done inside the window: a request's prompt once its first
    # token has come, and the completion tokens received before the close
    flops = sum(
        counts_solar_open2.serve_flops(
            prog.cfg, len(r["prompt"]), sum(t <= t1 for t in r["times"]))
        for r in records if r["times"] and r["times"][0] <= t1
    )
    traced = out["traced"]
    if traced:
        traced["live_kv_tokens"] = serve.live_kv_tokens(records, traced["t0"], traced["t1"])
    summary = ctx.tracer.reduce() if ctx.trace else None
    client = serve.end_to_end(records, t0, t1)
    setup_s = out["t0_wall"] - ctx.t_start
    return {
        "attempted": len(records) + out["unfinished"],
        "failed": failed,
        "setup_s": setup_s,
        "end_to_end": client,
        "checks": checks,
        "memory_peak_bytes": peak_bytes,
        "notes": {"setup_s": setup_s, "check_s": check_s, "window_s": window,
                  "requests": len(records), "tokens_compared": compared,
                  "decode_steps": out["steps"],
                  "tokens": sum(len(r["tokens"]) for r in records),
                  "logprob_diff": spread, "routed_a_step": routed_notes(out["registry"]),
                  "window": window_notes(records, t0, t1, prog.run["slots"]),
                  "cache_bytes": out["registry"].get("engine_cache_bytes", {}).get("series")},
        "record": {
            "cfg": prog.cfg, "spans": ctx.spans.durations, "window_s": window,
            "flops": flops, "steps": out["steps"], "registry": out["registry"],
            "slots": prog.run["slots"], "trace": summary, "traced": traced, "client": client,
            "peak": ctx.peak,
        },
    }


def limit_readings(ctx, with_control: bool) -> dict:
    """For ``perfbench/tools/limits.py``: ``serve_pangu_moe.limit_readings``
    over this model: a short window at the cell's own load, the served
    tokens' readings against the reference and, where asked, the float8
    control's at the same positions."""
    prog = Program(ctx)
    out = serve_pangu_moe.drive(ctx, prog)
    prog.release()
    check = ctx.workload["check"]
    picked = serve.sample(out["records"], ctx.seed, check["sample"])
    loose = {k: float("inf") for k in check["limits"]}
    ctl = reference_gaps(prog, picked, check, mm="fp8") if with_control else None
    ref = reference_gaps(prog, picked, check, also=ctl["top"] if ctl else None)
    checks = serve.compare(picked, ref, loose) + robust_checks(
        served_diff(picked, ref), check, loose)
    res = {"program": {c.name: c.value for c in checks},
           "requests": len(out["records"]), "tokens_compared": int(ref["valid"].sum())}
    if ctl:
        diff = np.abs(ctl["logp"] - ref["logp"])
        res["control_fp8"] = {
            "logit_gap": float((ref["best"] - ref["also_logit"])[ref["valid"]].max()),
            "logprob_diff": float(diff.max()),
            **{c.name: c.value for c in robust_checks(diff, check, loose)},
        }
    return res
