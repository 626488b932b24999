"""Driver kind ``serve_pangu_moe``: the continuous-batching engine over
openPangu-Ultra-MoE, one chip's share of an expert-parallel deployment, in
this process.

The window, the clients' stamps, the sample and the comparison are those
of ``drivers/serve.py`` (``drive``, ``end_to_end``, ``live_kv_tokens``,
``sample``, ``compare``), and the ``record`` carries the same keys, so
that the serve cells' readers read it. Its own: every seed serves the
pool from its first request on (``pool_start_seed``), the model and its seeded
weights (``weights_pangu_moe``), the FLOPs of the window's work done here
(``counts_pangu_moe``: the held experts at their expected share of a
token's choices), and the reference's side of ``correct``
(``reference_pangu_moe``: the expanded attention and a loop over the held
experts, given the same shard; its head reduced block by block).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import itertools
import time

import numpy as np

from perfbench import counts_pangu_moe, reference_pangu_moe, traffic, weights_pangu_moe
from perfbench.drivers import _llama, serve
from perfbench.harness import Check, memory_peak_bytes


def model_keys(config: dict) -> dict:
    """What the reference and the counts read: the file's published keys
    (``n_routed_experts``: the experts held here), and from its
    ``deployment`` the router's range and the first expert held."""
    cfg = _llama.model_keys(config)
    cfg["router_experts"] = config["deployment"]["router_experts"]
    cfg["first_expert"] = config["deployment"]["first_expert"]
    return cfg


class Program:
    def __init__(self, ctx):
        # first thing, before any weight is made: a program without the
        # model fails here, at once
        from tensorflowonspark_tpu.models.pangu_moe import PanguMoE, from_hf_config

        import jax
        import jax.numpy as jnp

        from tensorflowonspark_tpu.serving.engine import ContinuousBatcher

        self.cfg = model_keys(ctx.config)
        self.run = ctx.config["run"]
        self.key = weights_pangu_moe.seed_key(ctx.seed)
        self.dtype = jnp.dtype(self.run["param_dtype"])
        self.model = PanguMoE(from_hf_config(
            ctx.config, n_routed_experts=self.cfg["router_experts"],
            experts_held=self.cfg["n_routed_experts"],
            first_expert=self.cfg["first_expert"],
            max_seq_len=self.run["max_seq_len"],
            dtype=jnp.dtype(self.run["compute_dtype"]),
        ))
        params = jax.jit(
            lambda k: weights_pangu_moe.make_params(self.cfg, k, self.dtype)
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


def pool_start_seed(spec: dict, seed: int, vocab: int, head: int = 4) -> int:
    """The seed under which ``traffic.requests`` begins a pool in rotation
    at the pool's first request: the first of ``seed``, ``seed + 2**32``,
    ``seed + 2 * 2**32``, ... whose first ``head`` requests have the sizes
    of the pool's first ``head``. Found through the generator itself, not
    by knowing how it draws. A mix that is not in rotation keeps ``seed``.

    Why: the generator lets a seed choose where the rotation starts. A
    window of this cell ends before its pool does (about 280 of 512
    requests), so that choice decides *which* requests a run serves; the
    engine prefills one request at a time, so their prompt widths decide
    how much of the window is prefill, and the seeds spread 1.0 to 1.8 %
    in tokens/s by it (PERF.md §6, PR 31: a property of the program, kept
    there). Every seed now serves the pool from its first request on, the
    same lengths in the same order: the same work. The seed still decides
    every token, the weights and the sample."""
    if spec.get("order", "permute") != "rotate":
        return seed
    first = traffic.request_sizes(spec)[:head]
    for k in itertools.count():
        s = seed + (k << 32)
        begun = itertools.islice(traffic.requests(spec, s, vocab), head)
        if [(len(p), o) for p, o in begun] == first:
            return s


def drive(ctx, prog: Program) -> dict:
    """``serve.drive``'s window, over the pool from its first request."""
    start = pool_start_seed(ctx.traffic, ctx.seed, prog.cfg["vocab_size"])
    return serve.drive(dataclasses.replace(ctx, seed=start), prog)


def reference_leaves(cfg: dict, key, store_dtype, edit=None):
    """``get_leaf(name)`` for the reference: the same leaf from the same
    key, rounded to the dtype it is stored in, as float32. ``edit(name,
    leaf)`` (the sensitivity tool's) may change it."""
    import jax
    import jax.numpy as jnp

    specs = {"/".join(p): (n, s, k)
             for n, (p, s, k) in enumerate(weights_pangu_moe.leaf_specs(cfg))}

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def make(k, index, shape, kind):
        return weights_pangu_moe.make_leaf(k, index, shape, kind, store_dtype).astype(jnp.float32)

    def get_leaf(name: str):
        n, shape, kind = specs[name]
        leaf = make(key, np.int32(n), shape, kind)
        return leaf if edit is None else edit(name, leaf)

    get_leaf.names = list(specs)
    return get_leaf


def reference_gaps(prog: Program, picked: list[dict], check: dict, mm: str = "highest",
                   also=None) -> dict:
    """One full forward pass of the reference over each sampled request.

    Per served token (flattened): how far its logit lies below the
    reference's best there and the reference's log-probability of it;
    per position, the reference's own best token and logit. ``also``
    (rows, tokens) int32: further token ids whose logits to read at the
    same positions (``also_logit``).
    """
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
    best, top, lse, got = (np.asarray(x) for x in reference_pangu_moe.serve_readings(
        prog.cfg, reference_leaves(prog.cfg, prog.key, prog.dtype), seqs, at, toks,
        mm=mm, blocks=check.get("blocks", 4), vocab_blocks=check.get("vocab_blocks", 8),
    ))
    return {"gap": (best - got[..., 0])[valid], "logp": (got[..., 0] - lse)[valid],
            "top": top, "best": best, "valid": valid,
            "also_logit": None if also is None else got[..., 1]}


def robust_checks(diff: np.ndarray, check: dict, limits: dict) -> list[Check]:
    """Two numbers of the served tokens' log-probability differences that
    a few far-off tokens cannot move, beside ``serve.compare``'s two
    largest: their median, and the share of tokens more than
    ``check["off_by"]`` off.

    Why this cell needs them: where a token's 8th and 9th router scores
    are close, bfloat16 hidden states choose another expert than the
    float32 reference's, and if one of the two is held here its whole
    contribution appears or vanishes, as it would had the pair been
    dropped. Some hundredth of the tokens a layer are so (PERF.md §6, PR
    31); they own the largest gap of any run, so the limits on the largest
    must leave them room, and a fault that mistreats the routed part of
    *many* tokens by less than one flip does (a scaling factor, one
    expert's tokens) shows in these two and not in the largest."""
    return [
        Check("logprob_diff_p50", float(np.median(diff)), limits["logprob_diff_p50"]),
        Check("logprob_off_pct", float(100.0 * np.mean(diff > check["off_by"])),
              limits["logprob_off_pct"]),
    ]


def served_diff(picked: list[dict], ref: dict) -> np.ndarray:
    served = np.concatenate([np.asarray(r["logprobs"], np.float64) for r in picked])
    return np.abs(served - ref["logp"])


def diff_notes(diff: np.ndarray) -> dict:
    """For the result line's notes: where the served tokens'
    log-probability differences lie, so that the limits can be read
    against the whole of them and not their largest alone."""
    return {
        "quantiles": {f"p{q}": float(np.percentile(diff, q)) for q in (50, 90, 99, 99.9)},
        "off_by_more_than_pct": {str(t): float(100.0 * np.mean(diff > t))
                                 for t in (0.05, 0.1, 0.15, 0.2, 0.4)},
    }


def routed_notes(registry: dict) -> dict | None:
    """For the result line's notes: what a decode step of the window
    routed, from the engine's counters (the roofline readers' inputs)."""
    def delta(name):
        m = registry.get(name)
        return m["series"][""]["delta"] if m and "" in m["series"] else None

    steps = delta("engine_decode_steps_total")
    if not steps or delta("engine_moe_assignments_total") is None:
        return None
    return {k: delta(f"engine_moe_{k}_total") / steps
            for k in ("assignments", "local_assignments", "experts_reached")}


def window_notes(records: list[dict], t0: float, t1: float, slots: int) -> dict | None:
    """For the result line's notes: when the tokens came. A run that reads
    low says here whether the slots filled late, the chip stood still (the
    longest time in which no client received a token) or the whole window
    ran slow (tokens received in each 2 s of it)."""
    firsts = sorted(r["times"][0] - t0 for r in records if r["times"])
    if len(firsts) < slots:
        return None
    times = np.sort(np.concatenate([r["times"] for r in records if r["times"]])) - t0
    edges = np.concatenate([[0.0], times[times <= t1 - t0], [t1 - t0]])
    gaps = np.diff(edges)
    return {
        "slots_full_at_s": float(firsts[slots - 1]),
        "longest_silence_ms": float(1e3 * gaps.max()),
        "longest_silence_at_s": float(edges[gaps.argmax()]),
        "tokens_per_2s": np.histogram(edges[1:-1], bins=np.arange(0.0, t1 - t0 + 2.0, 2.0))[0].tolist(),
    }


def run(ctx) -> dict:
    prog = Program(ctx)
    ctx.spans.reset()
    out = drive(ctx, prog)
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
        counts_pangu_moe.serve_flops(
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
    """For ``perfbench/tools/limits.py``: a short window at the cell's own
    load, then the served tokens' readings against the reference and,
    where asked, the control's: at each position of the same prompts and
    tokens, the gap of the token that the float8 reference puts first, and
    how far its log-probability of the served token lies from the
    reference's. The control goes first: the reference then reads the
    logit of the control's best token beside the served one's."""
    prog = Program(ctx)
    out = drive(ctx, prog)
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
