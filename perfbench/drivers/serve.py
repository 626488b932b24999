"""Driver kind ``serve``: the continuous-batching engine, in this process.

``ContinuousBatcher`` is built on the seed's weights and warmed up; the
window runs ``clients`` threads in a closed loop, each calling
``stream()`` again the moment its last request ends. Everything a client
sees is stamped on the client's side. Once the window has closed, the
engine is stopped and its memory freed; the plain reference then runs one
full forward pass over a sample of the finished requests (the longest in
it), prompt and served tokens together, and every served token's logit is
held against the reference's best at its position.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from perfbench import counts, reference, traffic, weights
from perfbench.drivers import _llama
from perfbench.harness import Check, memory_peak_bytes


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


class Program:
    def __init__(self, ctx):
        import jax.numpy as jnp

        from tensorflowonspark_tpu.models.llama import Llama
        from tensorflowonspark_tpu.serving.engine import ContinuousBatcher

        self.cfg = _llama.model_keys(ctx.config)
        self.run = ctx.config["run"]
        self.key = weights.seed_key(ctx.seed)
        self.dtype = jnp.dtype(self.run["param_dtype"])
        model = Llama(_llama.llama_config(ctx.config))
        params = _llama.device_params(self.cfg, self.key, self.dtype)
        # every option the configuration does not name stays at the
        # constructor's default
        self.engine = ContinuousBatcher(
            model, params, slots=self.run["slots"],
            prompt_widths=tuple(self.run["prompt_widths"]),
        )
        self.engine.warmup()

    def release(self) -> None:
        self.engine.close()
        self.engine = None
        gc.collect()


def drive(ctx, prog: Program) -> dict:
    """The measured window. Returns the requests as the clients saw them
    and the window's bounds on the ``perf_counter`` clock."""
    spec = ctx.traffic
    engine = prog.engine
    source = traffic.requests(spec, ctx.seed, prog.cfg["vocab_size"])
    lock = threading.Lock()
    stop = threading.Event()
    records: list[dict] = []

    def client() -> None:
        while not stop.is_set():
            with lock:
                prompt, n_out = next(source)
            rec = {"prompt": prompt, "n_out": n_out, "times": [], "tokens": [],
                   "logprobs": [], "error": None, "t_submit": time.perf_counter()}
            try:
                for tok, lp in engine.stream(prompt, n_out, eos_id=-1, yield_logprobs=True):
                    rec["times"].append(time.perf_counter())
                    rec["tokens"].append(int(tok))
                    rec["logprobs"].append(float(lp))
            except Exception as e:  # a failed request counts, it does not end the run
                rec["error"] = repr(e)
            rec["t_end"] = time.perf_counter()
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, daemon=True) for _ in range(spec["clients"])]
    engine.metrics.window()  # the registry's deltas start here
    steps0 = engine.steps
    t0_wall, t0 = time.time(), time.perf_counter()
    for t in threads:
        t.start()
    traced = None
    if ctx.trace:
        lead = max(0.0, ctx.seconds - ctx.workload["trace"]["seconds"])
        time.sleep(lead)
        ctx.tracer.start()
        traced = {"t0": time.perf_counter(), "steps0": engine.steps}
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    t1 = time.perf_counter()
    stop.set()
    registry = engine.metrics.window()
    steps = engine.steps - steps0
    if ctx.trace:
        traced.update(t1=time.perf_counter(), steps=engine.steps - traced.pop("steps0"))
        ctx.tracer.stop()
    # an answer that comes late is late, not wrong: wait for each
    for t in threads:
        t.join(timeout=ctx.workload.get("drain_s", 60))
    unfinished = sum(t.is_alive() for t in threads)
    return {"records": records, "t0": t0, "t1": t1, "t0_wall": t0_wall,
            "registry": registry, "steps": steps, "traced": traced,
            "unfinished": unfinished}


def end_to_end(records: list[dict], t0: float, t1: float) -> dict:
    window = t1 - t0
    received = sum(sum(t <= t1 for t in r["times"]) for r in records)
    worst = max([r["times"][0] - r["t_submit"] for r in records if r["times"]] + [window])
    ttft = [r["times"][0] - r["t_submit"] if r["times"] and not r["error"] else worst
            for r in records]
    gaps = [b - a for r in records for a, b in zip(r["times"], r["times"][1:]) if b <= t1]
    return {
        "serve_tokens_per_s": received / window,
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "itl_p95_ms": 1e3 * percentile(gaps, 95),
    }


def live_kv_tokens(records: list[dict], a: float, b: float) -> float:
    """Mean over [a, b] of the summed context lengths of the requests that
    were decoding: each grows from its prompt's length by one a token."""
    total = 0.0
    for r in records:
        if len(r["times"]) < 2:
            continue
        s, e = r["times"][0], r["times"][-1]
        lo, hi = max(a, s), min(b, e)
        if hi <= lo:
            continue
        n = len(r["times"])
        mid = ((lo + hi) / 2 - s) / (e - s)  # linear growth: the mean is at the middle
        total += (len(r["prompt"]) + mid * n) * (hi - lo)
    return total / (b - a)


def sample(records: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` finished requests drawn from the seed, the longest in it."""
    done = [r for r in records if not r["error"] and len(r["tokens"]) == r["n_out"]]
    if not done:
        return []
    done.sort(key=lambda r: r["t_submit"])  # threads finish in any order
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    pick = traffic.rng_for(seed, 3).permutation(len(rest))[: n - 1]
    return [longest] + [rest[i] for i in pick]


def reference_gaps(prog: Program, picked: list[dict], check: dict, mm: str = "highest") -> dict:
    """One full forward pass of the reference over each sampled request.

    Returns, per served token (flattened): how far its logit lies below
    the reference's best there, the reference's log-probability of it,
    and the reference's own best token.
    """
    import jax
    import jax.numpy as jnp

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
    leaf = _llama.reference_leaves(prog.cfg, prog.key, prog.dtype)
    logits = reference.serve_logits(
        prog.cfg, leaf, seqs, at, mm=mm, blocks=check.get("blocks", 4)
    )

    @jax.jit
    def read(lg, t):
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, t[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(lg, axis=-1)
        return best - got, got - lse, jnp.argmax(lg, axis=-1)

    gap, logp, top = jax.device_get(read(logits, jnp.asarray(tok)))
    return {"gap": gap[valid], "logp": logp[valid], "top": top, "valid": valid,
            "logits": logits}


def compare(picked: list[dict], ref: dict, limits: dict) -> list[Check]:
    served_lp = np.concatenate([np.asarray(r["logprobs"], np.float64) for r in picked])
    return [
        Check("logit_gap", float(ref["gap"].max()), limits["logit_gap"]),
        Check("logprob_diff", float(np.abs(served_lp - ref["logp"]).max()),
              limits["logprob_diff"]),
    ]


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
    picked = sample(records, ctx.seed, check["sample"])
    failed = sum(bool(r["error"]) or len(r["tokens"]) != r["n_out"] for r in records)
    failed += out["unfinished"]
    if picked:
        ref = reference_gaps(prog, picked, check)
        checks = compare(picked, ref, check["limits"])
        compared = int(ref["valid"].sum())
    else:
        checks, compared = [Check("logit_gap", float("nan"), check["limits"]["logit_gap"])], 0
    checks.append(Check("failed_requests", float(failed), 0.0))
    check_s = time.perf_counter() - t_check

    window = t1 - t0
    # the work done inside the window: a request's prompt once its first
    # token has come, and the completion tokens received before the close
    flops = sum(
        counts.serve_flops(prog.cfg, len(r["prompt"]), sum(t <= t1 for t in r["times"]))
        for r in records if r["times"] and r["times"][0] <= t1
    )
    traced = out["traced"]
    if traced:
        traced["live_kv_tokens"] = live_kv_tokens(records, traced["t0"], traced["t1"])
    summary = ctx.tracer.reduce() if ctx.trace else None
    client = end_to_end(records, t0, t1)
    return {
        "attempted": len(records) + out["unfinished"],
        "failed": failed,
        "setup_s": out["t0_wall"] - ctx.t_start,
        "end_to_end": client,
        "checks": checks,
        "memory_peak_bytes": peak_bytes,
        "notes": {"setup_s": out["t0_wall"] - ctx.t_start, "check_s": check_s, "window_s": window, "requests": len(records),
                  "tokens_compared": compared, "decode_steps": out["steps"],
                  "tokens": sum(len(r["tokens"]) for r in records)},
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
    reference's."""
    import jax.numpy as jnp

    prog = Program(ctx)
    out = drive(ctx, prog)
    prog.release()
    check = ctx.workload["check"]
    picked = sample(out["records"], ctx.seed, check["sample"])
    loose = {k: float("inf") for k in check["limits"]}
    ref = reference_gaps(prog, picked, check)
    res = {"program": {c.name: c.value for c in compare(picked, ref, loose)},
           "requests": len(out["records"]), "tokens_compared": int(ref["valid"].sum())}
    if with_control:
        ctl = reference_gaps(prog, picked, check, mm="fp8")
        lg = np.asarray(ref["logits"], np.float32)
        best = lg.max(-1)
        at_ctl = np.take_along_axis(lg, np.asarray(ctl["top"])[..., None], -1)[..., 0]
        res["control_fp8"] = {
            "logit_gap": float((best - at_ctl)[ref["valid"]].max()),
            "logprob_diff": float(np.abs(ctl["logp"] - ref["logp"]).max()),
        }
        del lg
    return res
