"""Driver kind ``train``: the packed train step, in this process.

seeded documents -> ``data.packing.pack_batches`` -> ``DevicePrefetcher``
-> the callable ``build_train_step`` returns, with ``llama_loss_fn`` over
``segment_ids``. Set-up builds ONE step and state, drives them through
their first three steps (the proof steps, on rows that all differ, through
the window's own feed and call), and hands the same objects to the window.
Once the window has closed and the state is freed, the plain reference
follows the same three steps from the same seed.
"""

from __future__ import annotations

import collections
import statistics
import time

import numpy as np

from perfbench import counts, reference, traffic, weights
from perfbench.drivers import _llama
from perfbench.harness import Check, memory_peak_bytes

PROOF_STEPS = 3
IN_FLIGHT = 4  # steps dispatched ahead of the one waited for: rides out a host stall of 3 steps


def _runs(seg_row: np.ndarray) -> list[int]:
    """Lengths of the documents (runs of one non-zero id) of a row."""
    cuts = np.flatnonzero(np.diff(seg_row)) + 1
    return [len(r) for r in np.split(seg_row, cuts) if r[0] != 0]


def batch_work(cfg: dict, seg: np.ndarray) -> tuple[int, int]:
    """Real (non-padding) input positions of a packed batch and the
    (query, key) pairs attention needs inside its documents."""
    seg = seg[:, :-1]
    pairs = sum(
        counts.attended_pairs(_runs(row), cfg.get("sliding_window")) for row in seg
    )
    return int((seg != 0).sum()), pairs


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, med)
        if not gap <= worst:  # NaN wins
            worst, at = gap, k
    return worst, at


def compare(prog: dict, ref: dict, limits: dict) -> tuple[list[Check], dict]:
    """The numbers compared, each beside its limit. Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone under Adam and are left out of the change."""
    # a step's loss is compared where the cell's file gives it a limit
    checks = [
        Check(f"loss{n + 1}_gap", abs(p - r) / abs(r), limits[f"loss{n + 1}_gap"])
        for n, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))
        if f"loss{n + 1}_gap" in limits
    ]
    g, g_at = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    dead = {k for k, v in ref["grad_norms"].items() if v < 1e-3 * med}
    d, d_at = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"], dead)
    checks += [
        Check("grad_norm_gap", g, limits["grad_norm_gap"]),
        Check("delta_norm_gap", d, limits["delta_norm_gap"]),
    ]
    return checks, {"grad_worst_leaf": g_at, "delta_worst_leaf": d_at, "left_out": sorted(dead)}


def make_model(config: dict):
    """The program's ``Llama`` at the configuration's sizes and remat."""
    from tensorflowonspark_tpu.models.llama import Llama

    remat = config["run"]["remat"]
    return Llama(_llama.llama_config(
        config, attention_impl="auto", remat=remat != "none",
        remat_policy="full" if remat == "none" else remat,
    ))


def make_tx(run: dict):
    """The program's AdamW as the configuration states it."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.compute import optim

    opt = run["optimizer"]
    return optim.adamw(
        opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], moment_dtype=jnp.dtype(run["adam_moment_dtype"]),
    )


class Program:
    """The step, its state and its feed: built once, used by the proof
    steps and by the window."""

    def __init__(self, ctx, fault=None):
        import jax
        import jax.numpy as jnp

        from tensorflowonspark_tpu.compute import TrainState, build_train_step, shard_state
        from tensorflowonspark_tpu.compute.mesh import make_mesh
        from tensorflowonspark_tpu.data.packing import pack_batches
        from tensorflowonspark_tpu.feed import DevicePrefetcher
        from tensorflowonspark_tpu.models.llama import llama_loss_fn, llama_param_shardings

        cfg = self.cfg = _llama.model_keys(ctx.config)
        run = self.run = ctx.config["run"]
        self.opt = run["optimizer"]
        self.key = weights.seed_key(ctx.seed)
        self.rows, self.seq = run["rows_per_step"], run["max_seq_len"]
        self.moment_dtype = jnp.dtype(run["adam_moment_dtype"])
        model, tx = make_model(ctx.config), make_tx(run)
        mesh = make_mesh({"fsdp": -1})
        params = _llama.device_params(cfg, self.key, jnp.dtype(run["param_dtype"]))
        psh = llama_param_shardings(params, mesh)
        params = jax.tree.map(jax.device_put, params, psh)
        self.state = shard_state(TrainState.create(params, tx), mesh, psh)
        token_loss = llama_loss_fn(model)
        self.step = build_train_step(
            lambda p, bt: token_loss(p, bt["tokens"], bt["segment_ids"]),
            tx, mesh, param_shardings=psh,
        )
        if fault is not None:
            self.step = fault(self.step)

        self.first: list[dict] = []  # host copies of the proof steps' batches
        self.work: collections.deque = collections.deque()
        docs = traffic.document_batches(ctx.traffic, ctx.seed, self.rows, cfg["vocab_size"])

        def host_batches():
            for d, _ in docs:
                got = list(pack_batches(d, self.rows, self.seq, drop_remainder=False))
                batch = {k: np.concatenate([g[k] for g in got]) for k in got[0]}
                if batch["tokens"].shape[0] != self.rows:
                    raise RuntimeError("a step's documents did not pack into its rows")
                if len(self.first) < PROOF_STEPS:
                    self.first.append({k: v.copy() for k, v in batch.items()})
                self.work.append(batch_work(cfg, batch["segment_ids"]))
                yield batch

        self.feed = DevicePrefetcher(host_batches(), mesh, depth=2)

        b1 = self.opt["b1"]
        specs = weights.leaf_specs(cfg)

        @jax.jit
        def grad_norms(mu):
            # after one step mu = (1 - b1) g: the first gradient as the
            # optimizer got it
            return jax.tree.map(
                lambda m: jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32)))) / (1 - b1), mu
            )

        @jax.jit
        def delta_norms(p, key):
            flat = _llama.flatten(p)
            return {
                "/".join(path): jnp.sqrt(jnp.sum(jnp.square(
                    flat["/".join(path)].astype(jnp.float32)
                    - weights.make_leaf(key, n, shape, kind, jnp.float32)
                )))
                for n, (path, shape, kind) in enumerate(specs)
            }

        self._grad_norms, self._delta_norms = grad_norms, delta_norms

    def next_step(self, spans):
        """One step through the feed and the call: the proof steps' and the
        window's alike. Returns the loss (on the device) and the batch's
        (real tokens, attended pairs)."""
        with spans.span("next_batch"):
            batch = next(self.feed)
        work = self.work.popleft()
        with spans.span("step_dispatch"):
            self.state, loss = self.step(self.state, batch)
        return loss, work

    def proof_steps(self, spans) -> dict:
        import jax

        losses, gnorm = [], None
        for n in range(PROOF_STEPS):
            loss, _ = self.next_step(spans)
            losses.append(loss)
            if n == 0:
                adam = next(s for s in self.state.opt_state if hasattr(s, "mu"))
                gnorm = self._grad_norms(adam.mu)
        dnorm = self._delta_norms(self.state.params, self.key)
        out = jax.device_get({"losses": losses, "grad_norms": gnorm, "delta_norms": dnorm})
        return {
            "losses": [float(x) for x in out["losses"]],
            "grad_norms": {k: float(v) for k, v in _llama.flatten(out["grad_norms"]).items()},
            "delta_norms": {k: float(v) for k, v in out["delta_norms"].items()},
        }

    def release(self) -> None:
        self.feed.close()
        self.state = self.step = None

    def reference_readings(self, mm="highest", keep=None) -> dict:
        import jax.numpy as jnp

        leaf = _llama.reference_leaves(self.cfg, self.key, jnp.dtype(self.run["param_dtype"]))
        return reference.train_readings(
            self.cfg, self.opt, lambda: {k: leaf(k) for k in leaf.names},
            self.first[:PROOF_STEPS], mm=mm, blocks=self.run.get("reference_blocks", 8),
            moment_dtype=self.moment_dtype, keep=keep,
        )


def run(ctx) -> dict:
    import jax

    prog = Program(ctx, ctx.fault)
    spans = ctx.spans
    proof = prog.proof_steps(spans)
    spans.reset()

    steps = tokens = flops = 0
    in_flight: collections.deque = collections.deque()

    def one():
        nonlocal steps, tokens, flops
        loss, (real, pairs) = prog.next_step(spans)
        in_flight.append(loss)
        steps += 1
        tokens += real
        flops += counts.train_step_flops(prog.cfg, real, pairs)
        if len(in_flight) > IN_FLIGHT:
            with spans.span("step_wait"):
                jax.block_until_ready(in_flight.popleft())
        return real, pairs

    traced = None
    t0_wall, t0 = time.time(), time.perf_counter()
    reserve = ctx.workload.get("trace", {}).get("reserve_s", 0) if ctx.trace else 0
    while time.perf_counter() < t0 + ctx.seconds - reserve:
        one()
    if ctx.trace:
        jax.block_until_ready(prog.state)
        in_flight.clear()
        ctx.tracer.start()
        n = ctx.workload["trace"]["steps"]
        work = [one() for _ in range(n)]
        jax.block_until_ready(prog.state)
        ctx.tracer.stop()
        traced = {
            "steps": n, "tokens": sum(w[0] for w in work), "pairs": sum(w[1] for w in work),
            "positions": n * prog.rows * prog.seq,
        }
    jax.block_until_ready(prog.state)
    window_s = time.perf_counter() - t0
    last_loss = float(in_flight[-1]) if in_flight else float("nan")

    peak_bytes = memory_peak_bytes()
    feed_stats = prog.feed.stats()
    prog.release()
    in_flight.clear()

    t_check = time.perf_counter()
    ref = prog.reference_readings()
    checks, notes = compare(proof, ref, ctx.workload["check"]["limits"])
    check_s = time.perf_counter() - t_check

    summary = ctx.tracer.reduce() if ctx.trace else None
    return {
        "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "setup_s": t0_wall - ctx.t_start,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "checks": checks,
        "memory_peak_bytes": peak_bytes,
        "notes": {**notes, "setup_s": t0_wall - ctx.t_start, "check_s": check_s, "window_s": window_s, "steps": steps,
                  "last_loss": last_loss, "feed": feed_stats,
                  "proof_losses": proof["losses"], "reference_losses": ref["losses"]},
        "record": {
            "cfg": prog.cfg, "spans": spans.durations, "window_s": window_s,
            "flops": flops, "steps": steps, "tokens": tokens,
            "trace": summary, "traced": traced, "peak": ctx.peak,
        },
    }


def limit_readings(ctx, with_control: bool) -> dict:
    """For ``perfbench/tools/limits.py``: the program's readings against
    the reference on this seed and, where asked, the control's (the
    reference in float8 put in the program's place) and the planted
    fault's (half of the batch left out of the reference put in its
    place). A state left unchanged reads 1 by ``delta_norm_gap``'s measure
    and needs no run."""
    loose = dict.fromkeys(
        [f"loss{n + 1}_gap" for n in range(PROOF_STEPS)] + ["grad_norm_gap", "delta_norm_gap"],
        float("inf"),
    )
    prog = Program(ctx)
    proof = prog.proof_steps(ctx.spans)
    prog.release()
    ref = prog.reference_readings()
    out = {"program": {c.name: c.value for c in compare(proof, ref, loose)[0]}}
    if with_control:
        ctl = prog.reference_readings(mm="fp8")
        out["control_fp8"] = {c.name: c.value for c in compare(ctl, ref, loose)[0]}
        keep = [1.0] * (prog.rows - prog.rows // 2) + [0.0] * (prog.rows // 2)
        half = prog.reference_readings(keep=keep)
        out["fault_half_batch"] = {c.name: c.value for c in compare(half, ref, loose)[0]}
    return out
