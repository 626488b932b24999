"""Decode CLI over a Llama orbax checkpoint — no user Python needed.

The decode-side sibling of ``tools/run_model.py`` (which replays AOT
forward artifacts, the Scala-API parity path — SURVEY.md §2.2): load a
checkpointed Llama, read JSONL prompt rows, batch them with right-padding
+ per-row true lengths (``generate(prompt_lengths=...)``), sample with
greedy/top-k/top-p and optional EOS early stop, write JSONL completions
trimmed at each row's first EOS.

Prompts are token ids (``{"tokens": [1, 5, 9]}`` per line) — tokenizers
are corpus-specific and out of framework scope; pipe through one on
either side.

Usage::

    python -m tensorflowonspark_tpu.tools.generate_text \
        --checkpoint ckpt_dir/ --model tiny --prompts prompts.jsonl \
        --output out.jsonl [--max-new-tokens 64] [--eos-id N] \
        [--temperature 0.8 --top-k 40 --top-p 0.95] [--batch-size 8] \
        [--config-overrides '{"vocab_size": 1024}']

``--score`` switches from decoding to scoring: each row's per-token
next-token logprobs + summed total (the eval/perplexity surface; the
same scorer backs serve_model's /score endpoint). Composes with
``--mesh`` for models that need TP to fit.
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="generate_text",
        description="KV-cache decode over a Llama orbax checkpoint",
    )
    p.add_argument(
        "--checkpoint",
        required=True,
        help="orbax dir: a CheckpointManager model dir (latest step is "
        "used; TrainState or bare param trees both work) or a "
        "save_checkpoint path",
    )
    p.add_argument("--model", choices=("tiny", "1b", "7b"), default="tiny")
    p.add_argument(
        "--config-overrides",
        default=None,
        help='JSON dict of LlamaConfig field overrides, e.g. '
        '\'{"vocab_size": 1024, "max_seq_len": 512}\'',
    )
    p.add_argument("--prompts", required=True, help="JSONL: {'tokens': [...]}")
    p.add_argument("--output", required=True, help="output JSONL path ('-' = stdout)")
    p.add_argument(
        "--score",
        action="store_true",
        help="score instead of decode: each input row's per-token "
        "next-token logprobs (+ summed total) as JSONL — the batch "
        "eval/perplexity surface (decode flags are ignored)",
    )
    p.add_argument(
        "--lora-scale",
        type=float,
        default=None,
        help="LoRA checkpoints: alpha/rank scale to re-apply after "
        "restore (the static scale field is not stored; default 1.0 "
        "matches add_lora's default alpha=rank)",
    )
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--min-p", type=float, default=None)
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--mesh",
        default=None,
        help="decode sharded over a device mesh, e.g. 'data=2,model=4' "
        "(TP weights on 'model', batch + KV caches on 'data'); "
        "--batch-size must be divisible by the 'data' extent",
    )
    p.add_argument(
        "--draft-checkpoint",
        default=None,
        help="speculative decoding: orbax checkpoint of a (smaller) "
        "draft model that proposes --spec-k tokens per target "
        "verification; greedy output is token-identical to the plain "
        "greedy decode, temperature>0 preserves the target's sampling "
        "distribution via the rejection rule. No --top-k/--top-p; "
        "composes with --mesh (TP/DP target, replicated draft)",
    )
    p.add_argument(
        "--draft-model", choices=("tiny", "1b", "7b"), default="tiny"
    )
    p.add_argument(
        "--draft-config-overrides",
        default=None,
        help="JSON LlamaConfig overrides for the draft model",
    )
    p.add_argument("--spec-k", type=int, default=4)
    return p


def _load_config(args):
    import dataclasses

    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.llama import LlamaConfig

    base = {
        "tiny": LlamaConfig.tiny,
        "1b": LlamaConfig.llama_1b,
        "7b": LlamaConfig.llama2_7b,
    }[args.model]()
    if args.config_overrides:
        overrides = json.loads(args.config_overrides)
        if "dtype" in overrides:  # JSON carries it as a name string
            overrides["dtype"] = getattr(jnp, overrides["dtype"])
        if isinstance(overrides.get("rope_scaling"), dict):
            # JSON carries the RopeScaling dataclass as a dict
            # (import_hf_llama's --config-out emits it this way)
            from tensorflowonspark_tpu.models.llama import RopeScaling

            overrides["rope_scaling"] = RopeScaling(
                **overrides["rope_scaling"]
            )
        base = dataclasses.replace(base, **overrides)
    return base


def _load_params(checkpoint: str, cfg, lora_scale: "float | None" = None):
    """Restore params from either a CheckpointManager dir (latest step)
    or a bare save_checkpoint path; accept TrainState trees, {'state':
    ...} wrappers, or bare param trees. LoRA nodes (single adapters or
    multi-adapter banks) restored as plain dicts are rewrapped so the
    adapter paths route again (``ops/lora.py:rewrap_lora``);
    ``lora_scale`` re-supplies the non-stored static scale — None means
    the 1.0 default, resolved HERE so no caller can reintroduce the
    `or 1.0` falsy-zero bug (an explicit 0.0 disables the adapters)."""
    lora_scale = 1.0 if lora_scale is None else float(lora_scale)
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.compute.checkpoint import (
        CheckpointManager,
        restore_checkpoint,
    )

    with CheckpointManager(checkpoint) as mgr:
        step = mgr.latest_step()
        tree = mgr.restore(step) if step is not None else None
    if tree is None:
        tree = restore_checkpoint(checkpoint)
    for key in ("state", "params"):
        if isinstance(tree, dict) and key in tree:
            tree = tree[key]
    if isinstance(tree, dict) and "params" in tree:
        tree = tree["params"]
    if not (isinstance(tree, dict) and "embed" in tree):
        raise ValueError(
            f"checkpoint {checkpoint} does not contain a Llama param tree "
            f"(top-level keys: {sorted(tree) if isinstance(tree, dict) else type(tree)})"
        )
    from tensorflowonspark_tpu.ops.lora import rewrap_lora

    tree = rewrap_lora(tree, lora_scale)
    # decode in the model's compute dtype
    return jax.tree.map(
        lambda x: x.astype(cfg.dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


class PromptError(ValueError):
    """A problem with the CALLER's prompts (empty / longer than the
    decode width) — servers map this to a 4xx, unlike server-side
    configuration errors which stay plain ValueError/500."""


def decode_batches(
    model,
    params,
    prompts: list[list[int]],
    *,
    batch_size: int,
    width: int,
    max_new_tokens: int,
    rng,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    min_p: float | None = None,
    eos_id: int | None = None,
    uniform: bool = False,
    pad_to_batch: bool = False,
    mesh=None,
    draft=None,
    spec_k: int = 4,
):
    """Decode ``prompts`` at ONE static (batch_size, width) shape so the
    jitted prefill + decode loop compiles exactly once: short chunks pad
    rows by repeating the last prompt (results trimmed), short prompts
    right-pad to ``width`` (``generate``'s prompt_lengths path;
    ``uniform=True`` skips it when every prompt is exactly ``width``).
    Returns ``(completions, rng)`` with each completion trimmed at its
    first ``eos_id``. Shared by the CLI and serve_model's /generate.

    ``pad_to_batch``: always decode at exactly ``batch_size`` rows even
    when fewer prompts arrive (rows padded by repeating the last
    prompt). Servers MUST set this: the ``min()`` shortcut below would
    otherwise compile a fresh (n, width) program per distinct request
    size — seconds-to-minutes on the request thread — and thrash the
    compile cache, violating the one-static-shape bucketing policy.
    The one-shot CLI keeps the shortcut (smaller batch = less wasted
    compute, and its single compile is paid exactly once either way).

    ``mesh``: decode sharded over a device mesh (TP weights on 'model',
    batch + KV caches on 'data' — ``models.llama.generate``'s mesh
    path). The effective batch size must be divisible by the 'data'
    extent (set ``pad_to_batch`` so it stays the full ``batch_size``).

    ``draft``: a ``(draft_model, draft_params)`` pair switches decoding
    to speculative (``models.speculative``): the draft proposes
    ``spec_k`` tokens per target verification. At ``temperature == 0``
    output is token-identical to the plain greedy decode; at
    ``temperature > 0`` the rejection rule preserves the target's
    sampling distribution exactly. top_k/top_p do not combine with a
    draft. Composes with ``mesh`` (TP/DP target, replicated draft).
    """
    import jax
    import numpy as np

    from tensorflowonspark_tpu.models.llama import generate

    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if draft is not None and (
        top_k is not None or top_p is not None or min_p is not None
    ):
        raise ValueError(
            "speculative decoding supports greedy (temperature 0) and "
            "plain-temperature sampling, not top_k/top_p/min_p "
            "truncation (truncation would change the distribution the "
            "rejection rule preserves)"
        )
    if not prompts:
        raise PromptError("no prompts given")
    bad = [i for i, p in enumerate(prompts) if not p or len(p) > width]
    if bad:
        raise PromptError(
            f"prompt rows {bad} are empty or exceed the decode width "
            f"({width})"
        )
    bsz = batch_size if pad_to_batch else min(batch_size, len(prompts))
    out: list[list[int]] = []
    for lo in range(0, len(prompts), bsz):
        chunk = prompts[lo : lo + bsz]
        n_real = len(chunk)
        chunk = chunk + [chunk[-1]] * (bsz - n_real)
        padded = np.zeros((bsz, width), np.int32)
        lengths = np.zeros(bsz, np.int32)
        for i, p in enumerate(chunk):
            padded[i, : len(p)] = p
            lengths[i] = len(p)
        rng, key = jax.random.split(rng)
        if draft is not None:
            from tensorflowonspark_tpu.models.speculative import (
                speculative_generate,
            )

            draft_model, draft_params = draft
            toks = np.asarray(
                speculative_generate(
                    model,
                    params,
                    draft_model,
                    draft_params,
                    jax.numpy.asarray(padded),
                    max_new_tokens=max_new_tokens,
                    k=spec_k,
                    eos_id=eos_id,
                    prompt_lengths=None if uniform else lengths,
                    mesh=mesh,
                    temperature=temperature,
                    rng=key,
                )
            )
        else:
            toks = np.asarray(
                generate(
                    model,
                    params,
                    jax.numpy.asarray(padded),
                    max_new_tokens=max_new_tokens,
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    min_p=min_p,
                    rng=key,
                    eos_id=eos_id,
                    prompt_lengths=None if uniform else lengths,
                    mesh=mesh,
                )
            )
        for row in toks[:n_real]:
            row = row.tolist()
            if eos_id is not None and eos_id in row:
                row = row[: row.index(eos_id) + 1]
            out.append(row)
    return out, rng


def build_score_fn(model, params, width: int, bsz: int):
    """Build ``sequences -> per-token logprobs`` over a Llama — the
    eval-harness surface (perplexity / sequence scoring), shared by the
    CLI's ``--score`` and serve_model's ``/score`` so the two cannot
    diverge. One static (bsz, width) compile, rows right-padded; a pure
    forward (no KV cache). If ``params`` are mesh-sharded (device_put
    under ``llama_param_shardings``), the jitted forward runs SPMD
    against those placements."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def score(tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = tokens[:, 1:]
        return jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]

    def score_rows(rows: list[list[int]]) -> list[list[float]]:
        if not rows:
            raise PromptError("'sequences' must be a non-empty list")
        if len(rows) > bsz:
            raise PromptError(
                f"at most {bsz} sequences per request (the compiled "
                f"batch shape)"
            )
        vocab = model.cfg.vocab_size
        for r in rows:
            if len(r) < 2:
                raise PromptError(
                    "each sequence needs >= 2 tokens (scores are "
                    "next-token logprobs)"
                )
            if len(r) > width:
                raise PromptError(
                    f"sequence length {len(r)} exceeds the score "
                    f"width {width}"
                )
            bad = [t for t in r if not 0 <= t < vocab]
            if bad:
                # XLA clamps out-of-range gathers, which would return
                # plausible-looking but meaningless logprobs
                raise PromptError(
                    f"token ids {bad[:5]} outside the vocabulary "
                    f"[0, {vocab})"
                )
        arr = np.zeros((bsz, width), np.int32)
        for i, r in enumerate(rows):
            arr[i, : len(r)] = r
        lp = np.asarray(score(jnp.asarray(arr)))
        return [lp[i, : len(r) - 1].tolist() for i, r in enumerate(rows)]

    return score_rows


def _score_main(args, model, params, cfg, seqs) -> int:
    """--score: emit per-token next-token logprobs (and the summed
    sequence logprob) for each input row instead of decoding — the
    batch eval surface, the CLI twin of serve_model's /score."""
    width = min(max(len(s) for s in seqs), cfg.max_seq_len)
    score_rows = build_score_fn(
        model, params, width=width, bsz=args.batch_size
    )
    out = open(args.output, "w") if args.output != "-" else sys.stdout
    try:
        for i in range(0, len(seqs), args.batch_size):
            for row in score_rows(seqs[i : i + args.batch_size]):
                out.write(
                    json.dumps(
                        {"logprobs": row, "total": float(sum(row))}
                    )
                    + "\n"
                )
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    import jax

    from tensorflowonspark_tpu.models.llama import Llama
    from tensorflowonspark_tpu.utils.util import enable_compile_cache

    enable_compile_cache()

    if args.batch_size < 1:
        raise SystemExit("--batch-size must be >= 1")
    cfg = _load_config(args)
    model = Llama(cfg)
    params = _load_params(
        args.checkpoint, cfg,
        lora_scale=getattr(args, "lora_scale", None),
    )

    with open(args.prompts) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    prompts = [list(map(int, r["tokens"])) for r in rows]
    if not prompts:
        raise ValueError(f"no prompts in {args.prompts}")
    if args.score and args.draft_checkpoint:
        raise SystemExit(
            "--score is a plain forward; --draft-checkpoint "
            "(speculative decoding) does not apply"
        )
    width = max((len(p) for p in prompts), default=1)
    if not args.score and width + args.max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"longest prompt ({width}) + max_new_tokens "
            f"({args.max_new_tokens}) exceeds max_seq_len "
            f"({cfg.max_seq_len})"
        )

    mesh = None
    if args.mesh:
        from tensorflowonspark_tpu.compute.mesh import (
            make_mesh,
            parse_axis_spec,
        )
        from tensorflowonspark_tpu.models.llama import llama_param_shardings

        mesh = make_mesh(parse_axis_spec(args.mesh))
        # place the weights in their TP layout once, not per chunk
        params = jax.device_put(params, llama_param_shardings(params, mesh))

    if args.score:
        # after the mesh placement above: sharded params make the
        # scoring forward SPMD (the 7B-doesn't-fit-one-chip case)
        return _score_main(args, model, params, cfg, prompts)

    draft = None
    if args.draft_checkpoint:
        dcfg = _load_config(
            argparse.Namespace(
                model=args.draft_model,
                config_overrides=args.draft_config_overrides,
            )
        )
        draft_params = _load_params(args.draft_checkpoint, dcfg)
        if mesh is not None:
            from tensorflowonspark_tpu.compute import layout

            # replicate the draft once, not per chunk
            draft_params = jax.device_put(
                draft_params, layout.replicated(mesh)
            )
        draft = (Llama(dcfg), draft_params)

    completions, _ = decode_batches(
        model,
        params,
        prompts,
        batch_size=args.batch_size,
        width=width,
        max_new_tokens=args.max_new_tokens,
        rng=jax.random.PRNGKey(args.seed),
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        min_p=args.min_p,
        eos_id=args.eos_id,
        # uniform corpora skip the padded path's scatter writes
        uniform=all(len(p) == width for p in prompts),
        # sharded decode needs the batch divisible by the 'data' extent;
        # padding to the full batch keeps one shape that is
        pad_to_batch=mesh is not None,
        mesh=mesh,
        draft=draft,
        spec_k=args.spec_k,
    )
    out = open(args.output, "w") if args.output != "-" else sys.stdout
    try:
        for row in completions:
            out.write(json.dumps({"tokens": row}) + "\n")
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
