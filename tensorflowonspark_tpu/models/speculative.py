"""Speculative decoding — draft-and-verify greedy generation.

A small DRAFT model proposes ``k`` tokens autoregressively; the TARGET
model scores all ``k+1`` positions in ONE forward and keeps the longest
prefix it agrees with plus its own correction token. Greedy speculative
decoding emits EXACTLY the target model's greedy sequence (the
acceptance rule only ever keeps tokens the target itself would have
picked) — tested token-identically against :func:`...llama.generate`.

Why it wins on TPU: single-token decode is HBM-bandwidth-bound — every
step reads every weight once. Verification reads the target weights
once per ``a+1`` emitted tokens (``a`` = accepted drafts), and the
(B, k+1) verify forward is a better MXU shape than k+1 single-token
steps. Net speedup ≈ (accepted+1) / (k·cost_draft/cost_target + 1).

Cache discipline (no rollback needed): both models run their KV caches
through the per-row scatter path (``padded=True``), where a token's
slot IS its position and writes land BEFORE attention in each forward
(``llama.py:_cached_attention``). Rejected drafts leave stale cache
entries only at positions ≥ the next iteration's write window, and
every such slot is overwritten by that window before any query's
position reaches it — so acceptance just moves the position counters.

Reference parity note: the reference had no decode path at all
(SURVEY.md §2.2 — its serving story was per-executor SavedModel
replay); this module is capability beyond the reference, built on the
same KV-cache machinery as :func:`...llama.generate`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tensorflowonspark_tpu.compute import layout
from tensorflowonspark_tpu.parallel.context import use_mesh

__all__ = ["speculative_generate", "speculative_accept"]


def speculative_accept(key, t_probs, d_probs, drafts):
    """One speculative-SAMPLING verification (Leviathan/Chen rejection
    rule): accept draft ``x_j`` with probability ``min(1, p_j(x_j) /
    q_j(x_j))``; at the first rejection sample from the residual
    ``normalize(max(p_j - q_j, 0))``; if all ``k`` drafts survive,
    sample the bonus token from ``p_k``. The emitted tokens are then
    distributed EXACTLY as if each had been sampled from the target
    distribution ``p`` — for ANY draft distribution ``q`` (the draft
    only moves the acceptance rate). Monte-Carlo-verified in
    ``tests/test_speculative.py``.

    Args: ``t_probs (B, k+1, V)`` target probabilities, ``d_probs
    (B, k, V)`` draft probabilities, ``drafts (B, k)`` the draft's
    samples. Returns ``(emit, accepted)``: ``emit (B, k+1)`` holds the
    accepted drafts in ``[0, accepted)`` and the residual/bonus sample
    at index ``accepted`` (later entries are padding), ``accepted
    (B,)`` in ``[0, k]``.
    """
    b, kp1, v = t_probs.shape
    k = kp1 - 1
    key_u, key_r = jax.random.split(key)
    u = jax.random.uniform(key_u, (b, k), jnp.float32)
    p_x = jnp.take_along_axis(t_probs[:, :k], drafts[..., None], -1)[..., 0]
    q_x = jnp.take_along_axis(d_probs, drafts[..., None], -1)[..., 0]
    # u < p/q  <=>  u*q < p (no divide; q=0 with p>0 accepts, both 0
    # rejects — the residual then resamples safely)
    accept = u * q_x < p_x
    accepted = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
    )
    # q padded with a zero row at j=k: the all-accepted bonus case then
    # falls out of the same residual formula (residual = p_k - 0 = p_k)
    q_pad = jnp.concatenate(
        [d_probs, jnp.zeros((b, 1, v), d_probs.dtype)], axis=1
    )
    p_at = jnp.take_along_axis(
        t_probs, accepted[:, None, None], axis=1
    )[:, 0]
    q_at = jnp.take_along_axis(q_pad, accepted[:, None, None], axis=1)[:, 0]
    residual = jnp.clip(
        p_at.astype(jnp.float32) - q_at.astype(jnp.float32), 0.0, None
    )
    # p == q exactly -> empty residual, but rejection then has
    # probability zero anyway; guard the log with p itself
    degenerate = jnp.sum(residual, axis=-1, keepdims=True) <= 0
    weights = jnp.where(degenerate, p_at.astype(jnp.float32), residual)
    corr = jax.random.categorical(key_r, jnp.log(weights + 1e-38)).astype(
        jnp.int32
    )
    pad = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
    j_idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    emit = jnp.where(j_idx == accepted[:, None], corr[:, None], pad)
    return emit, accepted


def speculative_generate(
    model,
    params,
    draft_model,
    draft_params,
    prompt: jax.Array,
    max_new_tokens: int,
    k: int = 4,
    eos_id: int | None = None,
    prompt_lengths: jax.Array | None = None,
    mesh: Mesh | None = None,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Speculative decode: (B, S) int32 -> (B, max_new_tokens).

    ``temperature == 0`` (default): token-for-token identical to
    ``generate(model, params, prompt, max_new_tokens, eos_id=...)``
    (greedy) for ANY draft model — the draft only changes speed, never
    output. ``temperature > 0``: speculative SAMPLING — the draft
    samples ``k`` proposals at the same temperature and the target
    accepts/resamples via the rejection rule
    (:func:`speculative_accept`), so emitted tokens are distributed
    exactly as target-only sampling; ``rng`` seeds it. top-k/top-p
    truncation is not offered here (it would change the distribution
    the acceptance rule preserves).

    ``k`` is the number of draft proposals per verification; both
    models need ``max_seq_len >= S + max_new_tokens + k`` (the verify
    window may scratch up to ``k`` slots past the emitted text). Rows
    finish independently on ``eos_id`` and the loop exits early once
    every row is done. Mixed-length prompts: RIGHT-pad and pass
    ``prompt_lengths`` (B,), exactly like ``generate``.

    ``mesh``: the TARGET runs TP/DP-sharded exactly like ``generate``'s
    mesh path (weights on 'model', batch + caches on 'data'); the DRAFT
    is fully replicated with only its batch/cache sharded on 'data' —
    a draft is small by construction, and replication frees it from the
    target's head-divisibility constraints.
    """
    b, s = prompt.shape
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    rng = jax.random.PRNGKey(0) if rng is None else rng
    for name, cfg in (("model", model.cfg), ("draft_model", draft_model.cfg)):
        if s + max_new_tokens + k > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + k "
                f"({k}) exceeds {name}.cfg.max_seq_len ({cfg.max_seq_len})"
            )
    if mesh is not None:
        from tensorflowonspark_tpu.models.llama import llama_param_shardings

        dp = mesh.shape["data"]
        tp = mesh.shape["model"]
        if b % dp:
            raise ValueError(
                f"batch {b} not divisible by the mesh 'data' extent {dp}"
            )
        if model.cfg.num_kv_heads % tp or model.cfg.num_heads % tp:
            raise ValueError(
                f"target heads ({model.cfg.num_heads}/"
                f"{model.cfg.num_kv_heads} kv) not divisible by the mesh "
                f"'model' extent {tp}"
            )
        params = jax.device_put(params, llama_param_shardings(params, mesh))
        draft_params = jax.device_put(
            draft_params, layout.replicated(mesh)
        )
        prompt = jax.device_put(
            prompt, layout.activation_sharding(mesh, "prompt")
        )
    run = _build_speculative(
        model,
        draft_model,
        b,
        s,
        max_new_tokens,
        int(k),
        None if eos_id is None else int(eos_id),
        mixed=prompt_lengths is not None,
        mesh=mesh,
        temperature=float(temperature),
    )
    if mesh is not None:
        rng = jax.device_put(rng, layout.replicated(mesh))
    if prompt_lengths is None:
        return run(params, draft_params, prompt, rng)
    lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if lengths.shape != (b,):
        raise ValueError(
            f"prompt_lengths must have shape ({b},), got {lengths.shape}"
        )
    import numpy as _np

    host = _np.asarray(lengths)
    if (host < 1).any() or (host > s).any():
        raise ValueError(
            f"prompt_lengths must be in [1, {s}] (the padded prompt "
            f"width); got {host.tolist()}"
        )
    if mesh is not None:
        lengths = jax.device_put(
            lengths, layout.activation_sharding(mesh, "per_row")
        )
    return run(params, draft_params, prompt, rng, lengths)


@functools.lru_cache(maxsize=16)
def _build_speculative(
    model, draft_model, b, s, max_new_tokens, k, eos_id, mixed=False,
    mesh=None, temperature=0.0,
):
    """Compile-once body per (models, shapes, k, eos, temperature)."""
    sampled = temperature > 0.0

    def greedy(logits):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def probs_of(logits):
        return jax.nn.softmax(
            logits.astype(jnp.float32) / temperature, axis=-1
        )

    def pick_first(logits, key):
        # the first emitted token comes from the target alone
        if not sampled:
            return greedy(logits)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature
        ).astype(jnp.int32)

    def constrain(cache, tp_sharded):
        # pin both KV caches at the loop boundary: the target's like
        # generate's mesh path (batch on 'data', heads on 'model'), the
        # draft's batch-sharded only (its weights are replicated —
        # layout.decode_cache_spec(tp=False) drops the head axis)
        if mesh is None:
            return cache
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, layout.decode_cache_sharding(mesh, x, tp=tp_sharded)
            ),
            cache,
        )

    # the mesh is ambient while the body is traced: under one, the
    # draft's one-position steps keep the einsum GSPMD partitions
    @jax.jit
    @use_mesh(mesh)
    def run(params, draft_params, prompt, rng, lengths=None):
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        # Prefill BOTH caches on the prompt. padded=True everywhere:
        # slots are positions, which is what lets per-row acceptance
        # advance rows independently.
        t_logits, t_prefill = model.apply(
            {"params": params},
            prompt,
            positions=positions,
            decode=True,
            padded=True,
            mutable=["cache"],
        )
        _, d_prefill = draft_model.apply(
            {"params": draft_params},
            prompt,
            positions=positions,
            decode=True,
            padded=True,
            mutable=["cache"],
        )
        # first token: the target's own greedy pick at each row's last
        # REAL prompt position (cache invariant from here on: `last` is
        # NOT in either cache; `pos` is the next position to fill).
        # Mixed-length rows: the pad-slot garbage a full-width prefill
        # writes past a row's true length is only ever attended after
        # being overwritten by that row's real tokens (write-before-
        # attend + query position == write position), exactly as in
        # ``generate``'s padded path.
        rng, key0 = jax.random.split(rng)
        if mixed:
            last = pick_first(
                jnp.take_along_axis(
                    t_logits, (lengths - 1)[:, None, None], axis=1
                )[:, 0],
                key0,
            )
            pos0 = lengths + 1
        else:
            last = pick_first(t_logits[:, -1], key0)
            pos0 = jnp.full((b,), s + 1, jnp.int32)
        fill = eos_id if eos_id is not None else 0
        buf = jnp.full((b, max_new_tokens), fill, jnp.int32)
        buf = buf.at[:, 0].set(last)
        done = (
            (last == eos_id)
            if eos_id is not None
            else jnp.zeros((b,), bool)
        )
        n_out = jnp.ones((b,), jnp.int32)

        def draft_step(cache, tok, pos, key=None):
            logits, updated = draft_model.apply(
                {"params": draft_params, "cache": cache},
                tok[:, None],
                positions=pos[:, None],
                decode=True,
                padded=True,
                mutable=["cache"],
            )
            logits = logits[:, -1]
            if not sampled:
                return updated["cache"], greedy(logits), None
            nxt = jax.random.categorical(
                key, logits.astype(jnp.float32) / temperature
            ).astype(jnp.int32)
            return updated["cache"], nxt, probs_of(logits)

        def cond(carry):
            _, _, _, _, n_out, done, _, _ = carry
            return ~jnp.all(done | (n_out >= max_new_tokens))

        def body(carry):
            t_cache, d_cache, last, pos, n_out, done, buf, rng = carry
            rng, key_draft, key_verify = jax.random.split(rng, 3)

            # --- draft k tokens sequentially -------------------------
            def dstep(c, xs):
                d_cache, tok = c
                j, key = xs
                d_cache, nxt, q = draft_step(
                    d_cache, tok, pos - 1 + j, key
                )
                return (d_cache, nxt), (nxt, q)

            draft_keys = jax.random.split(key_draft, k)
            (d_cache, _), (drafts, d_probs) = jax.lax.scan(
                dstep,
                (d_cache, last),
                (jnp.arange(k, dtype=jnp.int32), draft_keys),
            )
            drafts = jnp.swapaxes(drafts, 0, 1)  # (B, k)
            if sampled:
                d_probs = jnp.swapaxes(d_probs, 0, 1)  # (B, k, V)
            # feed the draft its own final proposal: when all k are
            # accepted the next iteration queries slot pos+k-1, which
            # only this write fills (an unwritten slot would silently
            # degrade the NEXT round's proposals — never correctness,
            # which the target alone decides)
            d_cache, _, _ = draft_step(
                d_cache, drafts[:, -1], pos - 1 + k, draft_keys[-1]
            )
            d_cache = constrain(d_cache, tp_sharded=False)

            # --- one target forward over [last, drafts[:-1]] ---------
            # logits[:, j] predicts the token at position pos+j
            verify_in = jnp.concatenate([last[:, None], drafts], axis=1)[
                :, : k + 1
            ]
            vpos = pos[:, None] - 1 + jnp.arange(k + 1, dtype=jnp.int32)
            t_logits, t_upd = model.apply(
                {"params": params, "cache": t_cache},
                verify_in,
                positions=vpos,
                decode=True,
                padded=True,
                mutable=["cache"],
            )
            t_cache = constrain(t_upd["cache"], tp_sharded=True)
            if sampled:
                # rejection-sampling verification: emitted tokens are
                # distributed exactly as target-only sampling
                emit, accepted = speculative_accept(
                    key_verify, probs_of(t_logits), d_probs, drafts
                )
            else:
                t_pick = greedy(t_logits)  # (B, k+1) target's choices
                # accepted = longest prefix where draft == target pick;
                # emitted tokens are target picks throughout (positions
                # 0..a-1 equal the drafts there, position a is the
                # correction / bonus) — which is WHY output == plain
                # greedy
                match = t_pick[:, :k] == drafts  # (B, k)
                accepted = jnp.sum(
                    jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
                )  # (B,) in [0, k]
                emit = t_pick  # (B, k+1)
            j_idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            valid = j_idx <= accepted[:, None]

            if eos_id is not None:
                # nothing after a row's first EOS is emitted
                before_eos = (
                    jnp.cumsum((emit == eos_id).astype(jnp.int32), axis=1)
                    - (emit == eos_id).astype(jnp.int32)
                ) == 0
                valid &= before_eos
            valid &= ~done[:, None]

            # scatter this iteration's tokens at per-row offsets;
            # out-of-range (row full) writes drop
            rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k + 1))
            cols = jnp.where(
                valid, n_out[:, None] + j_idx, max_new_tokens
            )
            buf = buf.at[rows, cols].set(emit, mode="drop")

            emitted = jnp.sum(valid.astype(jnp.int32), axis=1)
            if eos_id is not None:
                done = done | jnp.any((emit == eos_id) & valid, axis=1)
            n_out_new = jnp.minimum(n_out + emitted, max_new_tokens)
            done = done | (n_out_new >= max_new_tokens)

            # next `last` = the last token this row emitted (the
            # correction, or the last pre-EOS token for finishing
            # rows); frozen rows keep their state
            last_j = jnp.maximum(emitted - 1, 0)
            new_last = jnp.take_along_axis(
                emit, last_j[:, None], axis=1
            )[:, 0]
            step_rows = emitted > 0
            last = jnp.where(step_rows, new_last, last)
            pos = jnp.where(done, pos, pos + emitted)
            n_out = n_out_new
            return (t_cache, d_cache, last, pos, n_out, done, buf, rng)

        carry = (
            constrain(t_prefill["cache"], tp_sharded=True),
            constrain(d_prefill["cache"], tp_sharded=False),
            last, pos0, n_out, done, buf, rng,
        )
        carry = jax.lax.while_loop(cond, body, carry)
        return carry[6]

    return run
