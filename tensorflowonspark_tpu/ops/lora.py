"""LoRA — low-rank adapters for parameter-efficient fine-tuning.

Full fine-tuning of a 7B model needs ~3 copies of every weight in HBM
(params + grads + Adam moments, ~80 GB fp32); LoRA trains only a pair
of rank-r factors per targeted matrix (``w ≈ w_base + a @ b · s``),
shrinking trainable state to well under 1% while the frozen base stays
a single read-only copy. The TPU shape of the idea:

- :class:`LoraTensor` is a registered pytree node (like
  ``quant.QuantTensor``), so LoRA-ified param trees ride jit,
  ``device_put``, mesh sharding, and orbax unchanged.
- The base matrix is wrapped in ``stop_gradient`` INSIDE the op, so XLA
  never builds the base-weight gradient matmuls — the backward pass
  costs scale with the adapters, not the model.
- :func:`lora_optimizer` masks the frozen leaves out of the optimizer
  with ``optax.multi_transform``, so Adam moments exist ONLY for the
  adapters — that is where the HBM win comes from.
- ``models/llama.py:QDense`` consumes ``LoraTensor`` kernels natively;
  ``llama_param_shardings`` shards ``base`` like the kernel it wraps
  and the factors along their matching halves, so FSDP/TP configs work
  untouched.

Reference parity note: the reference delegated all training machinery
to TF and had no parameter-efficient path (SURVEY.md §2.3); this is
capability beyond it, motivated by the same HBM arithmetic as the
optimizer-state footprint (``compute/optim.py``).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import struct

DEFAULT_TARGETS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)


@struct.dataclass
class LoraTensor:
    """``w_eff = base + a @ b * scale`` with ``base`` frozen.

    ``base`` (in, out); ``a`` (in, r) gaussian-init; ``b`` (r, out)
    zero-init — so a freshly added adapter is an exact no-op (the
    standard LoRA init). ``scale`` = alpha / r, static.
    """

    base: jax.Array
    a: jax.Array
    b: jax.Array
    scale: float = struct.field(pytree_node=False, default=1.0)

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype


def lora_apply(x: jax.Array, w: LoraTensor) -> jax.Array:
    """``x @ w_eff`` without materializing the merged matrix: the
    adapter path is two skinny matmuls (B·S·in·r + B·S·r·out FLOPs —
    negligible at r≪min(in,out)). ``stop_gradient`` on the base keeps
    the backward pass adapter-sized."""
    base = jax.lax.stop_gradient(w.base)
    y = x @ base.astype(x.dtype)
    lo = (x @ w.a.astype(x.dtype)) @ w.b.astype(x.dtype)
    return y + lo * w.scale


def add_lora(
    params: Any,
    rank: int,
    rng: jax.Array,
    targets: Sequence[str] = DEFAULT_TARGETS,
    alpha: float | None = None,
    dtype=jnp.float32,
) -> Any:
    """Wrap every 2-D leaf whose path contains a target name in a
    :class:`LoraTensor`. ``alpha`` defaults to ``rank`` (scale 1.0).
    The wrapped tree's forward output is EXACTLY the base tree's until
    the adapters train (b starts at zero)."""
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    scale = (alpha if alpha is not None else float(rank)) / float(rank)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(rng, len(flat))

    def name_of(path) -> str:
        return "/".join(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path
        )

    out = []
    n_wrapped = 0
    for (path, leaf), key in zip(flat, keys):
        joined = name_of(path)
        if (
            hasattr(leaf, "ndim")
            and leaf.ndim == 2
            and any(t in joined for t in targets)
        ):
            d_in, d_out = leaf.shape
            if rank > min(d_in, d_out):
                raise ValueError(
                    f"rank {rank} exceeds min dim of {joined} {leaf.shape}"
                )
            a = (
                jax.random.normal(key, (d_in, rank), dtype)
                / jnp.sqrt(jnp.asarray(d_in, dtype))
            )
            b = jnp.zeros((rank, d_out), dtype)
            out.append(LoraTensor(base=leaf, a=a, b=b, scale=scale))
            n_wrapped += 1
        else:
            out.append(leaf)
    if n_wrapped == 0:
        raise ValueError(
            f"no 2-D params matched targets {tuple(targets)}; nothing to "
            "adapt"
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def merge_lora(params: Any) -> Any:
    """Fold trained adapters into plain kernels (``base + a@b·s``) for
    serving/export — zero inference overhead, and the merged tree is a
    drop-in for every consumer of the original params."""

    def rule(x):
        if isinstance(x, LoraTensor):
            merged = (
                x.base.astype(jnp.float32)
                + (x.a.astype(jnp.float32) @ x.b.astype(jnp.float32))
                * x.scale
            )
            return merged.astype(x.base.dtype)
        return x

    return jax.tree.map(
        rule, params, is_leaf=lambda x: isinstance(x, LoraTensor)
    )


def lora_labels(params: Any) -> Any:
    """'train' / 'freeze' label tree for ``optax.multi_transform``:
    adapter factors train, everything else (including every LoraTensor
    base) freezes. Same structure as ``params``."""

    def rule(x):
        if isinstance(x, LoraTensor):
            return LoraTensor(base="freeze", a="train", b="train",
                              scale=x.scale)
        return "freeze"

    return jax.tree.map(
        rule, params, is_leaf=lambda x: isinstance(x, LoraTensor)
    )


def lora_optimizer(
    tx: optax.GradientTransformation, params: Any
) -> optax.GradientTransformation:
    """Wrap ``tx`` so ONLY adapter leaves get optimizer state and
    updates: frozen leaves carry `set_to_zero` (no moments in HBM —
    the point of LoRA's memory win). The base's gradients are already
    zero (``lora_apply`` stop_gradient), this guarantees no optimizer
    bytes either."""
    return optax.multi_transform(
        {"train": tx, "freeze": optax.set_to_zero()},
        lora_labels(params),
    )


@struct.dataclass
class MultiLoraTensor:
    """``w_eff(row) = base + a[id] @ b[id] * scale`` — a BANK of K
    adapters over one shared frozen base, routed per batch row.

    The serving shape of LoRA (S-LoRA style): one resident copy of the
    base weights serves many fine-tunes concurrently; each request picks
    its adapter by integer id. ``a`` (K, in, r), ``b`` (K, r, out).
    Per-row application gathers the two skinny factors for each row —
    O(B·(in+out)·r) bytes, trivial next to the base read — so rows with
    different adapters share one batched matmul against ``base``.

    Convention: make slot 0 a zero adapter (``b[0] == 0``) so plain
    requests route there and run the base model exactly (the
    :func:`multi_lora_bank` builder does this).
    """

    base: jax.Array
    a: jax.Array
    b: jax.Array
    scale: float = struct.field(pytree_node=False, default=1.0)

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    @property
    def n_adapters(self) -> int:
        return self.a.shape[0]


def multi_lora_apply(
    x: jax.Array, w: MultiLoraTensor, adapter_ids: jax.Array
) -> jax.Array:
    """``x[i] @ w_eff(adapter_ids[i])`` for x (B, S, in), ids (B,).

    Same term order as :func:`lora_apply` (base matmul + two skinny
    adapter matmuls, scale applied last), so a row routed to adapter k
    matches a single-``LoraTensor`` run of that adapter bit-for-bit in
    shape and closely in rounding. The gathers materialize only the
    selected (B, in, r)/(B, r, out) factors, never a merged matrix."""
    base = jax.lax.stop_gradient(w.base)
    y = x @ base.astype(x.dtype)
    a_sel = jnp.take(w.a, adapter_ids, axis=0).astype(x.dtype)  # (B,in,r)
    b_sel = jnp.take(w.b, adapter_ids, axis=0).astype(x.dtype)  # (B,r,out)
    lo = jnp.einsum("bsd,bdr->bsr", x, a_sel)
    lo = jnp.einsum("bsr,bro->bso", lo, b_sel)
    return y + lo * w.scale


def multi_lora_bank(adapters: Sequence[Any]) -> Any:
    """Stack N single-adapter trees (from :func:`add_lora`, trained or
    not) into a served bank over the FIRST tree's bases.

    Slot 0 of the resulting bank is always the ZERO adapter (exact base
    model); trained adapters occupy slots 1..N. Every adapter must wrap
    the same kernels with the same rank and scale — mismatched trees
    (different targets/rank) fail loudly rather than mis-route."""
    if not adapters:
        raise ValueError("need at least one adapter tree")
    flats = [
        jax.tree_util.tree_flatten(
            t, is_leaf=lambda x: isinstance(x, LoraTensor)
        )
        for t in adapters
    ]
    treedef = flats[0][1]
    for i, (_, td) in enumerate(flats[1:], 1):
        if td != treedef:
            raise ValueError(
                f"adapter {i} has a different tree structure than "
                "adapter 0 (different LoRA targets?)"
            )
    out = []
    for leaves in zip(*(f[0] for f in flats)):
        first = leaves[0]
        if not isinstance(first, LoraTensor):
            out.append(first)
            continue
        for i, leaf in enumerate(leaves[1:], 1):
            if (
                leaf.a.shape != first.a.shape
                or leaf.scale != first.scale
            ):
                raise ValueError(
                    f"adapter {i} rank/scale mismatch: "
                    f"{leaf.a.shape}/{leaf.scale} vs "
                    f"{first.a.shape}/{first.scale}"
                )
            if leaf.base is not first.base:
                # Adapters fine-tuned from DIFFERENT base checkpoints
                # would silently serve on adapter 0's base. Same-object
                # is the common case (one tree add_lora'd N times); for
                # distinct arrays a 64-element sample comparison catches
                # a wrong checkpoint at bank-build time for microseconds.
                import numpy as np

                sa = np.asarray(leaf.base.ravel()[:64])
                sb = np.asarray(first.base.ravel()[:64])
                if not np.array_equal(sa, sb):
                    raise ValueError(
                        f"adapter {i} wraps a different base weight "
                        "than adapter 0 — all bank adapters must be "
                        "fine-tunes of the SAME base checkpoint"
                    )
        a = jnp.stack(
            [jnp.zeros_like(first.a)] + [l.a for l in leaves]
        )
        b = jnp.stack(
            [jnp.zeros_like(first.b)] + [l.b for l in leaves]
        )
        out.append(
            MultiLoraTensor(
                base=first.base, a=a, b=b, scale=first.scale
            )
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def select_adapter(params: Any, k: int) -> Any:
    """Slice adapter ``k`` out of a bank as a plain single-``LoraTensor``
    tree — the reference path for tests and for exporting one tenant's
    model (``merge_lora(select_adapter(bank, k))``)."""

    def rule(x):
        if isinstance(x, MultiLoraTensor):
            return LoraTensor(
                base=x.base, a=x.a[k], b=x.b[k], scale=x.scale
            )
        return x

    return jax.tree.map(
        rule, params, is_leaf=lambda x: isinstance(x, MultiLoraTensor)
    )


def bank_size(params: Any) -> int:
    """Number of adapter slots in a bank tree (0 = no bank present)."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, MultiLoraTensor)
    ):
        if isinstance(leaf, MultiLoraTensor):
            n = max(n, leaf.n_adapters)
    return n


def rewrap_lora(tree: Any, scale: float = 1.0) -> Any:
    """Reconstruct LoRA pytree nodes from a checkpoint restored WITHOUT
    a target tree.

    Orbax returns plain nested dicts in that mode, so ``LoraTensor`` /
    ``MultiLoraTensor`` nodes come back as ``{"base", "a", "b"}`` dicts
    (the static ``scale`` field is not stored at all). This rewraps
    them — 2-D ``a`` → :class:`LoraTensor`, 3-D → :class:`MultiLoraTensor`
    bank — so a served checkpoint routes through the adapter paths
    again. ``scale`` must be re-supplied when the fine-tune used
    ``alpha != rank`` (the default ``add_lora`` scale is 1.0)."""

    def is_node(x):
        return isinstance(x, dict) and set(x) == {"base", "a", "b"}

    def rule(x):
        if is_node(x):
            cls = MultiLoraTensor if x["a"].ndim == 3 else LoraTensor
            return cls(base=x["base"], a=x["a"], b=x["b"], scale=scale)
        return x

    return jax.tree.map(rule, tree, is_leaf=is_node)
