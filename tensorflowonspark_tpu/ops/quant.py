"""Int8 weight-only quantization for inference (decode is HBM-bound).

KV-cache decode reads every weight once per generated token, so the
resident weight bytes ARE the decode cost floor (the share of HBM
bandwidth llama1b decode reaches is not measured on this
installation). Per-output-channel symmetric
int8 storage halves that footprint: a 7B model's weights drop from
~13 GB bf16 to ~6.7 GB — the difference between fitting and not fitting
a 16 GB chip next to its KV cache.

Two layers:

- :func:`quantize_tree` / :func:`dequantize_tree` — pytree-level
  quantization. ``QuantTensor`` is a registered pytree node, so
  quantized trees ride jit/device_put/orbax like any param tree.
- :func:`quantized_dot` — ``x @ w`` against a ``QuantTensor`` with the
  scales applied to the fp32 accumulator per output channel: no bf16
  weight is ever materialized, so both the footprint AND the per-token
  weight read are int8. The Llama modules consume ``QuantTensor``
  kernels natively through this op (``models/llama.py:QDense``, the
  embed gather, and the head projection) — pass a ``quantize_tree``'d
  param tree to ``generate`` and decode runs against int8 weights.

Accuracy: per-channel symmetric int8 on transformer matmul weights is
the standard weight-only recipe (~0.1% relative error per layer; see
the round-trip test tolerances in ``tests/test_quant.py``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class QuantTensor:
    """Symmetric per-channel int8 weight: ``w ≈ q * scale``.

    ``q`` is int8 with the original shape; ``scale`` is fp32 broadcast
    along ``axis`` (kept as a struct field so the pair travels as one
    pytree node through jit, device placement, and checkpointing).
    """

    q: jax.Array
    scale: jax.Array
    axis: int = struct.field(pytree_node=False, default=-1)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.scale.dtype


def quantize(w: jax.Array, axis: int = -1) -> QuantTensor:
    """Per-channel symmetric int8: one scale per slice along ``axis``
    (the output-channel dim for row-major ``(in, out)`` kernels), i.e.
    the max-abs reduction runs over every OTHER axis."""
    w32 = w.astype(jnp.float32)
    channel = axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != channel)
    amax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantTensor(q=q, scale=scale, axis=channel)


def dequantize(t: QuantTensor, dtype=jnp.bfloat16) -> jax.Array:
    return (t.q.astype(jnp.float32) * t.scale).astype(dtype)


def quantize_tree(
    params: Any,
    min_size: int = 1 << 16,
    axis: int = -1,
    axis_overrides: dict[str, int] | None = None,
) -> Any:
    """Quantize every 2-D floating leaf with ``>= min_size`` elements;
    small leaves (norm scales, biases) stay as-is. Only matrices: that is
    what the consumers handle (``QDense``, the embed gather, the head
    projection) — 3-D MoE expert banks are deliberately left unquantized
    (``parallel/moe.py`` consumes plain arrays).

    ``axis_overrides`` maps a leaf's *name* (its last pytree path key)
    to a quantization axis. The default ``{"embed": 0}`` stores the
    ``(vocab, hidden)`` embedding table with per-ROW scales: an axis=-1
    scale would be a max-abs over the whole 32k-row vocab per hidden
    unit, so a single outlier token row inflates quantization error for
    every token. The head projection keeps axis=-1 (its name is
    ``lm_head``), matching ``quantized_dot``'s output-channel contract.
    """
    if axis_overrides is None:
        axis_overrides = {"embed": 0}

    def leaf_name(path) -> str:
        if not path:
            return ""
        last = path[-1]
        for attr in ("key", "name", "idx"):
            if hasattr(last, attr):
                return str(getattr(last, attr))
        return str(last)

    def rule(path, x):
        if (
            hasattr(x, "ndim")
            and x.ndim == 2
            and x.size >= min_size
            and jnp.issubdtype(x.dtype, jnp.floating)
        ):
            return quantize(x, axis=axis_overrides.get(leaf_name(path), axis))
        return x

    return jax.tree_util.tree_map_with_path(rule, params)


def dequantize_tree(params: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse of :func:`quantize_tree`; call INSIDE jit so int8 stays
    the at-rest representation."""
    return jax.tree.map(
        lambda x: dequantize(x, dtype) if isinstance(x, QuantTensor) else x,
        params,
        is_leaf=lambda x: isinstance(x, QuantTensor),
    )


def quantized_dot(x: jax.Array, w: QuantTensor) -> jax.Array:
    """``x @ w`` with the scales folded into the fp32 accumulator.

    The int8 operand feeds the dot directly (no materialized bf16
    weight); per-output-channel scales multiply the accumulator. Only
    ``axis=-1`` (output-channel) quantization is supported — that is
    what :func:`quantize_tree` produces for ``(in, out)`` kernels.
    """
    if w.axis != -1 and w.axis != w.q.ndim - 1:
        raise ValueError("quantized_dot needs output-channel (axis=-1) scales")
    acc = jax.lax.dot_general(
        x.astype(jnp.bfloat16),
        w.q,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (acc * w.scale.reshape((1,) * (acc.ndim - 1) + (-1,))).astype(
        x.dtype
    )
