"""Attention ops.

``dot_product_attention`` routes to the best available implementation:

- ``impl='xla'`` — plain einsum attention; XLA fuses softmax chains well
  and this is the safest default on CPU/testing.
- ``impl='flash'`` — the Pallas TPU flash-attention kernel from
  :mod:`tensorflowonspark_tpu.ops.flash_attention` (blockwise online
  softmax in VMEM; O(seq) memory).
- ``impl='auto'`` — flash on a single-device TPU when shapes allow; on a
  multi-device TPU with an ambient mesh (``parallel.use_mesh`` — the
  train-step builder publishes it during tracing), flash per-shard under
  ``shard_map`` with batch/head sharding (:func:`mesh_flash_attention`);
  otherwise xla, which GSPMD partitions fine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.utils import compat

# Test hook: lets CI exercise the TPU-only dispatch decisions (the
# mesh-flash route below) on the 8-device virtual CPU mesh with the
# Pallas interpreter. Read only in the un-jitted dispatcher, never inside
# a jitted function, so flipping it cannot leave stale traces behind.
TREAT_AS_TPU = False


def _on_tpu() -> bool:
    return TREAT_AS_TPU or jax.default_backend() == "tpu"


def _one_head_width(q, v) -> bool:
    """The flash kernel takes one head width, from q: latent attention's
    prefill (query-key width 192, value width 128) keeps the einsum.
    ``v`` None: a caller that has none to show."""
    return v is None or v.shape[3] == q.shape[3]


def _flash_shapes_ok(q, k, segment_ids) -> bool:
    """Shapes the Pallas flash kernel accepts (whole-array view)."""
    return (
        q.shape[1] >= 128
        and q.shape[1] % 128 == 0
        and k.shape[1] % 128 == 0
        and q.shape[3] >= 64
        # segment masking needs square attention (one id per position)
        and (segment_ids is None or q.shape[1] == k.shape[1])
    )


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Reference attention: (B, Sq, H, D) x (B, Sk, H, D) -> (B, Sq, H, D).

    Supports grouped-query attention: k/v may have fewer heads than q as
    long as q_heads % kv_heads == 0.
    """
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    scale = (d**-0.5) if scale is None else scale
    if hq != hk:
        if hq % hk:
            raise ValueError(f"q heads {hq} not divisible by kv heads {hk}")
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            # sliding window: query i (absolute i + sk - sq) attends only
            # the last `window` keys — same end-aligned convention
            q_pos = jnp.arange(sq)[:, None] + (sk - sq)
            k_pos = jnp.arange(sk)[None, :]
            mask = mask & (q_pos - k_pos < window)
        logits = jnp.where(mask[None, None], logits, jnp.finfo(logits.dtype).min)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(
            seg_mask[:, None], logits, jnp.finfo(logits.dtype).min
        )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    impl: str = "auto",
    window: int | None = None,
) -> jax.Array:
    """Multi-head attention with optional causal masking and GQA.

    Shapes: q (B, Sq, Hq, D); k/v (B, Sk, Hkv, D); returns (B, Sq, Hq, D).

    ``window`` restricts each query to the last ``window`` keys
    (sliding-window / Mistral-style local attention; requires
    ``causal=True``). All impls support it: xla/flash mask (the flash
    kernel also restricts its grids to the window span), ring shortens
    the rotation to the owners in reach (``parallel.ring_attention.
    ring_hops`` — O(window) ICI traffic per device), ulysses passes it
    to the per-device full-sequence attention.

    ``impl='ring'`` runs sequence-parallel ring attention over the ambient
    mesh's ``seq`` axis (set with ``parallel.use_mesh``); the mesh is a
    trace-time object, so this path is dispatched outside the jit cache —
    it is meant to be called from inside an outer jitted train step.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    if impl in ("ring", "ulysses"):
        from tensorflowonspark_tpu.parallel import current_mesh

        mesh = current_mesh()
        if mesh is None:
            raise ValueError(
                f"impl={impl!r} needs an ambient mesh; wrap the call (or "
                "the train-step trace) in "
                "tensorflowonspark_tpu.parallel.use_mesh"
            )
        if mesh.shape.get("seq", 1) == 1 and mesh.shape.get("model", 1) == 1:
            # re-enter the auto dispatcher (not _jitted_attention
            # directly) so degenerate ring/ulysses configs still get the
            # mesh-flash shard_map route on a multi-device batch mesh
            return dot_product_attention(
                q, k, v, causal=causal, scale=scale,
                segment_ids=segment_ids, impl="auto", window=window,
            )
        if impl == "ring":
            from tensorflowonspark_tpu.parallel import mesh_ring_attention

            # window ALSO shortens the ring: see ring_hops — a device
            # stops rotating once no reachable owner can contribute
            return mesh_ring_attention(
                q, k, v, mesh, causal=causal, scale=scale,
                segment_ids=segment_ids, window=window,
            )
        from tensorflowonspark_tpu.parallel import mesh_ulysses_attention

        return mesh_ulysses_attention(
            q, k, v, mesh, causal=causal, scale=scale,
            segment_ids=segment_ids, window=window,
        )
    if impl == "auto":
        mesh = _flash_mesh(q, k, segment_ids, v)
        if mesh is not None:
            return mesh_flash_attention(
                q, k, v, mesh, causal=causal, scale=scale,
                segment_ids=segment_ids, window=window,
            )
        impl = _local_auto_impl(q, k, segment_ids, v)
    return _jitted_attention(
        q, k, v, causal=causal, scale=scale,
        segment_ids=segment_ids, impl=impl, window=window,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "impl", "window")
)
def _jitted_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    impl: str = "auto",
    window: int | None = None,
) -> jax.Array:
    if impl == "auto":
        # 'auto' is resolved by the dispatcher (dot_product_attention:
        # _flash_mesh for the shard_map route, _local_auto_impl
        # otherwise) BEFORE this jitted function is entered — resolving
        # it here would fork the gate logic and bake trace-time ambient
        # state into the jit cache.
        raise ValueError(
            "impl='auto' must be resolved before _jitted_attention; "
            "call dot_product_attention instead"
        )
    if impl == "flash":
        from tensorflowonspark_tpu.ops.flash_attention import (
            flash_attention,
        )

        if v.shape[-1] != q.shape[-1]:
            raise ValueError(
                f"impl='flash' takes one head width; q has {q.shape[-1]} "
                f"and v {v.shape[-1]} (use impl='auto' or 'xla')"
            )
        # positional: custom_vjp functions reject keyword arguments
        return flash_attention(
            q, k, v, causal, scale, None, None, window, segment_ids
        )
    return _xla_attention(
        q, k, v, causal=causal, scale=scale, segment_ids=segment_ids,
        window=window,
    )


def _local_auto_impl(q, k, segment_ids, v=None) -> str:
    """``auto`` for operands known to be shard-LOCAL: on a single-device
    process trivially, or inside a shard_map body (e.g. a ulysses or
    gpipe stage), where each device holds its own block — the raw flash
    kernel is safe there on any device count; the multi-device gate only
    guards GSPMD-sharded whole arrays."""
    try:
        local = len(jax.devices()) == 1
    except RuntimeError:  # pragma: no cover - no backend at all
        return "xla"
    if not local:
        try:
            local = jax.core.nonempty_axis_env_DO_NOT_USE()
        except AttributeError:  # pragma: no cover - future jax rename
            local = False
    return (
        "flash"
        if (
            _on_tpu() and local and _one_head_width(q, v)
            and _flash_shapes_ok(q, k, segment_ids)
        )
        else "xla"
    )


def _flash_mesh(q, k, segment_ids, v=None):
    """The ambient mesh, iff ``auto`` should take the shard_map flash
    route: multi-device TPU, a published mesh whose only sharded axes are
    batch/head-like, and shapes the kernel accepts both globally and
    per-shard. Returns None for "resolve locally instead"."""
    from tensorflowonspark_tpu.parallel.context import dispatch_mesh

    # Only batch/head sharding: a sharded sequence wants ring/ulysses
    # (impl='ring'|'ulysses'), and pipe/expert bodies already run inside
    # a shard_map — nesting another would need a sub-mesh we don't have.
    mesh = dispatch_mesh(
        _on_tpu, q.shape[0], forbidden_axes=("pipe", "expert", "seq")
    )
    if mesh is None:
        return None
    tp = mesh.shape.get("model", 1)
    if q.shape[2] % tp or k.shape[2] % tp:
        return None
    if not (_one_head_width(q, v) and _flash_shapes_ok(q, k, segment_ids)):
        return None
    return mesh


def mesh_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    causal: bool = False,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array:
    """Flash attention on a multi-device mesh via ``shard_map``.

    GSPMD cannot partition a ``pallas_call`` (the same limitation
    documented at :func:`bn_kernels.stats_mesh` and
    :func:`parallel.context.dispatch_mesh`): left inside a plain
    ``jit`` over a sharded mesh, the kernel's operands would be
    all-gathered onto every chip. Attention is embarrassingly parallel
    over batch and heads, so this wrapper places the kernel per-shard —
    batch over ``(data, fsdp)``, heads over ``model`` (K/V heads shard
    the same way, so GQA grouping stays intact per shard), sequence
    replicated (a sharded sequence wants ring/ulysses instead). No
    collectives run inside the body; the backward pass is the flash
    custom-VJP per shard, transposed by shard_map for free.

    Inputs are global arrays (B, S, H, D); B must divide the
    ``(data, fsdp)`` extent and both head counts the ``model`` extent
    (checked by the ``auto`` gate in :func:`_flash_mesh`; direct callers
    get shard_map's own divisibility errors).
    """
    from tensorflowonspark_tpu.compute import layout
    from tensorflowonspark_tpu.ops.flash_attention import flash_attention
    from tensorflowonspark_tpu.parallel.context import sp_specs_and_args

    spec = layout.activation_spec("attn_bshd")

    def body(q, k, v, segment_ids=None):
        # positional: custom_vjp functions reject keyword arguments
        return flash_attention(
            q, k, v, causal, scale, None, None, window, segment_ids
        )

    in_specs, args = sp_specs_and_args(spec, q, k, v, segment_ids)
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=spec,
        check_vma=False,
    )
    return fn(*args)
