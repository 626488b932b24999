"""Pallas TPU kernels for BatchNorm channel statistics.

Why (figures from traces taken before PR 1 on an earlier installation;
not measured on this one): the ResNet-50 train step spent
~45% of its time in XLA's `convert_reduce_fusion` ops — the BN statistics
reductions. The op *count* (~2 fused passes per BN layer) shows XLA already
merges the sibling reductions; the *rate* is the problem: the 97 reduce
fusions move ~9-14 GB of activations but take 44.5 ms/step, i.e. ~20-30%
of the chip's HBM streaming bandwidth. These kernels pin the streaming
loop explicitly —
one DMA'd (block_rows x block_cols) bf16 tile per grid step, fp32
accumulation in registers, per-channel partial sums revisiting a
VMEM-resident output block — so the stats passes run at the DMA rate the
flash-attention kernel in this package already demonstrates.

Two kernels, both reducing over all rows of a (rows, channels) view:

- ``pair_stats(x)``      -> (sum(x), sum(x*x))     : the forward pass
- ``cross_stats(dy, x)`` -> (sum(dy), sum(dy*x))   : the backward pass

The backward pass deliberately computes raw ``sum(dy*x)`` rather than
``sum(dy*xhat)`` so the kernel needs no per-channel scalar inputs; the
caller derives ``sum(dy*xhat) = invstd * (sum(dy*x) - mean*sum(dy))`` in
fp32 (same cancellation class as the one-pass variance, accepted and
documented in ops/batch_norm.py).

Status (same earlier installation): IN-CONTEXT these kernels
REGRESSED — ResNet-50 8.9% MFU vs 16.1% through the XLA reduces,
Inception-v3 13.7% vs 18.2%. The "slow" reduce fusions were amortized:
fused with neighboring elementwise work over conv outputs still resident
in the fusion; an opaque ``pallas_call`` severs that and forces extra
materialized activation round-trips that outweigh the streamed reduce's
rate win. ``impl='auto'`` therefore
never picks these kernels; they remain for explicit standalone-stats
callers, where ``cross_stats`` measured ~2x the XLA reduce rate in
isolation.

Parity note: the reference delegated BN to TF's cuDNN fused kernels
(SURVEY.md §1 — no compute code of its own); this is the TPU-native
equivalent of that fused-statistics path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from tensorflowonspark_tpu.utils import compat

# Test hook: run the kernels in the Pallas interpreter (works on CPU).
INTERPRET = False

_OUT_SUBLANES = 8  # output blocks are (8, block_c): Mosaic's min f32 tile


def _choose_blocks(rows: int, cols: int) -> tuple[int, int]:
    """Tile choice: wide-ish lanes, ~1 MB bf16 input tiles.

    A 512-lane block keeps the DMA large while letting C=2048 layers
    partition cleanly. Narrow layers are real, not hypothetical —
    Inception-v3 BN sits at C=32/48/80/96 and the ResNet stem at C=64
    (models/inception.py, models/resnet.py) — so ``min(cols, 512)``
    passes sub-128-lane and non-128-aligned column blocks straight to
    Mosaic, which pads the lane dimension internally. Rows
    default to 1024 (so a (1024, 512) bf16 tile is 1 MB — big enough to
    hit DMA streaming rate, small enough to double-buffer in VMEM).
    """
    block_c = min(cols, 512)
    block_r = min(rows, 1024)
    return block_r, block_c


def _accumulate(ref, value):
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _():
        ref[...] = value

    @pl.when(ri > 0)
    def _():
        ref[...] += value


def _masked_rows(xf: jax.Array, rows: int, block_r: int) -> jax.Array:
    """Zero out rows past the array's true extent in the final partial
    block (zeros are exact identities for every statistic computed here)."""
    if rows % block_r == 0:
        return xf
    ri = pl.program_id(1)
    valid = rows - ri * block_r
    rid = lax.broadcasted_iota(jnp.int32, xf.shape, 0)
    return jnp.where(rid < valid, xf, 0.0)


def _pair_kernel(x_ref, sum_ref, sq_ref, *, rows: int, block_r: int):
    xf = _masked_rows(x_ref[...].astype(jnp.float32), rows, block_r)
    s = jnp.sum(xf, axis=0, keepdims=True)
    q = jnp.sum(xf * xf, axis=0, keepdims=True)
    _accumulate(sum_ref, jnp.broadcast_to(s, sum_ref.shape))
    _accumulate(sq_ref, jnp.broadcast_to(q, sq_ref.shape))


def _cross_kernel(dy_ref, x_ref, sdy_ref, sdyx_ref, *, rows: int, block_r: int):
    # Mask BOTH streams: a masked dy of 0 times a padded-garbage x (which
    # may be NaN) would still be NaN.
    dyf = _masked_rows(dy_ref[...].astype(jnp.float32), rows, block_r)
    xf = _masked_rows(x_ref[...].astype(jnp.float32), rows, block_r)
    s = jnp.sum(dyf, axis=0, keepdims=True)
    q = jnp.sum(dyf * xf, axis=0, keepdims=True)
    _accumulate(sdy_ref, jnp.broadcast_to(s, sdy_ref.shape))
    _accumulate(sdyx_ref, jnp.broadcast_to(q, sdyx_ref.shape))


def _stats_call(kernel, arrays, rows: int, cols: int):
    block_r, block_c = _choose_blocks(rows, cols)
    grid = (pl.cdiv(cols, block_c), pl.cdiv(rows, block_r))
    in_spec = pl.BlockSpec((block_r, block_c), lambda ci, ri: (ri, ci))
    # Output blocks revisit index (0, ci) across the (minor) row grid dim:
    # the accumulator stays VMEM-resident and flushes once per column block.
    out_spec = pl.BlockSpec((_OUT_SUBLANES, block_c), lambda ci, ri: (0, ci))
    out_shape = jax.ShapeDtypeStruct((_OUT_SUBLANES, cols), jnp.float32)
    a, b = pl.pallas_call(
        functools.partial(kernel, rows=rows, block_r=block_r),
        grid=grid,
        in_specs=[in_spec] * len(arrays),
        out_specs=[out_spec, out_spec],
        out_shape=[out_shape, out_shape],
        interpret=INTERPRET,
    )(*arrays)
    return a[0], b[0]


def _as_2d(x: jax.Array) -> jax.Array:
    return x.reshape(-1, x.shape[-1])


def pair_stats(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One streamed pass over ``x`` viewed as (rows, C):
    per-channel ``(sum(x), sum(x*x))`` in fp32."""
    x2 = _as_2d(x)
    return _stats_call(_pair_kernel, (x2,), x2.shape[0], x2.shape[1])


def cross_stats(dy: jax.Array, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One streamed pass over ``(dy, x)`` viewed as (rows, C):
    per-channel ``(sum(dy), sum(dy*x))`` in fp32."""
    dy2, x2 = _as_2d(dy), _as_2d(x)
    assert dy2.shape == x2.shape, (dy2.shape, x2.shape)
    return _stats_call(_cross_kernel, (dy2, x2), x2.shape[0], x2.shape[1])


def use_pallas(impl: str = "auto") -> bool:
    """'pallas' | 'xla' | 'auto'.

    'auto' ALWAYS resolves to the XLA sibling reduces. A chip A/B on an
    earlier installation (before PR 1; not measured on this one)
    falsified the kernels' in-context premise: ResNet-50
    measured 8.9% MFU through these kernels vs 16.1% through the XLA
    stats path (Inception-v3: 13.7% vs 18.2%) — an opaque
    ``pallas_call`` severs XLA's producer/consumer fusion around each
    BN layer, and the extra materialized activation round-trips cost
    more than the streamed reduce saves. The kernels remain for explicit
    ``impl='pallas'`` callers that use the stats standalone (the bwd
    ``cross_stats`` pair measured ~2× the XLA reduce rate in
    isolation) — where there is no surrounding fusion to sever.
    """
    if impl == "pallas":
        return True
    if impl == "xla":
        return False
    if impl != "auto":
        raise ValueError(f"impl must be pallas|xla|auto, got {impl!r}")
    return False


# Test hook, mirroring ops.attention.TREAT_AS_TPU: lets CI exercise the
# TPU-only dispatch decisions on the virtual CPU mesh with the Pallas
# interpreter. Read only at trace time in un-jitted resolvers.
TREAT_AS_TPU = False


def _on_tpu() -> bool:
    return TREAT_AS_TPU or jax.default_backend() == "tpu"


def stats_mesh(impl: str, batch_extent: int):
    """The ambient mesh, iff EXPLICIT ``impl='pallas'`` should take the
    shard_map route: per-shard Pallas partial sums + a psum over the
    batch axes. Returns None for "use use_pallas()'s answer".

    Keyed on explicit 'pallas' (not 'auto' — 'auto' always resolves to
    the XLA reduces since the round-5 regression measure, see
    :func:`use_pallas`): an explicit caller inside a jitted,
    GSPMD-sharded train step would otherwise hand a sharded operand to
    a raw ``pallas_call``, which GSPMD replicates — the shard_map route
    keeps the kernel's operands shard-local. Conditions: multi-device
    TPU, an ambient mesh published (``parallel.use_mesh`` — the
    train/eval-step builders do this during tracing), only batch-like
    axes sharded (conv activations shard the leading dim over
    ``(data, fsdp)``; a model/seq-sharded mesh means someone else owns
    the layout), not already inside a shard_map body, and the batch
    extent divisible over the mesh's batch axes.
    """
    if impl != "pallas":
        return None
    from tensorflowonspark_tpu.parallel.context import dispatch_mesh

    mesh = dispatch_mesh(
        _on_tpu,
        batch_extent,
        forbidden_axes=("pipe", "expert", "model", "seq"),
    )
    if mesh is None:
        return None
    # a trivial batch extent means the shard_map adds nothing over the
    # single-array path (and may strand the array on one device)
    if mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1) <= 1:
        return None
    return mesh


def _mesh_stats(stats_fn, arrays, mesh):
    """Place ``stats_fn`` (pair_stats/cross_stats) per-shard with
    shard_map — batch over ``(data, fsdp)``, everything else replicated —
    and psum the per-shard partial sums. Sums are exact identities under
    this split (each row lands in exactly one shard), so the result
    equals the single-device kernel up to fp32 summation order."""
    from tensorflowonspark_tpu.compute import layout

    axes = layout.BATCH_AXES
    spec = layout.batch_spec(arrays[0].ndim)

    def body(*arrs):
        a, b = stats_fn(*arrs)
        return lax.psum(a, axes), lax.psum(b, axes)

    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * len(arrays),
        out_specs=(
            layout.activation_spec("replicated"),
            layout.activation_spec("replicated"),
        ),
        check_vma=False,
    )
    return fn(*arrays)


def mesh_pair_stats(x: jax.Array, mesh) -> tuple[jax.Array, jax.Array]:
    """:func:`pair_stats` on a batch-sharded multi-device mesh."""
    return _mesh_stats(pair_stats, (x,), mesh)


def mesh_cross_stats(
    dy: jax.Array, x: jax.Array, mesh
) -> tuple[jax.Array, jax.Array]:
    """:func:`cross_stats` on a batch-sharded multi-device mesh."""
    return _mesh_stats(cross_stats, (dy, x), mesh)
