"""Fused-statistics BatchNorm for bandwidth-bound TPU conv nets.

Why this exists (figures from a profile taken before PR 1 on an earlier
installation; not measured on this one): 48% of the ResNet-50 train step
was BatchNorm statistics reductions (`convert_reduce_fusion`), because the
stats path makes several full passes over the activations: mean and
mean-of-squares forward, then sum(dy) and sum(dy*xhat) backward, each an
HBM read of a (N,H,W,C) tensor. The convolutions themselves are only ~22%
of the step (~76% MXU-efficient) — the stats traffic is the ceiling.

Round-4 finding (profiled A/B on the chip): XLA already merges the sibling
reductions into ~2 fused passes per layer — but runs them at ~20-30% of
HBM streaming rate. So the win looked like *pass rate*, not *pass
structure* (the round-3 custom-VJP re-derivation measured 15.8% MFU vs
flax BN's 16.1%), and `ops/bn_kernels.py` answered with Pallas streaming
kernels for the two stats passes.

Round-5 finding (the kernels' own chip A/B): the Pallas path REGRESSED
in-context — ResNet-50 8.9% vs 16.1%, Inception-v3 13.7% vs 18.2%. The
"slow" reduce fusions were amortized: fused with neighboring elementwise
work over inputs still resident from the producing conv. An opaque
``pallas_call`` severs that, forcing extra materialized activation
round-trips that cost more than the streamed reduce saves. ``impl='auto'``
therefore resolves to the XLA reduces everywhere; the kernels stay for
explicit ``impl='pallas'`` standalone-stats callers:

- forward: ONE kernel pass over x for per-channel (sum, sum_sq) → mean/var
  (fp32 accumulation over the bf16 stream); one fused normalize pass
  (read x, write y) in the model dtype, left to XLA.
- backward: ONE kernel pass over (dy, x) for (sum_dy, sum_dy_x) — xhat is
  never materialized; sum(dy·x̂) = invstd·(sum(dy·x) − mean·sum(dy)) in
  fp32 — and one XLA elementwise pass producing dx.

The statistics are computed exactly once per layer: `bn_train`'s custom
VJP computes them inside the op and returns them alongside the
normalized output, so the module reuses the same values for the
running-average update rather than recomputing and hoping for CSE.

Parity note: the reference delegated BN entirely to TF's library
(SURVEY.md §1 — it has no compute code of its own); this is the rebuild's
TPU-first equivalent of the cuDNN fused-BN kernels TF used on GPUs.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tensorflowonspark_tpu.ops import bn_kernels


def _channel_stats(af: jax.Array, bf: jax.Array, reduce_dims: tuple[int, ...]):
    """XLA-path per-channel (sum_a, sum_b), accumulated in fp32.

    Callers pass fp32 values built from the streamed tensor (convert
    FIRST, then square/multiply — squaring in bf16 loses the low bits
    that E[x²]−E[x]² cancellation needs). Two sibling reductions over
    inputs sharing the same streamed operand: XLA merges them into one
    multi-output reduce fusion. A variadic ``lax.reduce`` would express
    the same thing explicitly; it was avoided for a compile fault of an
    earlier installation that has not been re-tested on this one
    (ROADMAP D8).
    """
    af = af.astype(jnp.float32)
    bf = bf.astype(jnp.float32)
    return jnp.sum(af, axis=reduce_dims), jnp.sum(bf, axis=reduce_dims)


def _reduce_extent(x: jax.Array) -> int:
    n = 1
    for d in x.shape[:-1]:
        n *= d
    return n


def _resolve_impl(impl, x):
    """Resolve ``impl`` against the ambient state at trace time.

    Returns ``'pallas'`` | ``'xla'`` | ``('mesh_pallas', mesh)`` — the
    third is the multi-device route: per-shard Pallas partial sums +
    psum under shard_map (:func:`bn_kernels.stats_mesh` gates it). An
    already-resolved value (tuple, or explicit literal) passes through,
    so the custom-VJP backward re-resolving can never flip routes.
    """
    if isinstance(impl, tuple):
        return impl
    mesh = bn_kernels.stats_mesh(impl, x.shape[0])
    if mesh is not None:
        return ("mesh_pallas", mesh)
    return "pallas" if bn_kernels.use_pallas(impl) else "xla"


def batch_norm_stats(x, impl="auto") -> tuple[jax.Array, jax.Array]:
    """One-pass per-channel (mean, var) over all-but-last dims, fp32."""
    n = _reduce_extent(x)
    resolved = _resolve_impl(impl, x)
    if isinstance(resolved, tuple):
        s, s2 = bn_kernels.mesh_pair_stats(x, resolved[1])
    elif resolved == "pallas":
        s, s2 = bn_kernels.pair_stats(x)
    else:
        xf = x.astype(jnp.float32)
        s, s2 = _channel_stats(xf, xf * xf, tuple(range(x.ndim - 1)))
    mean = s / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    return mean, var


def bn_train(x, gamma, beta, eps, impl="auto"):
    """Train-mode BatchNorm: ``(y, mean, var)`` with exact batch stats.

    One streamed stats pass and one fused normalize pass forward; one
    streamed stats pass and one elementwise pass backward — the custom
    VJP implements the FULL BatchNorm gradient (including the terms from
    the statistics' dependence on ``x``) and pins the pass structure so
    autodiff cannot de-fuse it. The returned ``mean``/``var`` are for the
    running-average update; cotangents flowing into them are IGNORED
    (their contribution to the normalize is already inside the dx
    formula — that is train-mode BN's semantics, not an approximation).

    ``impl='auto'`` is resolved HERE, at forward-trace time, and the
    resolved literal is what the custom-VJP rules see — so a backward
    traced later (e.g. a ``jax.vjp`` callback after ambient state
    changed) can never pair a Pallas forward with an XLA backward or
    vice versa.
    """
    resolved = _resolve_impl(impl, x)
    return _bn_train(x, gamma, beta, eps, resolved)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(x, gamma, beta, eps, impl):
    y, mean, var, _ = _bn_train_fwd_impl(x, gamma, beta, eps, impl)
    return y, mean, var


def _bn_train_fwd_impl(x, gamma, beta, eps, impl):
    mean, var = batch_norm_stats(x, impl)
    invstd = lax.rsqrt(var + eps)
    gamma_f = gamma.astype(jnp.float32)
    # Normalize in the model dtype: scale/shift collapse to one fused
    # multiply-add over the streamed tensor.
    scale = (invstd * gamma_f).astype(x.dtype)
    shift = (beta.astype(jnp.float32) - mean * invstd * gamma_f).astype(x.dtype)
    y = x * scale + shift
    return y, mean, var, invstd


def _bn_train_fwd(x, gamma, beta, eps, impl):
    y, mean, var, invstd = _bn_train_fwd_impl(x, gamma, beta, eps, impl)
    return (y, mean, var), (x, gamma, mean, invstd)


def _bn_train_bwd(eps, impl, res, cts):
    # impl is the literal bn_train resolved at forward-trace time.
    dy, _dmean, _dvar = cts  # stats cotangents ignored — see bn_train.
    x, gamma, mean, invstd = res
    n = _reduce_extent(x)
    if isinstance(impl, tuple) or bn_kernels.use_pallas(impl):
        if isinstance(impl, tuple):
            sum_dy, sum_dy_x = bn_kernels.mesh_cross_stats(dy, x, impl[1])
        else:
            sum_dy, sum_dy_x = bn_kernels.cross_stats(dy, x)
        sum_dy_xhat = invstd * (sum_dy_x - mean * sum_dy)
        xhat = ((x.astype(jnp.float32) - mean) * invstd).astype(x.dtype)
    else:
        # xhat recomputed inline in fp32 register math (the HBM stream is
        # still the bf16 tensors; XLA fuses the converts); one pass reads
        # (dy, x) and yields both sums.
        reduce_dims = tuple(range(x.ndim - 1))
        xhat_f = (x.astype(jnp.float32) - mean) * invstd
        dy_f = dy.astype(jnp.float32)
        sum_dy, sum_dy_xhat = _channel_stats(dy_f, dy_f * xhat_f, reduce_dims)
        xhat = xhat_f.astype(x.dtype)

    gamma_f = gamma.astype(jnp.float32)
    # dx = gamma*invstd * (dy - sum_dy/n - xhat * sum_dy_xhat/n)
    a = (gamma_f * invstd).astype(x.dtype)
    b = (gamma_f * invstd * sum_dy / n).astype(x.dtype)
    c = (gamma_f * invstd * sum_dy_xhat / n).astype(x.dtype)
    dx = dy * a - b - xhat * c
    dgamma = sum_dy_xhat.astype(gamma.dtype)
    dbeta = sum_dy.astype(gamma.dtype)
    return dx, dgamma, dbeta


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def fused_batch_norm(x, gamma, beta, eps, impl: str = "auto"):
    """Batch-normalize with exact batch statistics (train-mode BN).

    Stats in one streamed pass, normalize in one fused elementwise pass;
    gradient via :func:`bn_train`'s custom VJP (one streamed stats pass +
    one elementwise pass).
    """
    y, _, _ = bn_train(x, gamma, beta, eps, impl)
    return y


class FusedBatchNorm(nn.Module):
    """Drop-in for ``nn.BatchNorm`` on the conv-net train path.

    Train (``use_running_average=False``): normalizes with exact batch
    statistics (one stats pass per direction — XLA multi-output reduce
    fusion by default; explicit ``impl='pallas'`` opts into the
    streaming kernels, see the module header) and updates fp32 running stats
    under the standard ``batch_stats`` collection, with ``nn.BatchNorm``'s
    variable names (``mean``/``var``/``scale``/``bias``) and momentum
    convention. The flax auto-name of this class differs from
    ``nn.BatchNorm``'s (``FusedBatchNorm_N`` vs ``BatchNorm_N``), so the
    in-repo conv nets pass an explicit ``name="BatchNorm_N"`` to keep
    their checkpoint trees bit-compatible with the pre-swap era (see
    docs/SWITCHING.md "BatchNorm checkpoint compatibility"); do the same
    in new models if you need drop-in restore of ``nn.BatchNorm``
    checkpoints. Eval: normalizes with the running stats — a pure
    elementwise chain XLA fuses on its own.
    """

    use_running_average: bool | None = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None
    impl: str = "auto"

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None):
        use_avg = nn.merge_param(
            "use_running_average",
            self.use_running_average,
            use_running_average,
        )
        features = x.shape[-1]
        gamma = self.param("scale", nn.initializers.ones, (features,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros, (features,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((features,), jnp.float32)
        )
        dtype = self.dtype or x.dtype
        x = x.astype(dtype)

        if use_avg:
            invstd = lax.rsqrt(ra_var.value + self.epsilon)
            scale = (invstd * gamma).astype(dtype)
            shift = (beta - ra_mean.value * invstd * gamma).astype(dtype)
            return x * scale + shift

        # Stats computed exactly ONCE inside the custom-VJP op: shared by
        # the normalize and the running-average update — explicitly, not
        # via CSE of a recompute.
        y, mean, var = bn_train(x, gamma, beta, self.epsilon, self.impl)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * lax.stop_gradient(mean)
            ra_var.value = m * ra_var.value + (1.0 - m) * lax.stop_gradient(var)
        return y
