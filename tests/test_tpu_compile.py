"""The main path's Pallas kernels compile for the chip — without one.

The TPU's compiler is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology (nothing runs: a compile that passes is not a chip
run). Interpret-mode tests cannot see what this sees — tiling the chip
refuses, fast-memory overuse — so a few real-width compiles guard every
later PR at no chip time. Whole programs (the 1B train step, the engine's
decode block) take tens of seconds each and stay out of tier-1.

This is the ONE test file that describes a topology, and it does so inside
a fixture: only one process at a time may load the TPU's library, so the
call must not happen while any module is imported (every xdist worker
imports every test file) nor in a child process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensorflowonspark_tpu.ops import bn_kernels
from tensorflowonspark_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile can be written to the persistent cache but not read
    # back without a chip (a warning, then a recompile): keep it off here
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _attention(grad: bool, window=None):
    def fwd(q, k, v, seg=None):
        # positional: custom_vjp functions reject keyword arguments
        return flash_attention(q, k, v, True, None, None, None, window, seg)

    if not grad:
        return fwd

    def loss(q, k, v, seg=None):
        return fwd(q, k, v, seg).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


BF16 = jnp.bfloat16
# (B, S, H, D) of q; kv heads; window; segment ids; fwd+bwd
ATTENTION_CASES = {
    "llama1b_fwd": ((8, 1024, 16, 128), 16, None, False, False),
    "llama1b_fwd_bwd": ((8, 1024, 16, 128), 16, None, False, True),
    "gqa_32_8_fwd_bwd": ((2, 1024, 32, 128), 8, None, False, True),
    "window256_s4096_fwd_bwd": ((1, 4096, 16, 128), 16, 256, False, True),
    "segment_ids_fwd_bwd": ((8, 1024, 16, 128), 16, None, True, True),
}


@pytest.mark.parametrize("case", sorted(ATTENTION_CASES))
def test_flash_attention_compiles_for_v5e(one_chip, case):
    (b, s, h, d), kv_heads, window, segments, grad = ATTENTION_CASES[case]
    shapes = [((b, s, h, d), BF16)] + [((b, s, kv_heads, d), BF16)] * 2
    if segments:
        shapes.append(((b, s), jnp.int32))
    text = _compiled_text(_attention(grad, window), one_chip, *shapes)
    assert "tpu_custom_call" in text


# ResNet-50 b=256 activations viewed as (rows, C): the stem and the widest
@pytest.mark.parametrize("rows,channels", [(256 * 56 * 56, 64), (256 * 7 * 7, 2048)])
@pytest.mark.parametrize("kernel", ["pair_stats", "cross_stats"])
def test_bn_stats_kernels_compile_for_v5e(one_chip, kernel, rows, channels):
    x = ((rows, channels), BF16)
    if kernel == "pair_stats":
        text = _compiled_text(bn_kernels.pair_stats, one_chip, x)
    else:
        text = _compiled_text(bn_kernels.cross_stats, one_chip, x, x)
    assert "tpu_custom_call" in text
