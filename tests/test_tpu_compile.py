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

import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensorflowonspark_tpu.ops import attention as attn_mod
from tensorflowonspark_tpu.ops import bn_kernels
from tensorflowonspark_tpu.ops.decode_attention import (
    decode_attention,
    latent_decode_attention,
    latent_entry_width,
)
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


# -- the train cell's packed attention: (2, 8192, 32/8, 128), window 4096 --


@pytest.fixture(scope="module")
def cell_attention_calls(one_chip):
    """The ``tpu_custom_call`` lines of the train cell's attention, forward
    and backward, compiled with and without segment ids. Called from
    inside a flax module, as the model calls it: that is where the
    kernels get the instruction names the benchmark's patterns look for."""
    import flax.linen as nn

    from tensorflowonspark_tpu.ops.attention import _jitted_attention

    class Attend(nn.Module):
        @nn.compact
        def __call__(self, q, k, v, seg=None):
            return _jitted_attention(
                q, k, v, causal=True, impl="flash", window=4096,
                segment_ids=seg,
            )

    def step(q, k, v, seg=None):
        def loss(q, k, v):
            return Attend().apply({}, q, k, v, seg).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    shapes = [((2, 8192, 32, 128), BF16)] + [((2, 8192, 8, 128), BF16)] * 2

    def calls(*more):
        text = _compiled_text(step, one_chip, *shapes, *more)
        return [
            line.strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
        ]

    return {"packed": calls(((2, 8192), jnp.int32)), "unpacked": calls()}


# the tile table: one int32 vector, the first operand of the call
_PREFETCH_OPERAND = r"operand_layout_constraints=\{s32\[\d+\]\{0\}"


def test_packed_cell_attention_compiles_with_scalar_prefetch(
    cell_attention_calls,
):
    packed = cell_attention_calls["packed"]
    assert len(packed) == 3  # forward, dq, dkv
    assert all(re.search(_PREFETCH_OPERAND, line) for line in packed)


def test_unpacked_cell_attention_has_no_prefetch_operand(cell_attention_calls):
    unpacked = cell_attention_calls["unpacked"]
    assert len(unpacked) == 3
    assert not any("s32[" in line for line in unpacked)


@pytest.mark.parametrize("which", ["fwd", "dq", "dkv"])
def test_flash_roofline_patterns_still_find_the_packed_kernels(
    cell_attention_calls, which
):
    """``flash_roofline_pct.train`` finds the three kernels by instruction
    name and result dtypes (its file is read here, not edited): each
    pattern matches exactly one of the packed calls, as the trace's
    reduction shortens them."""
    from perfbench.trace_reduce import short

    metric = json.loads(
        (
            pathlib.Path(__file__).parents[1]
            / "perfbench/metrics/flash_roofline_pct.train.json"
        ).read_text()
    )
    (pattern,) = [
        k["pattern"] for k in metric["params"]["kernels"] if k["which"] == which
    ]
    names = [short(line) for line in cell_attention_calls["packed"]]
    assert sum(bool(re.search(pattern, n)) for n in names) == 1, names


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


# (rows, heads, kv_heads, d, C, window) of the serve cells' decode steps;
# Laguna's full layers (48 query rows over a plane of 14,336) and windowed
# ones (64 over a ring of exactly its window: no window given)
DECODE_CASES = {
    "mistral7b_16x2560": (16, 32, 8, 128, 2560, 4096),
    "falconh1_48x2048": (48, 20, 4, 128, 2048, None),
    "laguna_plane_48x14336": (48, 48, 8, 128, 14336, None),
    "laguna_ring_48x512": (48, 64, 8, 128, 512, None),
}


def _plane_copies(text: str, rows, C, kv_heads, d) -> list[str]:
    """Instructions whose result is a whole K/V plane made by a copy or a
    transpose: what a layout the kernel and XLA disagree on would cost,
    84 MB a plane and step."""
    plane = rf"= bf16\[{rows},{C},{kv_heads},{d}\]\S* (copy|transpose)\("
    return [line.strip() for line in text.splitlines() if re.search(plane, line)]


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_attention_compiles_for_v5e(one_chip, case):
    """The kernel at the serve cells' shapes, on the cache as stored:
    Mosaic takes the 4-D planes in the layout XLA keeps them in."""
    rows, heads, kv_heads, d, C, window = DECODE_CASES[case]
    plane = ((rows, C, kv_heads, d), BF16)
    text = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n, window=window),
        one_chip, ((rows, heads, d), BF16), plane, plane, ((rows,), jnp.int32),
    )
    assert "tpu_custom_call" in text
    assert not _plane_copies(text, rows, C, kv_heads, d)


# (C, step calls) of SambaY's decode step: the shared plane of 3072
# positions, a ring of 512; 128 rows, 40 query rows over 10 K/V pairs of 128
SAMBAY_PLANES = {"shared_plane": 3072, "ring": 512}


@pytest.mark.parametrize("plane", sorted(SAMBAY_PLANES))
def test_decode_attention_on_a_flat_plane_compiles_for_v5e(one_chip, plane):
    """The kernel on SambaY's flat planes (a position's 10 pairs as rows):
    one call named ``decode_attention`` whose result is ``bf16[128,48,128]``
    (what the benchmark counts a step's calls by), the plane neither
    copied nor transposed in front of it."""
    rows, heads, kv_heads, d, C = 128, 40, 10, 128, SAMBAY_PLANES[plane]
    flat = ((rows, C * kv_heads, d), BF16)
    text = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n, kv_heads=kv_heads),
        one_chip, ((rows, heads, d), BF16), flat, flat, ((rows,), jnp.int32),
    )
    calls = re.findall(r"%decode_attention\S* = bf16\[128,48,128\]\S* custom-call", text)
    assert len(calls) == 1
    copies = rf"= bf16\[{rows},{C * kv_heads},{d}\]\S* (copy|transpose)\("
    assert not [line for line in text.splitlines() if re.search(copies, line)]


def _latent_copies(text: str, rows, C, width) -> list[str]:
    plane = rf"= bf16\[{rows},{C},{width}\]\S* (copy|transpose)\("
    return [line.strip() for line in text.splitlines() if re.search(plane, line)]


@pytest.mark.parametrize("rows", [128, 16])
def test_latent_decode_attention_compiles_for_v5e(one_chip, rows):
    """The latent kernel at the expert cell's shape (128 slots of 3072
    positions, 128 heads over a 576-value entry stored in 640), on the
    plane as stored: the parameter keeps the row-major layout the kernel
    reads (a 576-wide plane would be laid out with its positions minor
    and copied whole in front of every call)."""
    heads, rank, rope, C = 128, 512, 64, 3072
    width = latent_entry_width(rank, rope)
    assert width == 640
    text = _compiled_text(
        lambda a, b, c, n: latent_decode_attention(a, b, c, n, scale=192**-0.5),
        one_chip, ((rows, heads, rank), BF16), ((rows, heads, rope), BF16),
        ((rows, C, width), BF16), ((rows,), jnp.int32),
    )
    assert "tpu_custom_call" in text and "latent_decode_attention" in text
    assert not _latent_copies(text, rows, C, width)


@pytest.mark.parametrize("rows", [128, 16])
def test_kda_step_compiles_for_v5e(one_chip, rows):
    """The delta-rule step kernel at the Solar-Open2 cell's shape (128
    slots, 64 heads of a 128 x 128 float32 state): the state is written
    over itself (the program holds it once) and is neither copied nor
    transposed in front of the call."""
    from tensorflowonspark_tpu.ops.kda import kda_step_pallas

    h, d = 64, 128
    f32 = jnp.float32
    shapes = (
        ((rows, h, d, d), f32), ((rows, h, d), f32), ((rows, h, d), f32),
        ((rows, h, d), f32), ((rows, h, d), f32), ((rows, h), f32),
    )
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    compiled = jax.jit(kda_step_pallas, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_step" in text
    state = rows * h * d * d * 4
    assert compiled.memory_analysis().alias_size_in_bytes == state
    assert not re.findall(
        rf"= f32\[{rows},{h},{d},{d}\]\S* (copy|transpose)\(", text
    )


def test_kda_chunked_compiles_for_v5e_within_the_parents_temporaries(one_chip):
    """The chunked delta rule at the Solar-Open2 cell's prefill (one row
    of 1024 positions, 64 heads of a 128 x 128 float32 state, chunk 32,
    ``valid``) compiles for the chip, and its temporaries stay within the
    134,572,544 bytes that the form before the sub-blocks asked for at
    this shape (compile, PR 36)."""
    from tensorflowonspark_tpu.ops.kda import kda_chunked

    rows, L, h, d = 1, 1024, 64, 128
    f32 = jnp.float32
    shapes = [((rows, L, h, d), f32)] * 4 + [
        ((rows, L, h), f32), ((rows, h, d, d), f32), ((rows, L), jnp.bool_),
    ]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    compiled = jax.jit(
        lambda q, k, v, g, b, s0, valid: kda_chunked(
            q, k, v, g, b, chunk=32, initial_state=s0, valid=valid
        )
    ).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 134_572_544


@pytest.mark.parametrize("rows", [1024, 8192])  # a decode step's, a prefill's
@pytest.mark.parametrize("banks,d,f", [
    (40, 4096, 1280), (40, 1280, 4096),  # Solar-Open2: gate / up, down
    (16, 7680, 2048), (16, 2048, 7680),  # the expert cell's
    (256, 2048, 512), (256, 512, 2048),  # Laguna's: every expert held
])
def test_grouped_matmul_compiles_for_v5e_at_the_tiles_it_chooses(
    one_chip, monkeypatch, rows, banks, d, f
):
    """``gmm_tiling``'s choice for the two expert cells' banks fits the
    fast memory the chip's compiler gives the kernel (it refuses a bank
    tile of 10 MiB, a whole 1280 x 4096 bank, and one of 7.5 MiB)."""
    from tensorflowonspark_tpu.parallel import moe

    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    assert moe._pallas_gmm()
    text = _compiled_text(
        moe.grouped_matmul, one_chip,
        ((rows, d), BF16), ((banks, d, f), BF16), ((banks,), jnp.int32),
    )
    assert "tpu_custom_call" in text and re.search(
        rf"%gmm\S* = bf16\[{rows},{f}\]", text
    )


def test_engine_decode_block_compiles_for_v5e_with_its_option(
    one_chip, monkeypatch
):
    """The decode block of the serving engine, one layer at Mistral-7B
    widths with the benchmark's 16 x 2560 cache, compiled for the chip
    as the engine compiles it there: with the TPU compiler option it
    asks for (an option the installed compiler did not know would fail
    here, not at a replica's start-up), with the whole batch cache
    aliased from input to output, and with the decode-attention kernel
    reading the planes where the scatter left them, no copy between.
    Whether memory-space assignment then leaves the planes in HBM shows
    only at the full depth (PERF.md §6, PR 26); that compile takes
    minutes and stays with ``perfbench/tools/compile_rehearsal.py``."""
    # the branch asks JAX for its backend, which is the CPU here
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig
    from tensorflowonspark_tpu.serving.engine import (
        _BIAS_SLOTS,
        ContinuousBatcher,
    )

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=4096, intermediate_size=14336,
        num_layers=1, num_heads=32, num_kv_heads=8, max_seq_len=2560,
        dtype=BF16, remat=False,
    )
    model = Llama(cfg)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(
        spec,
        jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        ),
    )
    # programs only: no scheduler thread, no state on a device
    eng = ContinuousBatcher.__new__(ContinuousBatcher)
    eng._model, eng._mesh, eng._slots, eng._params = model, None, 16, params
    eng._block_cache = {}
    assert eng._block_compiler_options()  # the weights are on a TPU
    slots, vocab = 16, cfg.vocab_size
    cache = jax.tree.map(spec, eng._cache_shapes(slots))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    compiled = eng._block_fn(2).lower(
        params, cache, arr(i32, slots), arr(i32, slots), arr(f32, slots),
        arr(i32, slots), arr(f32, slots, 3), arr(jnp.uint32, slots),
        arr(f32, slots, 2), arr(f32, slots, vocab),
        arr(i32, slots, _BIAS_SLOTS), arr(f32, slots, _BIAS_SLOTS),
        arr(jnp.bool_, 4),
    ).compile()
    cache_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache)
    )
    # the cache whole (tok, pos and counts ride along, padded to tiles)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _plane_copies(text, slots, cfg.max_seq_len, 8, 128)


@pytest.mark.parametrize("width", [128, 1024, 2048])
def test_engine_prefill_compiles_for_v5e_with_the_flash_kernel(
    one_chip, monkeypatch, width
):
    """The engine's prefill program, one layer at Mistral-7B widths over
    the benchmark's 2560-position cache, compiled for the chip: it is
    handed no cache, so its attention is the flash kernel among the
    prompt's own positions, and no (width, 2560) score array is left in
    the program (PERF.md §6, PR 32)."""
    # the dispatch asks JAX for its backend and its devices: the CPU's
    # eight here, one TPU in the cell
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    from tensorflowonspark_tpu.models.llama import Llama, LlamaConfig
    from tensorflowonspark_tpu.serving.engine import (
        _BIAS_SLOTS,
        ContinuousBatcher,
    )

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=4096, intermediate_size=14336,
        num_layers=1, num_heads=32, num_kv_heads=8, max_seq_len=2560,
        sliding_window=4096, dtype=BF16, remat=False,
    )
    model = Llama(cfg)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        ),
    )
    # the program only: no scheduler thread, no state on a device
    eng = ContinuousBatcher.__new__(ContinuousBatcher)
    eng._model, eng._mesh, eng._prefill_cache = model, None, {}

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    text = eng._prefill_fn(width).lower(
        params, arr(i32, 1, width), arr(i32, 1), arr(f32, 1), arr(i32, 1),
        arr(f32, 1, 3), arr(jnp.uint32, 1), arr(i32, 1, _BIAS_SLOTS),
        arr(f32, 1, _BIAS_SLOTS),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.findall(rf"\[(?:\d+,)*{width},{cfg.max_seq_len}\]", text)


def _phi4_programs(one_chip):
    """The SambaY cell's engine programs at its published widths, slots
    and cache, six layers deep (each kind of layer once, the windowed and
    the Mamba layer twice), compiled for the chip; programs only."""
    from tensorflowonspark_tpu.models.phi4_flash import Phi4Flash, Phi4FlashConfig
    from tensorflowonspark_tpu.serving.engine import ContinuousBatcher

    cfg = Phi4FlashConfig(
        vocab_size=2048, num_layers=6,
        layer_types=("mamba", "swa", "mamba", "full", "gmu", "cross"),
        max_seq_len=3072,
    )
    model = Phi4Flash(cfg)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    ))
    eng = ContinuousBatcher.__new__(ContinuousBatcher)
    eng._model, eng._mesh, eng._slots, eng._params = model, None, 128, params
    eng._block_cache, eng._prefill_cache = {}, {}
    return cfg, eng, spec


def test_sambay_decode_block_compiles_for_v5e(one_chip, monkeypatch):
    """``jit_block`` k=8 at 128 slots: every K/V read is the
    decode-attention kernel (the ring, the full layer's plane, the cross
    layer over it), the planes lie flat (a position's 10 pairs as rows,
    no padding to 16) and none is copied whole."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    from tensorflowonspark_tpu.serving.engine import _BIAS_SLOTS

    cfg, eng, spec = _phi4_programs(one_chip)
    slots, vocab = 128, cfg.vocab_size
    cache = jax.tree.map(spec, eng._cache_shapes(slots))
    assert cache["layer3"]["attn"]["k"].shape == (slots, 3072 * 10, 128)
    assert cache["layer1"]["attn"]["k"].shape == (slots, 512 * 10, 128)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    compiled = eng._block_fn(8).lower(
        eng._params, cache, arr(i32, slots), arr(i32, slots), arr(f32, slots),
        arr(i32, slots), arr(f32, slots, 3), arr(jnp.uint32, slots),
        arr(f32, slots, 2), arr(f32, slots, vocab),
        arr(i32, slots, _BIAS_SLOTS), arr(f32, slots, _BIAS_SLOTS),
        arr(jnp.bool_, 4),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%decode_attention[.0-9]* = ", text)) == 3
    assert not re.findall(r"= bf16\[128,(3072|512),10,128\]", text)
    assert not re.findall(r"= bf16\[128,(30720|5120),128\]\S* copy\(", text)


def test_sambay_prefill_compiles_for_v5e_with_the_scan_kernel(one_chip, monkeypatch):
    """The 1024-wide prefill: the Mamba layers' scan is the Pallas kernel,
    the attention layers the flash kernel among the prompt's own
    positions, the cross layer the decode-attention kernel at the last
    position over the full layer's plane."""
    monkeypatch.setattr(attn_mod, "TREAT_AS_TPU", True)
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    from tensorflowonspark_tpu.serving.engine import _BIAS_SLOTS

    _, eng, _ = _phi4_programs(one_chip)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32, w = jnp.float32, jnp.int32, 1024
    text = eng._prefill_fn(w).lower(
        eng._params, arr(i32, 1, w), arr(i32, 1), arr(f32, 1), arr(i32, 1),
        arr(f32, 1, 3), arr(jnp.uint32, 1), arr(i32, 1, _BIAS_SLOTS),
        arr(f32, 1, _BIAS_SLOTS),
    ).compile().as_text()
    assert len(re.findall(r"%selective_scan[.0-9]* = ", text)) == 2
    assert len(re.findall(r"%decode_attention[.0-9]* = ", text)) == 1
