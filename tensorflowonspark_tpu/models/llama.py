"""Llama-family decoder (the flagship model for the FSDP baseline).

TPU-first design notes:

- bf16 activations/params with fp32 RMSNorm accumulations and fp32 softmax
  (inside the attention op) — the MXU-friendly mix.
- RoPE applied functionally; no Python control flow under jit.
- Grouped-query attention via the shared
  :func:`tensorflowonspark_tpu.ops.attention.dot_product_attention`
  (Pallas flash kernel on TPU, XLA fallback elsewhere).
- Megatron-style mesh sharding rules in :func:`llama_param_shardings`:
  'fsdp' shards every matrix's non-TP dimension; 'model' (TP) shards
  attention heads and MLP hidden. DP/FSDP is the parity target
  (BASELINE.json Llama-2-7B config); TP rules ship so scaling past FSDP is a
  sharding change, not a rewrite (SURVEY.md §2.3 implication).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from tensorflowonspark_tpu.compute import layout
from tensorflowonspark_tpu.models.decode_cache import (
    init_cache,  # noqa: F401
    starts_sequence,
)
from tensorflowonspark_tpu.ops.attention import dot_product_attention
from tensorflowonspark_tpu.ops.decode_attention import (
    cache_block_k,
    decode_attention,
)
from tensorflowonspark_tpu.ops.lora import (
    LoraTensor,
    MultiLoraTensor,
    lora_apply,
    multi_lora_apply,
)
from tensorflowonspark_tpu.ops.quant import QuantTensor, quantized_dot
from tensorflowonspark_tpu.parallel.context import use_mesh


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency rescaling (hashable, so configs carrying it still
    key jit/lru caches).

    ``kind='llama3'``: wavelengths longer than
    ``original_max_seq_len/low_freq_factor`` divide by ``factor``,
    shorter than ``original_max_seq_len/high_freq_factor`` stay, the
    band between interpolates smoothly — the published Llama-3.1
    long-context recipe. ``kind='linear'``: every frequency divides by
    ``factor`` (position interpolation). ``kind='yarn'`` (YaRN,
    arXiv:2309.00071, as HF's ``_compute_yarn_parameters`` computes it):
    the frequencies that turn more than ``beta_fast`` times over
    ``original_max_seq_len`` positions stay, those that turn fewer than
    ``beta_slow`` times divide by ``factor``, a linear ramp over the
    indices between (the correction range, floored and ceiled); cos and
    sin are multiplied by ``attention_factor`` (None: ``0.1 ln(factor) +
    1``). The ``low_freq_factor`` / ``high_freq_factor`` are llama3's,
    the betas and ``attention_factor`` yarn's.
    """

    kind: str = "llama3"
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_seq_len: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    @property
    def cos_sin_scale(self) -> float:
        """What cos and sin are multiplied by: yarn's attention factor,
        1 for every other kind."""
        if self.kind != "yarn":
            return 1.0
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    rms_norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    remat: bool = True
    # 'full': recompute the whole block in backward (min memory);
    # 'dots': save matmul/einsum outputs, recompute the cheap elementwise
    # ops only (XLA's dots_with_no_batch_dims_saveable — usually the best
    # MFU/memory point when the model fits); ignored when remat=False.
    remat_policy: str = "full"
    # MoE: when num_experts > 0 every block's MLP is a routed expert bank
    # (expert-parallel over the mesh 'expert' axis — parallel/moe.py).
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    # Qwen2-family QKV bias: the q/k/v projections carry bias vectors
    # (o_proj and the MLP stay bias-free, matching the architecture).
    attention_bias: bool = False
    # Sliding-window (Mistral-style local) attention: each query
    # attends only the last `sliding_window` positions. None = full
    # causal attention. Applies to training/prefill (xla + flash — the
    # flash kernel restricts its grids to the window span — and the SP
    # impls: ring shortens its rotation to the owners in reach, ulysses
    # passes the window to each device's local attention) AND cached
    # decode (position-plane-masked reads of the full-length cache).
    sliding_window: int | None = None
    # Rolling KV cache: cache only this many slots (>= sliding_window
    # + write width - 1) instead of max_seq_len, with slot = position %
    # kv_cache_len. Requires sliding_window (full attention needs every
    # position). THE long-context serving lever for windowed models:
    # Mistral-7B at 32k context holds a 4.3 GB/row dense cache vs ~0.5
    # GB rolling at window 4096. None = dense (max_seq_len slots).
    kv_cache_len: int | None = None
    # KV-cache storage: "model" (= dtype, exact) or "int8" (per-token
    # per-head max-abs quantization — halves the cache HBM footprint
    # AND the per-step cache read traffic that bounds long-context
    # decode; dequant folds into the attention einsums, so no bf16 copy
    # of the cache ever exists). Decode-side only; training is
    # unaffected (no cache).
    kv_cache_dtype: str = "model"
    # Keys are multiplied by this before the rotation (a muP factor of
    # models/falcon_h1.py's published config). 1.0 multiplies nothing: a
    # Python branch, not a traced multiply by one.
    key_multiplier: float = 1.0
    # Rotary positions on queries and keys. False: none (NoPE), the
    # order of the keys is all the causal mask tells a query
    # (models/solar_open2.py). A Python branch: true traces what it
    # always traced.
    use_rope: bool = True
    # A gate on the attention's output before ``o_proj``:
    # ``sigmoid(g_proj(x))`` from the layer's input. True: one value an
    # output channel (models/solar_open2.py); "per_head": one value a
    # query head, over its head_dim outputs (models/laguna.py). False
    # makes no ``g_proj`` and traces nothing.
    attention_output_gate: bool | str = False
    # Rotate only the first ``rotary_dim`` channels of each query and key
    # head (a partial rotary factor; models/laguna.py's full layers). None
    # rotates the whole head and traces what it always traced.
    rotary_dim: int | None = None
    # Differential attention (Diff Transformer, arXiv:2410.05258) with
    # this lambda_init: each head is two softmax maps over the same keys,
    # the second taken away at a learned weight, then normed
    # (``differential_queries`` / ``Attention._differential``;
    # models/phi4_flash.py). None: ordinary attention, a Python branch
    # that traces nothing.
    differential_lambda_init: float | None = None
    # The K/V planes stored (rows, positions x kv heads, head_dim), a
    # position's heads as rows, in place of (rows, positions, kv heads,
    # head_dim): the TPU tiles a plane's two minor dimensions, and a head
    # count that is no multiple of 8 (models/phi4_flash.py's 10 pairs)
    # would pad to the next. False stores and traces what it always did.
    kv_heads_in_rows: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama_1b(**overrides) -> "LlamaConfig":
        """The single-chip benchmark config (953M params)."""
        base = dict(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_layers=16,
            num_heads=16,
            num_kv_heads=16,
            max_seq_len=1024,
            dtype=jnp.bfloat16,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def mistral_7b(**overrides) -> "LlamaConfig":
        """Mistral-7B-v0.1: Llama layout + GQA + sliding-window 4096
        (import real weights with ``tools/import_hf_llama`` — the
        converter accepts ``model_type: mistral``)."""
        base = dict(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            # matches the checkpoint's max_position_embeddings (the
            # importer produces the same value), NOT the 4096 window —
            # context runs far past the window by design
            max_seq_len=32768,
            rope_theta=10000.0,
            sliding_window=4096,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def llama3_8b(**overrides) -> "LlamaConfig":
        """Llama-3.1-8B: GQA 32/8, 128k vocab, llama3 RoPE scaling
        (the importer maps HF rope_scaling type 'llama3' to the same
        :class:`RopeScaling`)."""
        base = dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            max_seq_len=131072,
            rope_theta=500000.0,
            rope_scaling=RopeScaling(
                kind="llama3",
                factor=8.0,
                low_freq_factor=1.0,
                high_freq_factor=4.0,
                original_max_seq_len=8192,
            ),
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def qwen2_7b(**overrides) -> "LlamaConfig":
        """Qwen2-7B: Llama layout + QKV bias + GQA, 1M rope theta
        (import real weights with ``tools/import_hf_llama`` — the
        converter accepts ``model_type: qwen2``)."""
        base = dict(
            vocab_size=152064,
            hidden_size=3584,
            intermediate_size=18944,
            num_layers=28,
            num_heads=28,
            num_kv_heads=4,
            max_seq_len=32768,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-6,
            attention_bias=True,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Test-size config (also used by __graft_entry__ dry runs)."""
        base = dict(
            vocab_size=256,
            hidden_size=128,
            intermediate_size=256,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=128,
        )
        base.update(overrides)
        return LlamaConfig(**base)


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale).astype(self.dtype)


def _scaled_rope_freqs(
    d: int, theta: float, scaling: "RopeScaling | None"
) -> jax.Array:
    """Base (or rescaled) inverse frequencies for head dim ``d``."""
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if scaling is None:
        return freqs
    if scaling.kind == "linear":
        return freqs / scaling.factor
    if scaling.kind == "yarn":
        return _yarn_freqs(freqs, d, theta, scaling)
    if scaling.kind != "llama3":
        raise ValueError(f"unknown rope_scaling kind {scaling.kind!r}")
    # Llama-3.1 recipe: long wavelengths compress by `factor`, short
    # ones stay, the band between interpolates (matches the HF
    # implementation — logit-tested in tests/test_hf_import.py)
    orig = float(scaling.original_max_seq_len)
    low_wavelen = orig / scaling.low_freq_factor
    high_wavelen = orig / scaling.high_freq_factor
    wavelen = 2.0 * jnp.pi / freqs
    smooth = (orig / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor
    )
    interp = (1.0 - smooth) * freqs / scaling.factor + smooth * freqs
    out = jnp.where(wavelen > low_wavelen, freqs / scaling.factor, freqs)
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return jnp.where(mid, interp, out)


def _yarn_freqs(freqs, d: int, theta: float, scaling: "RopeScaling"):
    """YaRN's blend of ``freqs`` (extrapolated) and ``freqs / factor``
    (interpolated) over a ramp between the correction range's ends."""
    orig = float(scaling.original_max_seq_len)

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    # ramp 0: the frequency kept (extrapolated); 1: divided by the factor
    return freqs / scaling.factor * ramp + freqs * (1 - ramp)


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    scaling: "RopeScaling | None" = None,
    rotary_dim: int | None = None,
) -> jax.Array:
    """Rotary embedding; x (B, S, H, D), positions (B, S). ``scaling``
    rescales the frequencies (``RopeScaling``: ``llama3``, ``linear`` or
    ``yarn``, whose attention factor multiplies cos and sin).
    ``rotary_dim`` (the partial rotary fraction times D): only the first
    ``rotary_dim`` channels of a head rotate, split in halves as HF's
    ``rotate_half`` splits them, with the frequencies of a head of that
    width; the others pass through. None rotates the whole head."""
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim != d:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta, scaling),
             x[..., rotary_dim:]], axis=-1,
        )
    freqs = _scaled_rope_freqs(d, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class QDense(nn.Module):
    """Dense (bias-free by default) that also accepts int8
    ``QuantTensor`` kernels.

    With a regular array kernel this is exactly ``nn.Dense(use_bias=
    False, dtype=...)``; with a quantized kernel (``ops/quant.py``,
    e.g. a tree from ``quantize_tree``) the dot runs against the int8
    weight with the per-channel scales folded into the fp32 accumulator
    — weights stay int8 in HBM through the whole decode, which is the
    point (decode is weight-bandwidth-bound). A ``LoraTensor`` kernel
    (``ops/lora.py:add_lora``) runs base + low-rank adapter with the
    base stop-gradiented — the parameter-efficient fine-tune path.
    ``use_bias=True`` adds a bias vector AFTER whichever kernel path
    ran (Qwen2-family QKV projections; the bias is tiny and composes
    with quant/LoRA kernels untouched)."""

    features: int
    dtype: jnp.dtype
    use_bias: bool = False

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        kernel = self.param(
            "kernel",
            nn.initializers.normal(0.02),
            (jnp.shape(x)[-1], self.features),
        )
        x = x.astype(self.dtype)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.features,)
            )
            apply = lambda y: y + bias.astype(y.dtype)  # noqa: E731
        else:
            apply = lambda y: y  # noqa: E731
        if isinstance(kernel, QuantTensor):
            return apply(quantized_dot(x, kernel))
        if isinstance(kernel, LoraTensor):
            return apply(lora_apply(x, kernel))
        if isinstance(kernel, MultiLoraTensor):
            # Per-row adapter routing (the multi-tenant serving path);
            # ids default to slot 0, the bank's zero adapter == base.
            if adapter_ids is None:
                adapter_ids = jnp.zeros((jnp.shape(x)[0],), jnp.int32)
            return apply(multi_lora_apply(x, kernel, adapter_ids))
        return apply(x @ kernel.astype(self.dtype))


class Attention(nn.Module):
    """Grouped-query attention with rotary positions (``cfg.use_rope``,
    over the first ``cfg.rotary_dim`` channels of a head where that is
    set) and, in ``decode=True``, the static-shape KV cache; where
    ``cfg.attention_output_gate`` is set, gated by a projection of its
    input before ``o_proj``, a value an output channel or a head. ``cfg`` is a
    :class:`LlamaConfig` or any config that carries the attributes read
    here (``models/falcon_h1.py`` passes its own, whose ``head_dim`` is
    not ``hidden_size / num_heads``).

    Where ``cfg.differential_lambda_init`` is set the layer is
    differential (:func:`differential_queries`): ``num_heads`` counts
    query rows, two a differential head, and ``num_kv_heads`` /
    ``head_dim`` the K/V pairs as stored, each two published heads side by
    side; the query projection is half as wide.

    ``return_kv`` also returns the K/V that another layer may attend:
    those in hand without ``decode``, the cache's planes after this
    call's write with it. ``shared_kv`` is such a pair: the layer then
    projects queries only, writes no cache and attends the pair (causal
    in hand; in ``decode``, one position a row over the dense planes up
    to its position: :func:`attend_plane`)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, x, positions, segment_ids=None, decode=False, padded=False,
        adapter_ids=None, valid=None, shared_kv=None, return_kv=False,
    ):
        cfg = self.cfg
        dense = lambda feats, name, b=False: QDense(  # noqa: E731
            feats, cfg.dtype, use_bias=b, name=name
        )
        ab = cfg.attention_bias
        diff = cfg.differential_lambda_init is not None
        q = dense(cfg.num_heads * cfg.head_dim // (2 if diff else 1),
                  "q_proj", ab)(x, adapter_ids)
        if shared_kv is None:
            k = dense(cfg.num_kv_heads * cfg.head_dim, "k_proj", ab)(
                x, adapter_ids
            )
            v = dense(cfg.num_kv_heads * cfg.head_dim, "v_proj", ab)(
                x, adapter_ids
            )
        b, s, _ = x.shape
        if diff:
            q = differential_queries(q.reshape(b, s, cfg.num_heads, -1))
        else:
            q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
        if shared_kv is None:
            k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
        else:
            k, v = shared_kv
        # shared keys come as the layer that made them left them
        if cfg.key_multiplier != 1.0 and shared_kv is None:
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling, cfg.rotary_dim)
            if shared_kv is None:
                k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling, cfg.rotary_dim)
        if shared_kv is not None and decode:
            if s != 1:
                raise ValueError(
                    f"a layer that reads another's cache takes one position "
                    f"a row, got {s}"
                )
            out = attend_plane(q[:, 0], k, v, positions[:, 0] + 1, cfg)[:, None]
        elif decode:
            if segment_ids is not None and padded:
                raise ValueError(
                    "segment_ids with padded=True is unsupported: padded "
                    "decode writes each row's cache at its own positions "
                    "(mixed-length unpadded prompts), which conflicts "
                    "with packed rows' global slot indexing"
                )
            out = self._cached_attention(q, k, v, positions, padded,
                                         segment_ids, valid)
            if return_kv:
                k = self.get_variable("cache", "k")
                v = self.get_variable("cache", "v")
        else:
            out = dot_product_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                impl=cfg.attention_impl, window=cfg.sliding_window,
            )
        if diff:
            out = self._differential(out)
        else:
            out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        if cfg.attention_output_gate:
            per_head = cfg.attention_output_gate == "per_head"
            with jax.named_scope("attn.gate"):
                gate = dense(
                    cfg.num_heads * (1 if per_head else cfg.head_dim), "g_proj"
                )(x, adapter_ids)
                gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
                if per_head:
                    # a head's value over its head_dim outputs
                    gate = jnp.repeat(gate, cfg.head_dim, axis=-1)
                out = out * gate
        out = dense(cfg.hidden_size, "o_proj")(out, adapter_ids)
        return (out, (k, v)) if return_kv else out

    def _differential(self, out):
        """The two maps of each differential head made one: ``out``
        (b, s, 2h, 2d) holds, for head ``i``, rows ``2i`` (``q1`` against
        ``k1``) and ``2i + 1`` (``q2`` against ``k2``), each over ``[v1 |
        v2]``. ``a = a1 - lambda a2``, ``lambda = exp(lq1 . lk1) - exp(lq2
        . lk2) + lambda_init``, then RMSNorm over the head's 2d values and
        ``1 - lambda_init``."""
        cfg = self.cfg
        b, s, rows, width = out.shape
        lam0 = cfg.differential_lambda_init
        f32 = jnp.float32
        with jax.named_scope("sambay.diff"):
            lv = {
                n: self.param(n, nn.initializers.normal(0.1), (width // 2,))
                for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
            }
            dot = lambda a, c: jnp.sum(  # noqa: E731
                lv[a].astype(f32) * lv[c].astype(f32)
            )
            lam = (
                jnp.exp(dot("lambda_q1", "lambda_k1"))
                - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0
            )
            pair = out.astype(f32).reshape(b, s, rows // 2, 2, width)
            a = pair[:, :, :, 0] - lam * pair[:, :, :, 1]
            scale = self.param("subln", nn.initializers.ones, (width,))
            a = a * jax.lax.rsqrt(
                jnp.mean(a * a, axis=-1, keepdims=True) + cfg.rms_norm_eps
            ) * (scale.astype(f32) * (1.0 - lam0))
            return a.reshape(b, s, rows // 2 * width).astype(cfg.dtype)

    def _cached_attention(
        self, q, k, v, positions, padded=False, segment_ids=None,
        valid=None,
    ):
        """Autoregressive attention against a static-shape KV cache.

        The cache spans ``max_seq_len``. With uniform rows (``padded=
        False``) new K/V land at the scalar running write index
        (``lax.dynamic_update_slice``, so one jit covers prefill and
        every decode step); with ``padded=True`` each row writes at ITS
        OWN positions (a per-row scatter — the right-padded mixed-length
        prompt case, where row r's next slot is its true length). Either
        way the cache slot of a token is its ROW index (== its RoPE
        position for unpacked rows), so the slot-index query mask below
        excludes both unwritten slots and the right-padding garbage a
        padded prefill writes past each row's true length (those slots
        are only ever attended after being overwritten by that row's
        real decode tokens).

        Packed rows (``segment_ids`` given): each slot also records its
        token's segment id in the cache, and queries additionally mask
        by id EQUALITY — cross-document reads are structurally
        impossible, which is what makes packed prefill/scoring sound.
        RoPE ``positions`` restart per document and therefore DIVERGE
        from slot indices; the slot mask uses the running write index,
        never ``positions``. Ids must be unique per document within a
        row (``packed_loss_mask`` canonicalizes). Unpacked callers
        store zeros everywhere, making the id-equality term vacuous —
        one code path, one compiled program.

        ``valid`` (b, s) bool, on the ``padded=True`` path: a position
        marked false writes nothing (its scatter index is moved out of
        range and dropped) and its output is don't-care. ``Llama`` never
        passes it: the K/V it writes for padding are masked by slot, and
        an overlap it recomputes is recomputed identically. A block whose
        hidden states are wrong at invalid positions (a recurrence that
        skipped them: ``models/falcon_h1.py``) must not let them
        overwrite rows that are already right.

        What is read depends on whether the call was handed a cache
        (``decode_cache.starts_sequence``, asked before the variables
        are made), never on a config field:

        - A call that creates its cache starts its sequence. The cache
          is written as above, for the steps and chunks that follow, but
          nothing is read from it: the keys that exist are the ``k``,
          ``v`` in hand, and the output is ``dot_product_attention``
          among the call's own positions, causal, under the window and
          the ids (on one TPU at kernel shapes the flash kernel, per
          shard under a batch / head mesh, else the plain einsum over
          ``s`` keys). No (s, max_seq_len) logits exist. Sound because
          slot order is sequence order there: uniform rows write at
          ``idx`` = 0 onward, and every padded caller that creates a
          cache passes ``positions = arange(s)`` (``generate``,
          ``models/speculative.py``, the engine's prefill program), so
          "slot <= the query's slot" is "causal by index", and the
          window's position distance is the index distance (inside one
          packed document too). Right-padding and ``valid``-dropped
          positions lie after a row's real tokens, so no real query
          sees them; their own outputs are don't-care. The int8 and the
          rolling cache store what later calls read; this call attends
          the unrounded K/V. A padded caller that creates a cache must
          keep that order: positions other than ``arange(s)`` belong to
          a call that continues a cache.
        - A call that was handed a cache keeps everything below. Decode
          is HBM-bandwidth-bound: a padded step of one position against
          the dense model-dtype cache on a single TPU goes to
          ``ops/decode_attention.py``, which reads only the blocks a
          row has written, as does the same step against a ring of
          exactly its window; every other continuing caller (chunks,
          prefix resumes, speculative verification, uniform and packed
          rows, a ring longer than its window, the int8 cache, a mesh,
          the CPU) takes the einsum over the whole cache.
        """
        cfg = self.cfg
        b, s = q.shape[:2]
        fresh = starts_sequence(self, "k")  # before the variables exist
        C = cfg.kv_cache_len or cfg.max_seq_len
        rolling = C < cfg.max_seq_len
        if rolling:
            if cfg.sliding_window is None:
                raise ValueError(
                    f"kv_cache_len={C} < max_seq_len needs sliding_window "
                    "(full attention reads every position)"
                )
            if segment_ids is not None:
                # Packed rows restart positions per document, so
                # position % C COLLIDES across documents (doc2's slot 0
                # overwrites doc1's) — silently wrong, so refuse.
                raise ValueError(
                    "segment_ids (packed rows) are unsupported with a "
                    "rolling kv_cache_len: per-document positions "
                    "collide under slot = position % C"
                )
            if not fresh and C < cfg.sliding_window + s - 1:
                # a write of s positions may not wrap onto slots that
                # queries in the SAME call still attend (a call that
                # starts its sequence attends the K/V in hand, not the
                # ring: it may be wider than the ring, and stores the
                # last C of its positions)
                raise ValueError(
                    f"kv_cache_len={C} must be >= sliding_window "
                    f"({cfg.sliding_window}) + write width ({s}) - 1; "
                    "prefill in smaller chunks (the engine's "
                    "prefill_chunk) or grow the cache"
                )
        int8_kv = cfg.kv_cache_dtype == "int8"
        kv_store = jnp.int8 if int8_kv else cfg.dtype
        kvh, flat = cfg.num_kv_heads, cfg.kv_heads_in_rows
        if flat and int8_kv:
            raise ValueError("kv_heads_in_rows is unsupported with an int8 cache")
        plane = (b, C * kvh, cfg.head_dim) if flat else (b, C, kvh, cfg.head_dim)
        ck = self.variable("cache", "k", jnp.zeros, plane, kv_store)
        cv = self.variable("cache", "v", jnp.zeros, plane, kv_store)
        if int8_kv:
            # Per-token per-head max-abs scales. fp32: 4 bytes per
            # head-token next to head_dim int8 bytes (~3% at d=128).
            cks = self.variable(
                "cache", "k_scale", jnp.zeros,
                (b, C, cfg.num_kv_heads), jnp.float32,
            )
            cvs = self.variable(
                "cache", "v_scale", jnp.zeros,
                (b, C, cfg.num_kv_heads), jnp.float32,
            )
        cs = self.variable(
            "cache", "seg", jnp.zeros, (b, C), jnp.int32
        )
        if cfg.sliding_window is not None:
            # Each slot's RoPE position: the window masks by POSITION
            # distance, not slot distance — for packed rows continuing
            # an earlier document, the two diverge (other documents'
            # tokens occupy the slots between). Rolling caches init to
            # -1: slot 0's "position 0" would otherwise be
            # indistinguishable from never-written for early queries.
            # NOTE for cache consumers that build fresh rows outside
            # flax (the serving engine): this is the ONE cache leaf
            # whose init is non-zero under rolling — see
            # decode_cache.init_cache().
            cp = self.variable(
                "cache", "pos",
                lambda: jnp.full((b, C), -1 if rolling else 0, jnp.int32),
            )
        ci = self.variable(
            "cache", "idx", lambda: jnp.zeros((), jnp.int32)
        )
        cur = ci.value
        seg = (
            jnp.zeros((b, s), jnp.int32)
            if segment_ids is None
            else segment_ids.astype(jnp.int32)
        )

        def store(x):
            """What lands in the cache for new K/V rows: the model-dtype
            values, or (int8, scale) with symmetric max-abs rounding."""
            if not int8_kv:
                return x.astype(cfg.dtype), None
            xf = x.astype(jnp.float32)
            scale = jnp.maximum(
                jnp.max(jnp.abs(xf), axis=-1), 1e-8
            ) * (1.0 / 127.0)
            q8 = jnp.clip(
                jnp.round(xf / scale[..., None]), -127, 127
            ).astype(jnp.int8)
            return q8, scale

        k_new, ks_new = store(k)
        v_new, vs_new = store(v)

        def as_rows(new):
            """New K/V (b, s, heads, d) as the plane holds them: a
            position's heads as its rows where the plane is flat."""
            return new.reshape(b, s * kvh, -1) if flat else new

        def put(x, rows, at, new, **drop):
            """``new`` (b, s, heads, d) written at slots ``at`` (b, s)."""
            if flat:
                at = (at[..., None] * kvh + jnp.arange(kvh)).reshape(b, s * kvh)
            return x.at[rows, at].set(as_rows(new), **drop)

        def heads(x):
            """A plane as (b, C, kv heads, d)."""
            return x.reshape(b, C, kvh, -1) if flat else x
        if rolling:
            # slot = position % C for BOTH padded and uniform rows: the
            # mask below is purely positional (via the pos plane), so
            # the write-index bookkeeping of the dense branches is
            # unnecessary here
            rows = jnp.arange(b)[:, None]
            slots = positions % C
            drop = {}
            if s > C:
                # Wider than the ring (a call that starts its sequence):
                # two positions share a slot, so only each row's last C
                # valid positions are written, the others dropped.
                real = positions if valid is None else jnp.where(
                    valid, positions, -1)
                keep = positions > jnp.max(real, axis=1, keepdims=True) - C
                if valid is not None:
                    keep = keep & valid
                slots, drop = jnp.where(keep, slots, C), {"mode": "drop"}
            ck.value = put(ck.value, rows, slots, k_new, **drop)
            cv.value = put(cv.value, rows, slots, v_new, **drop)
            if int8_kv:
                cks.value = cks.value.at[rows, slots].set(ks_new, **drop)
                cvs.value = cvs.value.at[rows, slots].set(vs_new, **drop)
            cs.value = cs.value.at[rows, slots].set(seg, **drop)
            cp.value = cp.value.at[rows, slots].set(positions, **drop)
            slot_q = None  # unused: rolling masks by position only
        elif padded:
            rows = jnp.arange(b)[:, None]
            at = positions
            if valid is not None:
                at = jnp.where(valid, positions, C)  # out of range: dropped
            ck.value = put(ck.value, rows, at, k_new, mode="drop")
            cv.value = put(cv.value, rows, at, v_new, mode="drop")
            if int8_kv:
                cks.value = cks.value.at[rows, at].set(ks_new, mode="drop")
                cvs.value = cvs.value.at[rows, at].set(vs_new, mode="drop")
            if cfg.sliding_window is not None:
                cp.value = cp.value.at[rows, at].set(positions, mode="drop")
            # positions ARE the slots here (unpacked rows only; the
            # packed+padded combination is rejected in __call__)
            slot_q = positions
        else:
            at = (0, cur * kvh, 0) if flat else (0, cur, 0, 0)
            ck.value = jax.lax.dynamic_update_slice(ck.value, as_rows(k_new), at)
            cv.value = jax.lax.dynamic_update_slice(cv.value, as_rows(v_new), at)
            if int8_kv:
                cks.value = jax.lax.dynamic_update_slice(
                    cks.value, ks_new, (0, cur, 0)
                )
                cvs.value = jax.lax.dynamic_update_slice(
                    cvs.value, vs_new, (0, cur, 0)
                )
            cs.value = jax.lax.dynamic_update_slice(cs.value, seg, (0, cur))
            if cfg.sliding_window is not None:
                cp.value = jax.lax.dynamic_update_slice(
                    cp.value, positions.astype(jnp.int32), (0, cur)
                )
            slot_q = jnp.broadcast_to(
                (cur + jnp.arange(s, dtype=jnp.int32))[None, :], (b, s)
            )
        ci.value = cur + s
        if fresh:
            # The cache is new: its only keys are this call's, in slot
            # order (see the docstring), so attend among them and read
            # nothing back.
            return dot_product_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                impl=cfg.attention_impl, window=cfg.sliding_window,
            )
        if padded and s == 1 and cache_block_k(cfg) is not None:
            # One new position a row against the dense cache, on one
            # TPU: the kernel is told each row's written length (a slot
            # is its position here) and fetches no block past it, nor
            # before the window. The id plane is not read: ``padded``
            # with ids was refused in __call__. A ring of exactly its
            # window: its first slots up to the row's length, all of
            # them once full (each then holds one of the window's
            # positions).
            length = positions[:, 0] + 1
            out = decode_attention(
                q[:, 0], ck.value, cv.value,
                jnp.minimum(length, C) if rolling else length,
                window=None if rolling else cfg.sliding_window,
                **({"kv_heads": kvh} if flat else {}),
            )
            return out[:, None]
        # Grouped einsum against the un-repeated cache: materializing a
        # jnp.repeat of (b, max_seq_len, heads, d) K/V — plus an fp32 copy
        # — per layer per step would multiply exactly the HBM traffic that
        # bounds decode. Only the (b, h, q, k) logits live in fp32.
        #
        # int8 path: the HBM stream stays int8 (the astype below fuses
        # into the einsum as an operand producer); the K scale factors
        # OUT of the head_dim contraction and multiplies the fp32
        # logits per key slot, and the V scale folds into the fp32
        # probs before they narrow — dequantized K/V never exist as
        # arrays.
        rep = cfg.num_heads // cfg.num_kv_heads
        qg = q.reshape(b, s, cfg.num_kv_heads, rep, cfg.head_dim)
        logits = (
            jnp.einsum(
                "bqhrd,bkhd->bhrqk",
                qg,
                heads(ck.value).astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
            * cfg.head_dim**-0.5
        )
        if int8_kv:
            # (b, S, h) -> (b, h, 1, 1, S) against logits (b, h, r, q, S)
            logits = logits * cks.value.transpose(0, 2, 1)[:, :, None, None, :]
        if rolling:
            # Purely positional masking: a slot is attended iff its
            # recorded position is real (>= 0; stale slots were
            # overwritten, and their OLD positions are <= q - C <= q - W
            # so the window term also kills any that survived), causal
            # (<= q), and within the window (> q - W).
            kplane = cp.value[:, None, None, None, :]
            qcol = positions[:, None, None, :, None]
            mask = (
                (kplane >= 0)
                & (kplane <= qcol)
                & (kplane > qcol - cfg.sliding_window)
            )
            mask = mask & (
                cs.value[:, None, None, None, :]
                == seg[:, None, None, :, None]
            )
        else:
            key_pos = jnp.arange(C)
            mask = (
                key_pos[None, None, None, None, :]
                <= slot_q[:, None, None, :, None]
            )
            mask = mask & (
                cs.value[:, None, None, None, :]
                == seg[:, None, None, :, None]
            )
            if cfg.sliding_window is not None:
                # sliding window by RoPE-position distance (slots
                # already bounded above by slot_q): attend only the
                # last W positions
                mask = mask & (
                    cp.value[:, None, None, None, :]
                    > positions[:, None, None, :, None]
                    - cfg.sliding_window
                )
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if int8_kv:
            probs = probs * cvs.value.transpose(0, 2, 1)[:, :, None, None, :]
        probs = probs.astype(cfg.dtype)
        out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, heads(cv.value).astype(cfg.dtype))
        return out.reshape(b, s, cfg.num_heads, cfg.head_dim)


def differential_queries(q):
    """Differential heads' queries (b, s, 2h, d), rows ``2i``, ``2i + 1``
    being ``q1``, ``q2`` of head ``i``, as rows of width 2d against K/V
    stored in pairs ``[k1 | k2]``, ``[v1 | v2]``: row ``2i`` is ``[q1, 0]``,
    row ``2i + 1`` ``[0, q2]``, so that one attention over the pairs makes
    both maps of every head, reading each K/V block once. Scaled by
    ``sqrt(2)``: the attention's ``1 / sqrt(2d)`` is then the published
    ``1 / sqrt(d)``."""
    b, s, rows, d = q.shape
    lane = jnp.arange(2 * d)[None, :] < d
    first = jnp.arange(rows)[:, None] % 2 == 0
    mask = jnp.where(lane == first, 2.0**0.5, 0.0).astype(jnp.float32)
    wide = jnp.concatenate([q, q], axis=-1).astype(jnp.float32)
    return (wide * mask).astype(q.dtype)


def attend_plane(q, ck, cv, lengths, cfg):
    """One query position a row, ``q`` (b, heads, d), over a dense K/V
    plane (b, C, kv_heads, d) whose slot is its position (flat, (b, C x
    kv_heads, d), where ``cfg.kv_heads_in_rows``), the first ``lengths``
    (b,) of them: ``decode_attention`` on one TPU (the blocks
    a row has written), else the grouped einsum over the plane. What a
    layer that reads another layer's cache attends with
    (``Attention(shared_kv=...)``)."""
    kvh = cfg.num_kv_heads
    flat = cfg.kv_heads_in_rows
    if cache_block_k(cfg) is not None:
        return decode_attention(q, ck, cv, lengths, **({"kv_heads": kvh} if flat else {}))
    b, heads, d = q.shape
    if flat:
        ck, cv = (x.reshape(b, -1, kvh, d) for x in (ck, cv))
    qg = q.reshape(b, kvh, heads // kvh, d)
    logits = jnp.einsum(
        "bhrd,bkhd->bhrk", qg, ck.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ) * d**-0.5
    written = jnp.arange(ck.shape[1])[None, :] < lengths[:, None]
    logits = jnp.where(written[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
    out = jnp.einsum("bhrk,bkhd->bhrd", probs, cv.astype(cfg.dtype))
    return out.reshape(b, heads, d)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        cfg = self.cfg
        dense = lambda feats, name: QDense(  # noqa: E731
            feats, cfg.dtype, name=name
        )
        gate = dense(cfg.intermediate_size, "gate_proj")(x, adapter_ids)
        up = dense(cfg.intermediate_size, "up_proj")(x, adapter_ids)
        return dense(cfg.hidden_size, "down_proj")(
            nn.silu(gate) * up, adapter_ids
        )


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, x, positions, segment_ids=None, decode=False, padded=False,
        adapter_ids=None,
    ):
        cfg = self.cfg
        h = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x),
            positions,
            segment_ids,
            decode,
            padded,
            adapter_ids,
        )
        if cfg.num_experts > 0:
            from tensorflowonspark_tpu.parallel.moe import MoEConfig, MoEMLP

            mlp = MoEMLP(
                MoEConfig(
                    num_experts=cfg.num_experts,
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    hidden_size=cfg.hidden_size,
                    intermediate_size=cfg.intermediate_size,
                    dtype=cfg.dtype,
                ),
                name="mlp",
            )
        else:
            mlp = MLP(cfg, name="mlp")
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(h)
        if cfg.num_experts > 0:
            return h + mlp(normed)  # MoE routes by token, not adapter
        return h + mlp(normed, adapter_ids)


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        positions=None,
        segment_ids=None,
        decode=False,
        return_hidden=False,
        padded=False,
        adapter_ids=None,
        valid=None,
    ):
        """tokens (B, S) int32 -> logits (B, S, vocab).

        ``decode=True`` runs against per-layer KV caches (apply with
        ``mutable=["cache"]``; see :func:`generate`): ``positions`` must
        then be the absolute positions of ``tokens`` in the sequence.
        ``padded=True`` (decode only) makes each row write the cache at
        its own positions — the right-padded mixed-length prompt case
        (:func:`generate` with ``prompt_lengths``).

        ``segment_ids`` (B, S) marks packed documents: attention is
        masked by id EQUALITY and RoPE positions restart at adjacency
        boundaries, so ids must be unique per document within a row
        (:func:`llama_loss_fn` canonicalizes adjacency runs for you).
        Works with ``decode=True`` too — the KV cache records each
        slot's segment id and masks reads by it, so packed prefill and
        scoring (and continuing a chosen document by passing its id
        with the new tokens' positions) never attend across documents.
        Only the ``padded=True`` combination is rejected: per-row
        scatter slots conflict with packed rows' global slot indexing.

        ``adapter_ids`` (B,) int32 routes each row through its slot of
        any ``MultiLoraTensor`` adapter banks in the params
        (``ops/lora.py:multi_lora_bank`` — multi-tenant serving); None
        routes every row to slot 0, the bank's exact-base zero adapter.
        Ignored when the params hold no banks.

        ``return_hidden=True`` returns ``(hidden, lm_head)`` instead of
        logits — the final-norm hidden states (B, S, H) and the untied
        head weight — so callers can run the vocab projection in chunks
        (:func:`llama_loss_fn` with ``logit_chunk``) without ever
        materializing the (B, S, vocab) fp32 logits.

        ``valid`` (B, S) bool marks the positions that are real tokens
        of this call. Accepted and ignored: K/V written for padding are
        masked by slot when they are read. The serving engine passes it
        to every model; one that carries recurrent state needs it.
        """
        del valid
        cfg = self.cfg
        if positions is None:
            idx = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
            if segment_ids is None:
                positions = idx
            else:
                # Packed sequences: RoPE positions restart at each
                # document boundary. A position's document start is the
                # running max of boundary indices up to it.
                new_doc = jnp.concatenate(
                    [
                        jnp.ones_like(segment_ids[:, :1], dtype=bool),
                        segment_ids[:, 1:] != segment_ids[:, :-1],
                    ],
                    axis=1,
                )
                doc_start = jax.lax.cummax(
                    jnp.where(new_doc, idx, 0), axis=1
                )
                positions = idx - doc_start
        embed = self.param(
            "embed",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size),
        )
        x = embed_rows(embed, tokens).astype(cfg.dtype)
        if cfg.remat and not decode:
            # Rematerialize each layer's activations in backward: trades
            # FLOPs for HBM, the standard long-sequence TPU memory lever.
            # (decode stays out of the remat'd arg list: as a traced
            # operand it could not drive Python control flow.)
            if cfg.remat_policy not in ("full", "dots", "none"):
                raise ValueError(
                    f"unknown remat_policy {cfg.remat_policy!r}; "
                    "expected 'full', 'dots', or 'none'"
                )
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots"
                else None
            )
            block = nn.remat(Block, static_argnums=(), policy=policy)
            for i in range(cfg.num_layers):
                # decode/padded stay at their (static) defaults — passing
                # them positionally through remat would trace them
                x = block(cfg, name=f"layer{i}")(
                    x, positions, segment_ids, adapter_ids=adapter_ids
                )
        else:
            for i in range(cfg.num_layers):
                x = Block(cfg, name=f"layer{i}")(
                    x, positions, segment_ids, decode, padded, adapter_ids
                )
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        # untied output head
        head = self.param(
            "lm_head",
            nn.initializers.normal(0.02),
            (cfg.hidden_size, cfg.vocab_size),
        )
        if return_hidden:
            return x, head
        return head_logits(x, head, cfg.dtype)

    def head(self, hidden):
        """Logits of final-norm hidden states (..., H) from the bound
        ``lm_head`` (``model.apply({"params": p}, hidden,
        method="head")``): how a caller that took ``return_hidden``
        applies the head to the rows it kept."""
        return head_logits(
            hidden, self.get_variable("params", "lm_head"), self.cfg.dtype
        )


def embed_rows(embed, tokens):
    """The embedding table's rows for ``tokens``, an int8 ``QuantTensor``
    table staying int8 in HBM: its rows are gathered, then scaled.
    Per-row (axis=0) scales — quantize_tree's default for the embedding
    — gather alongside the rows; axis=-1 broadcasts."""
    if isinstance(embed, QuantTensor):
        rows = embed.q[tokens].astype(jnp.float32)
        return rows * (embed.scale[tokens] if embed.axis == 0 else embed.scale)
    return embed[tokens]


def head_logits(x, head, dtype, multiplier: float = 1.0):
    """float32 logits of hidden states ``x`` (..., H) under an untied head
    (H, vocab), an int8 ``QuantTensor`` head staying int8 in HBM."""
    if isinstance(head, QuantTensor):
        out = quantized_dot(x, head).astype(jnp.float32)
    else:
        out = (x @ head.astype(dtype)).astype(jnp.float32)
    return out if multiplier == 1.0 else out * multiplier


def llama_param_shardings(params, mesh: Mesh):
    """Mesh sharding rules for a Llama param tree — the declarative
    'llama' table in :mod:`tensorflowonspark_tpu.compute.layout`.

    Megatron layout on the ('fsdp', 'model') axes; biases/norms replicated.
    With mesh model=1 this degrades to pure FSDP (the Llama-2-7B baseline
    config); with fsdp=1 to pure TP. MoE expert banks and LoRA factor
    halves are rules in the same table, so model-level and module-level
    specs cannot diverge.
    """
    return layout.param_shardings(params, mesh, "llama")


def decode_cache_spec(x: jax.Array) -> PartitionSpec:
    """PartitionSpec for one KV-cache leaf under mesh-sharded decode:
    K/V (B, S, kv_heads, D) shard batch on 'data' and heads on 'model'
    (each TP shard holds only its heads' cache — the HBM split that
    makes 7B-class serving fit), int8-KV scale planes (B, S, kv_heads)
    follow their heads, the segment-id plane (B, S) shards on 'data',
    the scalar write index replicates. Declared as
    ``layout.DECODE_CACHE_SPECS``."""
    return layout.decode_cache_spec(x)


def generate(
    model: "Llama",
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    min_p: float | None = None,
    rng: jax.Array | None = None,
    eos_id: int | None = None,
    prompt_lengths: jax.Array | None = None,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Autoregressive sampling with a KV cache: (B, S) -> (B, max_new_tokens).

    One jitted prefill over the prompt, then single-token steps against
    the per-layer caches — static shapes throughout, so the whole loop is
    one compilation (cached across calls with the same model and shapes).
    ``temperature=0`` is greedy argmax; otherwise tokens are sampled from
    ``logits / temperature``, optionally truncated to the ``top_k`` most
    likely tokens and/or the smallest nucleus with cumulative probability
    ``top_p`` (top-k applies first, like the standard decoding stacks)
    and/or ``min_p`` (keep tokens whose probability is at least
    ``min_p`` times the most likely token's; composes with k/p by mask
    intersection).

    Mixed-length prompts: RIGHT-pad ``prompt`` and pass
    ``prompt_lengths`` (B,) true lengths. Each row samples its first
    token from the logits at ITS last real position, decodes from its
    own position, and overwrites its padding slots in the KV cache as it
    goes (per-row scatter writes; the positional mask keeps not-yet-
    overwritten padding invisible). Without ``prompt_lengths`` the
    prompt must be unpadded (all rows the same true length).

    ``eos_id``: rows that emit it are finished — their remaining slots
    fill with ``eos_id`` — and decoding exits EARLY once every row has
    finished (a ``lax.while_loop`` instead of the fixed-length scan; the
    output stays statically (B, max_new_tokens)). Decode is weight-read
    bound, so stopping at the true lengths is a proportional wall-clock
    win on typical generation workloads.

    ``mesh``: run the whole decode sharded over a device mesh — weights
    TP-sharded on the ``model`` axis (:func:`llama_param_shardings`,
    the Megatron layout; XLA inserts the per-layer psums over ICI),
    batch and KV caches sharded on ``data``/``model``
    (:func:`decode_cache_spec`). This is the multi-chip serving path:
    7B-class weights exceed one chip's HBM, so TP over ≥2 chips is the
    capacity floor, and DP over 'data' scales throughput. Tokens are
    bit-identical to the single-device decode up to TP reduction
    order. Requires batch % mesh 'data' extent == 0 and num_kv_heads %
    'model' extent == 0.
    """
    cfg = model.cfg
    b, s = prompt.shape
    if s + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len}); the KV cache cannot hold it"
        )
    if top_k is not None and top_k < 1:
        raise ValueError("top_k must be >= 1")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError("top_p must be in (0, 1]")
    if min_p is not None and not (0.0 <= min_p <= 1.0):
        raise ValueError("min_p must be in [0, 1]")
    if temperature == 0.0 and (
        top_k is not None or top_p is not None or min_p is not None
    ):
        raise ValueError(
            "top_k/top_p/min_p require temperature > 0 (temperature=0 is "
            "greedy argmax, which would silently ignore them)"
        )
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if mesh is not None:
        dp = mesh.shape["data"]
        tp = mesh.shape["model"]
        if b % dp:
            raise ValueError(
                f"batch {b} not divisible by the mesh 'data' extent {dp}"
            )
        if cfg.num_kv_heads % tp or cfg.num_heads % tp:
            raise ValueError(
                f"heads ({cfg.num_heads}/{cfg.num_kv_heads} kv) not "
                f"divisible by the mesh 'model' extent {tp}"
            )
        # Commit inputs to their decode shardings; jit then compiles the
        # SPMD program against the committed placements (device_put is a
        # no-op for already-placed serving calls).
        params = jax.device_put(params, llama_param_shardings(params, mesh))
        prompt = jax.device_put(
            prompt, layout.activation_sharding(mesh, "prompt")
        )
        rng = jax.device_put(rng, layout.replicated(mesh))
    # int8 weight-only decode: quantized trees (ops/quant.py
    # quantize_tree) pass straight through — QDense / the embed gather /
    # the head projection consume QuantTensor leaves natively, so the
    # weights stay int8 in HBM for the whole decode.
    run = _build_generate(
        model,
        b,
        s,
        max_new_tokens,
        float(temperature),
        None if top_k is None else int(top_k),
        None if top_p is None else float(top_p),
        None if eos_id is None else int(eos_id),
        padded=prompt_lengths is not None,
        mesh=mesh,
        min_p=None if min_p is None else float(min_p),
    )
    if prompt_lengths is None:
        return run(params, prompt, rng)
    lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if lengths.shape != (b,):
        raise ValueError(
            f"prompt_lengths must have shape ({b},), got {lengths.shape}"
        )
    # host-side range check: out-of-range lengths would clamp/wrap under
    # jit and decode plausible-looking garbage instead of raising
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
    return run(params, prompt, rng, lengths)


def sample_logits(
    logits, key, temperature, top_k=None, top_p=None, min_p=None
):
    """Sample next tokens from (B, vocab) logits.

    ``temperature == 0`` is greedy argmax (``key`` unused). Otherwise
    sample from ``logits / temperature``, optionally truncated to the
    ``top_k`` most likely tokens and/or the smallest nucleus with
    cumulative probability ``top_p`` (top-k applies first, matching the
    standard decoding stacks), and/or ``min_p`` (keep tokens whose
    probability is at least ``min_p`` times the most likely token's —
    an elementwise row-max compare on the scaled distribution,
    composing with k/p by mask intersection). Sampling params are
    trace-time constants — callers bake them into their jitted program.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    vocab = logits.shape[-1]
    k_active = top_k is not None and top_k < vocab
    p_active = top_p is not None and top_p < 1.0
    if k_active:
        # lax.top_k beats a full-vocab sort inside the scanned
        # single-token decode loop; when top_p is also set, the
        # nucleus scan then runs on k values instead of the vocab
        sorted_desc = jax.lax.top_k(logits, top_k)[0]
        logits = jnp.where(
            logits < sorted_desc[..., -1, None], -jnp.inf, logits
        )
    elif p_active:
        sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    if p_active:
        cum = jnp.cumsum(jax.nn.softmax(sorted_desc, axis=-1), axis=-1)
        # index of the last kept token: everything before the point
        # where cumulative mass reaches top_p, and always >= 0 (the
        # most likely token survives even when it alone exceeds p;
        # an index == k clamps to the last top-k entry = keep all)
        cutoff_index = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff_logit = jnp.take_along_axis(
            sorted_desc, cutoff_index, axis=-1
        )
        logits = jnp.where(logits < cutoff_logit, -jnp.inf, logits)
    if min_p is not None and min_p > 0.0:
        # log-space: prob >= min_p * prob_max  <=>  logit >= max + log(m),
        # on the temperature-scaled distribution. The row max survives
        # any k/p mask above (the most likely token is never truncated),
        # and already-masked entries stay -inf, so this intersects.
        floor = jnp.max(logits, axis=-1, keepdims=True) + jnp.log(
            jnp.float32(min_p)
        )
        logits = jnp.where(logits < floor, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def _build_generate(
    model: "Llama",
    b: int,
    s: int,
    max_new_tokens: int,
    temperature: float,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
    padded: bool = False,
    mesh: Mesh | None = None,
    min_p: float | None = None,
):
    """Compile-once generate body per (model config, shapes, sampling
    params).

    flax Modules hash by their dataclass fields, so two ``Llama`` instances
    with equal configs share the cache entry (``Mesh`` hashes by device
    assignment + axis names, so a mesh keys its own entry); a per-call
    ``jax.jit`` would recompile the prefill + scan graph on every
    invocation.
    """

    def constrain_cache(cache):
        # Pin the per-layer KV caches to their decode shardings at the
        # loop boundary; the scan/while carry then keeps them there
        # instead of letting sharding propagation pick (e.g.) a
        # replicated layout whose per-step all-gathers would swamp the
        # HBM-bound decode.
        if mesh is None:
            return cache
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, layout.decode_cache_sharding(mesh, x)
            ),
            cache,
        )

    def sample(logits, key):
        return sample_logits(
            logits, key, temperature, top_k, top_p, min_p
        )

    # the mesh is ambient while the body is traced: under one, the
    # cached attention of a padded step keeps the einsum GSPMD partitions
    @jax.jit
    @use_mesh(mesh)
    def run(params, prompt, rng, lengths=None):
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s)
        )
        logits, prefill = model.apply(
            {"params": params},
            prompt,
            positions=positions,
            decode=True,
            padded=padded,
            # right-padding is no token: a model that carries recurrent
            # state (models/falcon_h1.py) must not run over it
            valid=positions < lengths[:, None] if padded else None,
            mutable=["cache"],
        )
        keys = jax.random.split(rng, max_new_tokens)
        if padded:
            # each row's first token samples from the logits at ITS
            # last real position; decode continues from its own length
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1
            )[:, 0]
            tok = sample(last, keys[0])
            pos0 = lengths
        else:
            tok = sample(logits[:, -1], keys[0])
            pos0 = jnp.full((b,), s, jnp.int32)

        def decode_step(cache, tok, pos, key):
            logits, updated = model.apply(
                {"params": params, "cache": cache},
                tok[:, None],
                positions=pos[:, None],
                decode=True,
                padded=padded,
                mutable=["cache"],
            )
            return constrain_cache(updated["cache"]), sample(
                logits[:, -1], key
            )

        if eos_id is None:

            def step(carry, key):
                cache, tok, pos = carry
                cache, next_tok = decode_step(cache, tok, pos, key)
                return (cache, next_tok, pos + 1), tok

            init = (constrain_cache(prefill["cache"]), tok, pos0)
            (_, last, _), toks = jax.lax.scan(step, init, keys[1:])
            # scan emitted each step's *input* token; the final sample
            # closes the sequence
            return jnp.concatenate(
                [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1
            )

        # EOS path: while_loop exits as soon as EVERY row has emitted
        # eos_id; finished rows keep emitting eos_id. Output shape stays
        # statically (B, max_new_tokens).
        buf = jnp.full((b, max_new_tokens), eos_id, jnp.int32)
        buf = buf.at[:, 0].set(tok)
        done = tok == eos_id

        def cond(carry):
            _, _, _, done, _, i = carry
            return (i < max_new_tokens) & ~jnp.all(done)

        def body(carry):
            cache, tok, pos, done, buf, i = carry
            cache, next_tok = decode_step(
                cache, tok, pos, jax.lax.dynamic_index_in_dim(
                    keys, i, keepdims=False
                )
            )
            next_tok = jnp.where(done, eos_id, next_tok)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, next_tok[:, None], i, axis=1
            )
            return (
                cache,
                next_tok,
                pos + 1,
                done | (next_tok == eos_id),
                buf,
                i + 1,
            )

        init = (
            constrain_cache(prefill["cache"]), tok, pos0, done, buf,
            jnp.int32(1),
        )
        (_, _, _, _, buf, _) = jax.lax.while_loop(cond, body, init)
        return buf

    return run


def packed_loss_mask(segment_ids: jax.Array):
    """Loss mask + canonicalized ids for packed rows.

    ``segment_ids`` is (B, S+1), aligned with the (B, S+1) token rows
    ``llama_loss_fn`` trains on. Returns ``(mask, canonical_ids)``:

    - ``mask`` (B, S) float32 — 1 where the target position trains.
      Segment id 0 marks PADDING (the t5x/maxtext convention;
      ``data/packing.py`` emits it): pad targets never train. Positions
      whose NEXT token belongs to a different document are dropped — a
      document's last token must not be trained to predict the next
      document's first.
    - ``canonical_ids`` (B, S+1) — adjacency runs renumbered into
      per-row document indices: attention masks by id EQUALITY, so a
      packer that reuses an id for a later document (e.g.
      [0,0,1,1,0,0]) would silently leak attention between the two
      id-0 documents.

    ``mask.sum()`` is the batch's valid-token count — the exact weight
    to hand ``build_train_step(batch_weight_fn=...)`` when gradient-
    accumulating packed batches (see :func:`packed_valid_count`).
    """
    not_pad = (segment_ids[:, :-1] != 0).astype(jnp.float32)
    new_doc = segment_ids[:, 1:] != segment_ids[:, :-1]
    canonical = jnp.concatenate(
        [
            jnp.zeros_like(segment_ids[:, :1]),
            jnp.cumsum(new_doc.astype(jnp.int32), axis=1),
        ],
        axis=1,
    )
    mask = (canonical[:, :-1] == canonical[:, 1:]).astype(jnp.float32) * not_pad
    return mask, canonical


def packed_valid_count(segment_ids: jax.Array) -> jax.Array:
    """Scalar count of loss-contributing positions in a packed batch —
    ``build_train_step``'s ``batch_weight_fn`` for exact token-weighted
    gradient accumulation over packed/masked CE."""
    mask, _ = packed_loss_mask(segment_ids)
    return jnp.sum(mask)


def llama_loss_fn(model: "Llama", logit_chunk: int | None = None):
    """Next-token loss closure ``(params, tokens(B,S+1)) -> scalar`` that
    also collects sown auxiliary losses (the MoE router load-balancing
    loss — ``parallel/moe.py:MoEMLP``). A bare ``model.apply`` without
    ``mutable=['losses']`` silently discards those, so MoE configs MUST
    train through this (or an equivalent mutable-collecting) loss.

    ``logit_chunk``: compute the vocab projection + cross entropy per
    sequence chunk of this length under ``jax.checkpoint``, so the
    (B, S, vocab) fp32 logits are never materialized (backward
    recomputes each chunk's logits). At seq 4096 / vocab 32000 / b 8 the
    full logits alone are 4.2 GB of HBM — this trades one extra head
    matmul pass for that footprint. Must divide the sequence length.

    Packed sequences: pass ``segment_ids`` (B, S+1), aligned with
    ``tokens`` (``data/packing.py`` produces both). Attention is masked
    within documents (every impl incl. ring/Ulysses SP), positions whose
    NEXT token belongs to a different document are dropped from the loss
    — a document's last token must not be trained to predict the next
    document's first — and segment id 0 marks padding (the t5x/maxtext
    convention): padding positions never contribute loss.
    """

    def loss(params, tokens, segment_ids=None):
        mask = None
        if segment_ids is not None:
            mask, segment_ids = packed_loss_mask(segment_ids)
        seg_in = None if segment_ids is None else segment_ids[:, :-1]
        if logit_chunk is None:
            logits, state = model.apply(
                {"params": params},
                tokens[:, :-1],
                segment_ids=seg_in,
                mutable=["losses"],
            )
            total = cross_entropy_loss(logits, tokens[:, 1:], mask)
        else:
            (hidden, head), state = model.apply(
                {"params": params},
                tokens[:, :-1],
                segment_ids=seg_in,
                return_hidden=True,
                mutable=["losses"],
            )
            b, s, h = hidden.shape
            if s % logit_chunk:
                raise ValueError(
                    f"logit_chunk {logit_chunk} must divide seq len {s}"
                )
            targets = tokens[:, 1:]
            head16 = head.astype(hidden.dtype)
            mc = jnp.ones((b, s), jnp.float32) if mask is None else mask

            @jax.checkpoint
            def chunk_nll_sum(hc, tc, mk):
                # (B, C, H) @ (H, V) -> fp32 logits for this chunk only
                logits = (hc @ head16).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)
                return jnp.sum(nll[..., 0] * mk)

            n_chunks = s // logit_chunk
            hs = hidden.reshape(b, n_chunks, logit_chunk, h).swapaxes(0, 1)
            ts = targets.reshape(b, n_chunks, logit_chunk).swapaxes(0, 1)
            ms = mc.reshape(b, n_chunks, logit_chunk).swapaxes(0, 1)

            def body(acc, htm):
                hc, tc, mk = htm
                return acc + chunk_nll_sum(hc, tc, mk), None

            total, _ = jax.lax.scan(
                body, jnp.zeros((), jnp.float32), (hs, ts, ms)
            )
            total = total / jnp.maximum(jnp.sum(mc), 1)
        for leaf in jax.tree.leaves(state.get("losses", {})):
            total = total + jnp.sum(leaf)
        return total

    return loss


def cross_entropy_loss(logits: jax.Array, targets: jax.Array, mask=None):
    """Mean next-token cross entropy; logits (B,S,V), targets (B,S)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)
