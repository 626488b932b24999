"""Operations and bytes Phi-4-mini-flash (SambaY) needs, from shapes alone:
what ``perfbench/counts.py`` is to the Mistral cells. ``cfg`` is the
configuration file's dict (the keys of the public ``config.json``, and
the Mamba sizes it leaves open under ``mamba``). Nothing here knows what
implements a layer.

The layers by kind (``layer_kinds``): Mamba-1 at even layers up to L/2,
windowed attention at the odd ones below it, full attention at L/2 + 1,
then gated memory units (even) and cross attention (odd). The
self-decoder is every layer up to the full one; the cross-decoder, the
rest, writes no cache and runs at a prompt's last position only.
"""

from __future__ import annotations

from perfbench import counts


def mamba(cfg: dict) -> tuple[int, int, int, int]:
    """Channels, state size, convolution width and dt rank of the Mamba
    layers."""
    m = cfg["mamba"]
    return (m["expand"] * cfg["hidden_size"], m["d_state"], m["d_conv"],
            m["dt_rank"])


def layer_kinds(cfg: dict) -> list[str]:
    n, mb = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    half = n // 2
    out = []
    for i in range(n):
        even = i % mb == 0
        if i <= half:
            out.append("mamba" if even else "swa")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("gmu" if even else "cross")
    return out


def n_of(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    """The mixer's matrices: what a token is multiplied by."""
    h = cfg["hidden_size"]
    c, N, _, r = mamba(cfg)
    kvw = cfg["num_key_value_heads"] * head_dim(cfg)
    return {
        "mamba": h * 2 * c + c * (r + 2 * N) + r * c + c * h,
        "swa": 2 * h * h + 2 * h * kvw,
        "full": 2 * h * h + 2 * h * kvw,
        "cross": 2 * h * h,
        "gmu": 2 * h * c,
    }[kind]


def mixer_small_params(cfg: dict, kind: str) -> int:
    """The mixer's vectors: the convolution with its bias, dt's bias,
    A_log and D; the four lambda vectors and the subln scale."""
    c, N, k, _ = mamba(cfg)
    d = head_dim(cfg)
    if kind == "mamba":
        return k * c + c + c + c * N + c
    return 0 if kind == "gmu" else 4 * d + 2 * d


def layer_params(cfg: dict, kind: str) -> int:
    """119,895,040 (Mamba), 98,314,624 (windowed or full), 104,867,840
    (GMU), 91,761,024 (cross) as published: the mixer, the MLP and two
    LayerNorms with their biases."""
    return (mixer_matmul_params(cfg, kind) + mixer_small_params(cfg, kind)
            + mlp_params(cfg) + 4 * cfg["hidden_size"])


def n_params(cfg: dict) -> int:
    """3,852,457,984 as published: the layers, the embedding (the head is
    tied) and the final LayerNorm."""
    h = cfg["hidden_size"]
    return (sum(layer_params(cfg, k) for k in layer_kinds(cfg))
            + cfg["vocab_size"] * h + 2 * h)


def token_matmul_params(cfg: dict, part: str) -> int:
    """Weights a token is multiplied by in the ``self`` decoder (every
    layer up to the full one) or the ``cross`` decoder (the rest)."""
    kinds = layer_kinds(cfg)
    cut = kinds.index("full") + 1
    span = kinds[:cut] if part == "self" else kinds[cut:]
    return sum(mixer_matmul_params(cfg, k) + mlp_params(cfg) for k in span)


def scan_flops_per_token(cfg: dict) -> int:
    """The selective scan's own FLOPs a position and Mamba layer beside
    its projections: per channel and state index the decay's product and
    exponential, the state's product and sum, the input's product and
    the output's product and sum."""
    c, N, _, _ = mamba(cfg)
    return 7 * c * N


def attention_flops(cfg: dict, pairs: int) -> int:
    """Both maps of every differential head, as ``counts.
    attention_fwd_flops`` reckons a head: 4 x pairs x query heads x
    head width."""
    return 4 * pairs * cfg["num_attention_heads"] * head_dim(cfg)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """One request as the model computes it: the self-decoder at every
    prompt position and every decoded one, the cross-decoder at the
    prompt's last position and every decoded one, the head once a
    completion token; windowed attention over the last ``sliding_window``
    positions, full and cross attention over everything before."""
    decoded = max(new_tokens - 1, 0)
    total = prompt_len + decoded
    w = cfg["sliding_window"]
    # the cross-decoder's queries: the prompt's last position, then each
    # decoded one, each over every position up to its own
    cross_pairs = sum(range(prompt_len, total + 1))
    return (
        2 * total * token_matmul_params(cfg, "self")
        + 2 * (1 + decoded) * token_matmul_params(cfg, "cross")
        + 2 * new_tokens * cfg["hidden_size"] * cfg["vocab_size"]
        + n_of(cfg, "swa") * attention_flops(cfg, counts.attended_pairs([total], w))
        + attention_flops(cfg, counts.attended_pairs([total], None))
        + n_of(cfg, "cross") * attention_flops(cfg, cross_pairs)
        + n_of(cfg, "mamba") * total * scan_flops_per_token(cfg)
    )


def kv_bytes_per_position(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position of one attention layer: 5,120 B."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * dtype_bytes


def state_bytes(cfg: dict, slots: int) -> int:
    """One Mamba layer's scan state of every slot, float32."""
    c, N, _, _ = mamba(cfg)
    return slots * c * N * 4


def mamba_step_bytes(cfg: dict, slots: int) -> int:
    """What one decode step's scan-state updates must move: every Mamba
    layer's state of every slot read once and written once."""
    return n_of(cfg, "mamba") * 2 * state_bytes(cfg, slots)


def fixed_step_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Every weight once a step, the tied head included (of the embedding
    as a lookup only a few rows, left out: the head reads it whole)."""
    return n_params(cfg) * dtype_bytes


def decode_step_parts(cfg: dict, live_kv_tokens: float, live_window_tokens: float,
                      recurrent_bytes: float, dtype_bytes: int = 2) -> dict:
    """What one decode step must move, by part: the weights; the shared
    plane's live rows, read by the full layer and every cross layer; the
    rings' written positions; the recurrent leaves of the live slots,
    once in and once out (``recurrent_bytes``: the engine's count a
    step)."""
    per = kv_bytes_per_position(cfg, dtype_bytes)
    return {
        "fixed": fixed_step_bytes(cfg, dtype_bytes),
        "plane": live_kv_tokens * per * (1 + n_of(cfg, "cross")),
        "rings": live_window_tokens * per * n_of(cfg, "swa"),
        "recurrent": 2 * recurrent_bytes,
    }


def scan_call(cfg: dict, rows: int, width: int, in_bytes: int = 2) -> tuple[int, int]:
    """FLOPs and bytes of one prefill scan call over ``rows`` x ``width``
    positions: u (the model's dtype), dt, B and C in, y out, the state in
    and out."""
    c, N, _, _ = mamba(cfg)
    flops = rows * width * scan_flops_per_token(cfg)
    moved = (rows * width * c * (in_bytes + 4 + 4) + rows * width * 2 * N * 4
             + 2 * rows * N * c * 4)
    return flops, moved
