"""Operations and bytes Solar-Open2 needs, from shapes alone: what
``perfbench/counts.py`` is to the Mistral cells. ``cfg`` is the
configuration file's dict (the keys of the public ``config.json``), in
which ``n_routed_experts`` is the number of routed experts *held here*
and ``router_experts`` the router's range (``counts_pangu_moe``'s
convention, whose expert-layer functions serve here too: they read only
the keys the two models share). Nothing here knows what implements a
layer."""

from __future__ import annotations

from perfbench import counts
from perfbench.counts_pangu_moe import (  # noqa: F401  (router_width: the weights' entry)
    expert_params,
    experts_step_bytes,
    held_share,
    router_width,
)


def linear(cfg: dict) -> tuple[int, int, int]:
    """Heads, head width and convolution width of the linear layer."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def n_layers(cfg: dict) -> tuple[int, int]:
    """(GQA layers, linear layers)."""
    gqa = len(cfg["gqa_layers"])
    return gqa, cfg["num_hidden_layers"] - gqa


def gqa_matmul_params(cfg: dict) -> int:
    """q, k, v, the output gate and o: 109,051,904 as published."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * nq + 2 * h * nkv + nq * h + (h * nq if cfg["use_gqa_gate"] else 0)


def linear_matmul_params(cfg: dict) -> int:
    """q, k, v, o, the two low-rank gates and beta: 137,625,600."""
    h = cfg["hidden_size"]
    heads, d, _ = linear(cfg)
    c = heads * d
    return 4 * h * c + 2 * (h * d + d * c) + h * heads


def linear_small_params(cfg: dict) -> int:
    """The output gate's bias, three convolutions, A_log, dt_bias and the
    head norm: 114,880."""
    heads, d, k = linear(cfg)
    c = heads * d
    return c + 3 * k * c + heads + c + d


def moe_fixed_params(cfg: dict) -> int:
    """Router and shared experts: what an expert layer reads whatever is
    routed."""
    return (cfg["hidden_size"] * router_width(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg))


def layer_fixed_params(cfg: dict, gqa: bool) -> int:
    """A layer without its routed banks: 126,099,456 (GQA) or 154,788,032
    (linear) as published."""
    mix = gqa_matmul_params(cfg) if gqa else (
        linear_matmul_params(cfg) + linear_small_params(cfg))
    return mix + moe_fixed_params(cfg) + 2 * cfg["hidden_size"]


def n_params(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    gqa, lin = n_layers(cfg)
    banks = cfg["num_hidden_layers"] * cfg["n_routed_experts"] * expert_params(cfg)
    return (gqa * layer_fixed_params(cfg, True) + lin * layer_fixed_params(cfg, False)
            + banks + 2 * v * h + h)


def token_matmul_params(cfg: dict) -> float:
    """Weights a token is multiplied by over all layers, the held experts
    at their expected share (``held_share``): the work done here."""
    gqa, lin = n_layers(cfg)
    moe = (cfg["hidden_size"] * router_width(cfg)
           + (cfg["n_shared_experts"] + held_share(cfg)) * expert_params(cfg))
    return (gqa * gqa_matmul_params(cfg) + lin * linear_matmul_params(cfg)
            + cfg["num_hidden_layers"] * moe)


def delta_rule_flops_per_token(cfg: dict) -> int:
    """The recurrence's own FLOPs a token and linear layer, beside the
    projections: the two reads of the state (by the key and by the
    query) and the rank-one correction, 2 d_k d_v each, and the decay,
    d_k d_v. The chunked form reassociates these and is counted the same:
    what the layer needs, not what a form spends."""
    heads, d, _ = linear(cfg)
    return 7 * heads * d * d


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """One request, as ``counts.serve_flops`` reckons it: the prompt and
    all but the last completion token through the layers, the head once a
    completion token; causal attention over everything before in the GQA
    layers, the delta rule a token in the linear ones."""
    decoded = max(new_tokens - 1, 0)
    total = prompt_len + decoded
    gqa, lin = n_layers(cfg)
    head = cfg["hidden_size"] * cfg["vocab_size"]
    pairs = counts.attended_pairs([total], None)
    return (2 * total * token_matmul_params(cfg) + 2 * new_tokens * head
            + gqa * 4 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]
            + lin * total * delta_rule_flops_per_token(cfg))


def state_bytes(cfg: dict, slots: int) -> int:
    """One linear layer's delta-rule state of every slot, float32."""
    heads, d, _ = linear(cfg)
    return slots * heads * d * d * 4


def window_bytes(cfg: dict, slots: int, dtype_bytes: int = 2) -> int:
    """One linear layer's convolution windows of every slot."""
    heads, d, k = linear(cfg)
    return slots * (k - 1) * 3 * heads * d * dtype_bytes


def kda_step_bytes(cfg: dict, slots: int) -> int:
    """What one decode step's state updates must move: every linear
    layer's state of every slot read once and written once."""
    return n_layers(cfg)[1] * 2 * state_bytes(cfg, slots)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position over the GQA layers."""
    return (n_layers(cfg)[0] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * dtype_bytes)


def fixed_step_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the layers
    without their routed banks, the final norm and the head (of the
    embedding only the rows looked up, which are left out); the routers
    in float32."""
    h = cfg["hidden_size"]
    gqa, lin = n_layers(cfg)
    weights = (gqa * layer_fixed_params(cfg, True) + lin * layer_fixed_params(cfg, False)
               + h + h * cfg["vocab_size"])
    return (weights * dtype_bytes
            + cfg["num_hidden_layers"] * h * router_width(cfg) * (4 - dtype_bytes))


def decode_step_parts(cfg: dict, live_kv_tokens: float, slots: int, reached: float,
                      pairs: float, dtype_bytes: int = 2) -> dict:
    """What one decode step must move, by part: the fixed weights, the
    banks of the experts reached with their rows, the state and the
    windows of every slot once in and once out, the K/V of the live
    contexts."""
    lin = n_layers(cfg)[1]
    return {
        "fixed": fixed_step_bytes(cfg, dtype_bytes),
        "experts": experts_step_bytes(cfg, reached, pairs, dtype_bytes),
        "state": kda_step_bytes(cfg, slots),
        "windows": lin * 2 * window_bytes(cfg, slots, dtype_bytes),
        "kv": live_kv_tokens * kv_bytes_per_token(cfg, dtype_bytes),
    }


def decode_step_bytes(cfg: dict, live_kv_tokens: float, slots: int, reached: float,
                      pairs: float, dtype_bytes: int = 2) -> float:
    return sum(decode_step_parts(
        cfg, live_kv_tokens, slots, reached, pairs, dtype_bytes).values())
