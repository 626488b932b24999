"""Operations and bytes Laguna needs, from shapes alone: what
``perfbench/counts.py`` is to the Mistral cells. ``cfg`` is the
configuration file's dict (the keys of the public ``config.json``); every
expert is held. Nothing here knows what implements a layer."""

from __future__ import annotations

from perfbench import counts

FULL, WINDOW = "full_attention", "sliding_attention"


def heads(cfg: dict, layer: int) -> int:
    return cfg["num_attention_heads_per_layer"][layer]


def layers_of(cfg: dict, kind: str) -> list[int]:
    return [i for i, t in enumerate(cfg["layer_types"]) if t == kind]


def attention_matmul_params(cfg: dict, layer: int) -> int:
    """q, k, v, o and the per-head gate: 29,458,432 (48 heads) or
    37,879,808 (64) as published."""
    h, d, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    nq = heads(cfg, layer) * d
    return 2 * h * nq + 2 * h * kv * d + h * heads(cfg, layer)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse(cfg: dict, layer: int) -> bool:
    return cfg["mlp_layer_types"][layer] == "sparse"


def layer_fixed_params(cfg: dict, layer: int) -> int:
    """A layer without its routed banks: attention, the dense MLP or the
    router and the shared expert, two norms."""
    h = cfg["hidden_size"]
    ff = (h * cfg["num_experts"] + shared_params(cfg)) if sparse(cfg, layer) \
        else dense_mlp_params(cfg)
    return attention_matmul_params(cfg, layer) + ff + 2 * h


def n_sparse(cfg: dict) -> int:
    return sum(sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))


def n_params(cfg: dict) -> int:
    """3,869,857,792 at the cell's five layers; 33.44 B at the published
    forty."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    fixed = sum(layer_fixed_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return fixed + n_sparse(cfg) * cfg["num_experts"] * expert_params(cfg) + 2 * v * h + h


def token_matmul_params(cfg: dict) -> int:
    """Weights a token is multiplied by over all layers: the attention
    projections, the dense MLP, or the router, the chosen experts and the
    shared one."""
    h, k = cfg["hidden_size"], cfg["num_experts_per_tok"]
    total = 0
    for i in range(cfg["num_hidden_layers"]):
        total += attention_matmul_params(cfg, i)
        total += (h * cfg["num_experts"] + k * expert_params(cfg) + shared_params(cfg)
                  if sparse(cfg, i) else dense_mlp_params(cfg))
    return total


def attention_flops(cfg: dict, lengths, kind: str) -> int:
    """QK^T and PV of causal rows of these ``lengths`` over the layers of
    ``kind``, a windowed layer's queries clipped to the window."""
    window = cfg["sliding_window"] if kind == WINDOW else None
    pairs = counts.attended_pairs(lengths, window)
    return sum(4 * pairs * heads(cfg, i) * cfg["head_dim"] for i in layers_of(cfg, kind))


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """One prompt's prefill, model FLOPs: every position through the
    projections, the dense MLP or the chosen and the shared experts, the
    attention causal or windowed by layer, the head at the last position."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (2 * prompt_len * token_matmul_params(cfg) + 2 * head
            + attention_flops(cfg, [prompt_len], FULL)
            + attention_flops(cfg, [prompt_len], WINDOW))


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> int:
    """One request, as ``counts.serve_flops`` reckons it: the prompt and
    all but the last completion token through the layers, the head once a
    completion token, causal attention over everything before in the full
    layers and over the window in the others."""
    decoded = max(new_tokens - 1, 0)
    total = prompt_len + decoded
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (2 * total * token_matmul_params(cfg) + 2 * new_tokens * head
            + attention_flops(cfg, [total], FULL) + attention_flops(cfg, [total], WINDOW))


def kv_position_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one position in one layer: 4,096 B as published."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * dtype_bytes


def slot_cache_bytes(cfg: dict, max_seq_len: int, dtype_bytes: int = 2) -> int:
    """One slot's K/V: a dense plane of ``max_seq_len`` a full layer, a
    ring of the window a windowed one (123.7 MB at 14,336)."""
    per = kv_position_bytes(cfg, dtype_bytes)
    return per * (len(layers_of(cfg, FULL)) * max_seq_len
                  + len(layers_of(cfg, WINDOW)) * cfg["sliding_window"])


def fixed_step_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the layers
    without their routed banks, the final norm and the head (of the
    embedding only the rows looked up, which are left out); the routers
    in float32."""
    h = cfg["hidden_size"]
    weights = sum(layer_fixed_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return ((weights + h + h * cfg["vocab_size"]) * dtype_bytes
            + n_sparse(cfg) * h * cfg["num_experts"] * (4 - dtype_bytes))


def experts_step_bytes(cfg: dict, reached: float, pairs: float, dtype_bytes: int = 2) -> float:
    """What one decode step's grouped products must move over all expert
    layers: the three banks of each expert some row reached (``reached``:
    experts summed over layers) and per routed pair the row in, the two
    activations out and in again, and the row out."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (reached * expert_params(cfg) + pairs * (2 * h + 4 * f)) * dtype_bytes


def decode_step_parts(cfg: dict, full_positions: float, window_positions: float,
                      reached: float, pairs: float, dtype_bytes: int = 2) -> dict:
    """What one decode step must move, by part: the fixed weights, the
    banks of the experts reached with their rows, and K/V at the positions
    the step reads, summed over the full layers' planes and over the
    windowed layers' rings."""
    per = kv_position_bytes(cfg, dtype_bytes)
    return {
        "fixed": fixed_step_bytes(cfg, dtype_bytes),
        "experts": experts_step_bytes(cfg, reached, pairs, dtype_bytes),
        "planes": full_positions * per,
        "rings": window_positions * per,
    }
