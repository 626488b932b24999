"""Operations and bytes openPangu-Ultra-MoE needs, from shapes alone: what
``perfbench/counts.py`` is to the Mistral cells. ``cfg`` is the
configuration file's dict (the keys of the public ``config.json``), in
which ``n_routed_experts`` is the number of routed experts *held here*
and ``published.n_routed_experts``, carried as ``router_experts``, the
router's range: what this chip does not hold it does not compute, and
nothing here counts it."""

from __future__ import annotations

from perfbench import counts


def router_width(cfg: dict) -> int:
    return cfg.get("router_experts") or cfg["n_routed_experts"]


def held_share(cfg: dict) -> float:
    """Expected held experts among a token's chosen ones, under a router
    that is even over its range: ``top_k * held / range``."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)


def attention_matmul_params(cfg: dict) -> int:
    """The five projections. The key-value up-projection is counted once:
    applied to the latent (prefill) or absorbed into the query and the
    context (decode), it is the same products a token."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rot = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd, qr = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    return (h * qr + qr * heads * (nope + rot) + h * (rank + rot)
            + rank * heads * (nope + vd) + heads * vd * h)


def attention_params(cfg: dict) -> int:
    return attention_matmul_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    return attention_params(cfg) + 3 * h * cfg["intermediate_size"] + 4 * h


def expert_layer_fixed_params(cfg: dict) -> int:
    """An expert layer without its routed banks: attention, the shared
    experts, the router, four norms."""
    h = cfg["hidden_size"]
    return (attention_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg)
            + h * router_width(cfg) + 4 * h)


def expert_layer_params(cfg: dict) -> int:
    return expert_layer_fixed_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def n_layers(cfg: dict) -> tuple[int, int]:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def n_params(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    dense, routed = n_layers(cfg)
    return (dense * dense_layer_params(cfg) + routed * expert_layer_params(cfg)
            + 2 * v * h + h)


def token_matmul_params(cfg: dict) -> float:
    """Weights a token is multiplied by over all layers, the held experts
    at their expected share (``held_share``): the work done here."""
    dense, routed = n_layers(cfg)
    h = cfg["hidden_size"]
    per_dense = attention_matmul_params(cfg) + 3 * h * cfg["intermediate_size"]
    per_routed = (attention_matmul_params(cfg) + h * router_width(cfg)
                  + (cfg["n_shared_experts"] + held_share(cfg)) * expert_params(cfg))
    return dense * per_dense + routed * per_routed


def prefill_pair_flops(cfg: dict) -> int:
    """Per (query, key) pair and layer, keys and values expanded a head:
    QK^T over nope + rope, PV over the value width."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def latent_position_flops(cfg: dict) -> int:
    """Absorbed decode, per cached position and layer: every head's query
    against the whole entry, and its probabilities against the compressed
    part. 278,528 at the published widths."""
    rank = cfg["kv_lora_rank"]
    return 2 * cfg["num_attention_heads"] * (rank + cfg["qk_rope_head_dim"] + rank)


def latent_position_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """One cache entry: 1,152 B at the published widths."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * dtype_bytes


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """One request, as ``counts.serve_flops`` reckons it: the prompt and
    all but the last completion token through the layers, the head once a
    completion token; the prompt attends in the expanded form, each
    decoded token in the absorbed form over everything before it."""
    decoded = max(new_tokens - 1, 0)
    total = prompt_len + decoded
    layers = cfg["num_hidden_layers"]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    pairs_prompt = counts.attended_pairs([prompt_len], None)
    pairs_decode = counts.attended_pairs([total], None) - pairs_prompt
    return (2 * total * token_matmul_params(cfg) + 2 * new_tokens * head
            + layers * (pairs_prompt * prefill_pair_flops(cfg)
                        + pairs_decode * latent_position_flops(cfg)))


def latent_call_least_s(cfg: dict, positions: float, rows: int, peak: dict) -> float:
    """The least time of one call of the decode attention over rows whose
    written positions sum to ``positions``: the larger of its FLOPs over
    the peak and its bytes (the live entries, the queries in and the
    contexts out) over the bandwidth."""
    heads, rank, rot = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    flops = positions * latent_position_flops(cfg)
    moved = positions * latent_position_bytes(cfg) + rows * heads * (2 * rank + rot) * 2
    return max(flops / peak["flops"], moved / peak["hbm_bytes_per_s"])


def experts_step_bytes(cfg: dict, reached: float, pairs: float, dtype_bytes: int = 2) -> float:
    """What one decode step's grouped products must move over all expert
    layers: the three banks of each expert some row reached (``reached``:
    experts summed over layers), and per held pair the row in, the two
    activations out and in again, and the row out."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (reached * expert_params(cfg) + pairs * (2 * h + 4 * f)) * dtype_bytes


def fixed_step_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """Weights every decode step reads whatever is routed: the layers
    without their routed banks, the final norm and the head (of the
    embedding only the rows looked up, which are left out); the router in
    float32."""
    h = cfg["hidden_size"]
    dense, routed = n_layers(cfg)
    weights = (dense * dense_layer_params(cfg) + routed * expert_layer_fixed_params(cfg)
               + h + h * cfg["vocab_size"])
    return weights * dtype_bytes + routed * h * router_width(cfg) * (4 - dtype_bytes)


def decode_step_bytes(cfg: dict, live_tokens: float, reached: float, pairs: float,
                      dtype_bytes: int = 2) -> float:
    """What one decode step must move: the fixed weights, the banks of
    the experts reached with their rows, and the latent entries of the
    live contexts in every layer."""
    return (fixed_step_bytes(cfg, dtype_bytes)
            + experts_step_bytes(cfg, reached, pairs, dtype_bytes)
            + live_tokens * cfg["num_hidden_layers"] * latent_position_bytes(cfg, dtype_bytes))
