"""Operations and bytes the algorithm needs, from shapes alone.

Every share of a peak or of a roofline in the benchmark divides a number
from this file by a time from the chip. ``cfg`` is a configuration file's
dict (the keys of the model's public ``config.json``). Recomputation
(remat) is never counted; the embedding is a lookup and has no FLOPs.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that a token is multiplied by."""
    h, i, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q = h * cfg["num_attention_heads"] * d
    kv = 2 * h * cfg["num_key_value_heads"] * d
    o = cfg["num_attention_heads"] * d * h
    return q + kv + o + 3 * h * i


def n_params(cfg: dict) -> int:
    """All parameters: layers with their two norms, embedding, final norm
    and the untied head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * h)
    return layers + 2 * v * h + h


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    return (
        cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
        * head_dim(cfg) * dtype_bytes
    )


def attended_pairs(doc_lengths, window: int | None) -> int:
    """(query, key) pairs of causal attention inside each document, each
    query seeing at most its last ``window`` keys."""
    total = 0
    for n in doc_lengths:
        n = int(n)
        if window is None or n <= window:
            total += n * (n + 1) // 2
        else:
            total += window * (window + 1) // 2 + (n - window) * window
    return total


def attention_fwd_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV: 2 multiply-adds of head_dim per pair and query head."""
    return 4 * pairs * cfg["num_attention_heads"] * head_dim(cfg)


def forward_flops(cfg: dict, tokens: int, pairs: int, head_tokens: int) -> int:
    """One forward pass over ``tokens`` positions, with the head applied
    at ``head_tokens`` of them."""
    mm = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return 2 * tokens * mm + 2 * head_tokens * head + attention_fwd_flops(cfg, pairs)


def train_step_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward and backward (twice the forward) over ``tokens`` real
    positions, the head at each."""
    return 3 * forward_flops(cfg, tokens, pairs, tokens)


# The three flash kernels, by what each must compute from its own inputs:
# forward S=QK^T, O=PV; dq: S, dP=dO V^T, dQ=dS K; dkv: S, dP, dV=P^T dO,
# dK=dS^T Q. Each matmul is 2*head_dim FLOPs per pair and query head.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(cfg: dict, which: str, pairs: int) -> int:
    return 2 * FLASH_MATMULS[which] * pairs * cfg["num_attention_heads"] * head_dim(cfg)


def flash_call_bytes(cfg: dict, which: str, tokens: int, dtype_bytes: int = 2) -> int:
    """Least HBM traffic of one call: every operand read once and every
    result written once (q, o, do, dq per query head; k, v, dk, dv per KV
    head)."""
    d = head_dim(cfg)
    q = tokens * cfg["num_attention_heads"] * d * dtype_bytes
    kv = tokens * cfg["num_key_value_heads"] * d * dtype_bytes
    return {
        "fwd": 2 * q + 2 * kv,  # q, k, v in; o out
        "dq": 4 * q + 2 * kv,  # q, k, v, o/do in; dq out
        "dkv": 3 * q + 4 * kv,  # q, k, v, do in; dk, dv out
    }[which]


def decode_step_bytes(cfg: dict, live_kv_tokens: float, dtype_bytes: int = 2) -> float:
    """What one decode step must read: every weight a token is multiplied
    by (layers, norms, head; of the embedding only the rows looked up,
    which are left out) and the cache rows of the live contexts."""
    h = cfg["hidden_size"]
    weights = (
        cfg["num_hidden_layers"] * (layer_matmul_params(cfg) + 2 * h)
        + h + h * cfg["vocab_size"]
    )
    return weights * dtype_bytes + live_kv_tokens * kv_bytes_per_token(cfg, dtype_bytes)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> int:
    """One request: the prompt and all but the last completion token go
    through the layers, the head is applied once per completion token, and
    each position attends to everything before it (the window caps it)."""
    w = cfg.get("sliding_window")
    total = prompt_len + max(new_tokens - 1, 0)
    return forward_flops(cfg, total, attended_pairs([total], w), new_tokens)
