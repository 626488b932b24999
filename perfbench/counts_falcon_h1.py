"""Operations and bytes Falcon-H1 needs, from shapes alone: what
``perfbench/counts.py`` is to the Mistral cells. ``cfg`` is the
configuration file's dict (the keys of the public ``config.json``)."""

from __future__ import annotations

from perfbench import counts


def conv_dim(cfg: dict) -> int:
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mixer_matmul_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["mamba_d_ssm"]
    return h * (d + conv_dim(cfg) + cfg["mamba_n_heads"]) + d * h


def mixer_small_params(cfg: dict) -> int:
    """Convolution weight and bias, the gated norm's scale, dt_bias,
    A_log and D."""
    return (conv_dim(cfg) * (cfg["mamba_d_conv"] + 1) + cfg["mamba_d_ssm"]
            + 3 * cfg["mamba_n_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied by: attention and
    MLP as ``counts.layer_matmul_params`` reckons them, and the mixer's
    two projections."""
    return counts.layer_matmul_params(cfg) + mixer_matmul_params(cfg)


def layer_params(cfg: dict) -> int:
    return layer_matmul_params(cfg) + mixer_small_params(cfg) + 2 * cfg["hidden_size"]


def n_params(cfg: dict) -> int:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * v * h + h


def scan_flops_per_token(cfg: dict, decode: bool) -> int:
    """The selective scan's own FLOPs a token and layer, beside the
    projections. Decode, one step of the recurrence: decay and input into
    the state and the read by C, 4 h p N, and the D term, 2 h p. Prefill,
    the chunked form at ``mamba_chunk_size`` Q: C.B^T inside a chunk
    2 g N Q, the masked quadratic form over x 2 h p Q, and the chunk's
    state in and out 4 h p N."""
    hp = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    n = cfg["mamba_d_state"]
    if decode:
        return 4 * hp * n + 2 * hp
    q = cfg["mamba_chunk_size"]
    return 2 * cfg["mamba_n_groups"] * n * q + 2 * hp * q + 4 * hp * n


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> int:
    """One request, as ``counts.serve_flops`` reckons it (the prompt and
    all but the last completion token through the layers, the head once a
    completion token, causal attention over everything before), with the
    mixer's projections among the layer's weights and the scan's FLOPs:
    the chunked form for the prompt, the step for each decoded token."""
    decoded = max(new_tokens - 1, 0)
    total = prompt_len + decoded
    mm = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    head = cfg["hidden_size"] * cfg["vocab_size"]
    scan = cfg["num_hidden_layers"] * (
        prompt_len * scan_flops_per_token(cfg, False)
        + decoded * scan_flops_per_token(cfg, True)
    )
    pairs = counts.attended_pairs([total], None)
    return (2 * total * mm + 2 * new_tokens * head
            + counts.attention_fwd_flops(cfg, pairs) + scan)


def ssm_state_bytes(cfg: dict, slots: int) -> int:
    """One layer's recurrent state of every slot, float32."""
    return (slots * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"] * 4)


def conv_window_bytes(cfg: dict, slots: int, dtype_bytes: int = 2) -> int:
    return slots * (cfg["mamba_d_conv"] - 1) * conv_dim(cfg) * dtype_bytes


def ssm_step_bytes(cfg: dict, slots: int) -> int:
    """What one decode step's state updates must move: every layer's
    state of every slot read once and written once."""
    return cfg["num_hidden_layers"] * 2 * ssm_state_bytes(cfg, slots)


def decode_step_bytes(cfg: dict, live_kv_tokens: float, slots: int,
                      dtype_bytes: int = 2) -> float:
    """What one decode step must move: every weight a token is multiplied
    by and the small ones (layers, final norm, head; of the embedding only
    the rows looked up, which are left out), the K/V rows of the live
    contexts, and the recurrent state and convolution window of every
    slot, read once and written once."""
    h = cfg["hidden_size"]
    weights = cfg["num_hidden_layers"] * layer_params(cfg) + h + h * cfg["vocab_size"]
    recurrent = cfg["num_hidden_layers"] * 2 * (
        ssm_state_bytes(cfg, slots) + conv_window_bytes(cfg, slots, dtype_bytes))
    return (weights * dtype_bytes
            + live_kv_tokens * counts.kv_bytes_per_token(cfg, dtype_bytes) + recurrent)
