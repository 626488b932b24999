"""``counts.py`` against numbers worked by hand for both configurations.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q``.
"""

import json
import os

import pytest

from perfbench import counts

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_layer_is_218_1_million():
    # 4096*4096 (q) + 2*4096*1024 (k, v) + 4096*4096 (o) + 3*4096*14336 (mlp)
    assert counts.layer_matmul_params(cfg("mistral7b-d3-train")) == 218_103_808


def test_train_configuration_is_916_5_million():
    # 3 layers with two norms each, embedding and head of 131.072 M each, final norm
    n = counts.n_params(cfg("mistral7b-d3-train"))
    assert n == 3 * (218_103_808 + 8192) + 2 * 131_072_000 + 4096 == 916_484_096


def test_serve_configuration_and_cache():
    c = cfg("mistral7b-d16-serve")
    want = c["num_hidden_layers"] * (218_103_808 + 8192) + 262_144_000 + 4096
    assert counts.n_params(c) == want
    if c["num_hidden_layers"] == 16:
        assert want == 3_751_940_096  # 3.752 B
        assert counts.kv_bytes_per_token(c) == 65_536  # 16 * 2 * 8 * 128 * 2 B
    assert counts.kv_bytes_per_token(c) == c["num_hidden_layers"] * 4096


def test_attended_pairs_window():
    assert counts.attended_pairs([4], None) == 10
    assert counts.attended_pairs([4], 2) == 1 + 2 + 2 + 2
    assert counts.attended_pairs([3, 2], 8) == 6 + 3
    # a full row of 8192 under the 4096 window
    assert counts.attended_pairs([8192], 4096) == 4096 * 4097 // 2 + 4096 * 4096


def test_train_step_flops_by_hand():
    c = cfg("mistral7b-d3-train")
    tokens, pairs = 1000, counts.attended_pairs([1000], 4096)
    mm = 2 * tokens * (3 * 218_103_808 + 4096 * 32000)
    attn = 4 * pairs * 32 * 128
    assert counts.train_step_flops(c, tokens, pairs) == 3 * (mm + attn)


def test_flash_kernels():
    c = cfg("mistral7b-d3-train")
    assert counts.flash_call_flops(c, "fwd", 10) == 2 * 2 * 10 * 32 * 128
    assert counts.flash_call_flops(c, "dq", 10) == 2 * 3 * 10 * 32 * 128
    assert counts.flash_call_flops(c, "dkv", 10) == 2 * 4 * 10 * 32 * 128
    q, kv = 100 * 32 * 128 * 2, 100 * 8 * 128 * 2
    assert counts.flash_call_bytes(c, "fwd", 100) == 2 * q + 2 * kv


def test_decode_step_bytes():
    c = cfg("mistral7b-d16-serve")
    L = c["num_hidden_layers"]
    weights = 2 * (L * (218_103_808 + 8192) + 4096 + 4096 * 32000)
    assert counts.decode_step_bytes(c, 0) == weights
    assert counts.decode_step_bytes(c, 10) == weights + 10 * L * 4096


def test_unknown_device_kind_is_an_error():
    from perfbench import peaks

    assert peaks.peak_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9000")
