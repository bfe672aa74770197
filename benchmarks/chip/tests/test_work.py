"""Work counts against hand-worked shapes, and the table of peaks."""
import json
import os

import pytest

from benchmarks.chip import peaks, work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_paged_attention_counts_the_valid_prefix():
    cfg = _cfg("qwen2.5-3b")          # Hq 16, Hkv 2, head_dim 128
    flops, nbytes = work.paged_attention(cfg, [513, 700])
    assert flops == 4 * 16 * 128 * (513 + 700)
    q_and_out = 2 * 2 * 16 * 128 * 2
    kv = 2 * (513 + 700) * 2 * 128 * 2
    assert nbytes == q_and_out + kv
    # the padded cache (1024 a row) is not what the algorithm needs
    assert work.paged_attention(cfg, [1024, 1024])[1] > nbytes


def test_flash_attention_counts_half_the_causal_square():
    cfg = _cfg("qwen2.5-3b")
    flops, nbytes = work.flash_attention(cfg, 2, 512)
    full = 4 * 2 * 16 * 128 * 512 * 512
    assert flops == full // 2
    assert nbytes == 2 * (2 * 2 * 512 * 16 * 128 + 2 * 2 * 512 * 2 * 128)


def test_qwen_decode_and_prefill_flops():
    cfg = _cfg("qwen2.5-3b")
    per_layer = 2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert work.layer_matmul_params(cfg) == per_layer
    lens = [600] * 4
    assert work.decode_flops(cfg, lens) == (
        2 * 4 * (36 * per_layer + 2048 * 151936) + 36 * 4 * 16 * 128 * 2400)
    assert work.prefill_flops(cfg, 2, 512) == (
        2 * 2 * 512 * 36 * per_layer + 36 * 2 * 2 * 16 * 128 * 512 * 512
        + 2 * 2 * 2048 * 151936)


def test_roofline_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert work.roofline_s(197e12, 1.0, p) == (pytest.approx(1.0), "flops")
    assert work.roofline_s(1.0, 819e9, p) == (pytest.approx(1.0), "bytes")


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
