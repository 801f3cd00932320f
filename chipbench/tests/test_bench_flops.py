"""Operation and byte counts at the published widths, against hand
arithmetic."""

import pytest

from chipbench import flops, spec
from chipbench.weights import param_count

QWEN3 = spec.load_cell("qwen3-1.7b.chat").config
INTERNLM2 = spec.load_cell("internlm2-1.8b.chat").config


@pytest.mark.parametrize("cfg,want", [
    # 151936·2048 embed + 28 × (attention 2048·128·(2·16 + 2·8) + qk norms 2·128
    # + FFN 3·2048·6144 + 2 norms 2·2048) + final norm 2048
    (QWEN3, 151936 * 2048 + 28 * (2048 * 128 * 48 + 256 + 3 * 2048 * 6144 + 4096) + 2048),
    # 2 × 92544·2048 (embed, untied head) + 24 × (2048·128·48 + 3·2048·8192 + 4096) + 2048
    (INTERNLM2, 2 * 92544 * 2048 + 24 * (2048 * 128 * 48 + 3 * 2048 * 8192 + 4096) + 2048),
], ids=["qwen3-1.7b", "internlm2-1.8b"])
def test_param_count_at_published_widths(cfg, want):
    assert param_count(cfg) == want
    assert want in (1_720_574_976, 1_889_110_016)


@pytest.mark.parametrize("cfg,per_token", [
    (QWEN3, 2 * (28 * (2048 * 128 * 48 + 3 * 2048 * 6144) + 2048 * 151936)),
    (INTERNLM2, 2 * (24 * (2048 * 128 * 48 + 3 * 2048 * 8192) + 2048 * 92544)),
], ids=["qwen3-1.7b", "internlm2-1.8b"])
def test_decode_flops_per_token(cfg, per_token):
    # two rows at 10 and 30 cached positions: matmuls per row, attention
    # 4·H·hd per position per layer
    layers = cfg["num_hidden_layers"]
    want = 2 * per_token + layers * 4 * 16 * 128 * (10 + 30)
    assert flops.decode_flops(cfg, [10, 30]) == want
    assert per_token == pytest.approx(3.44e9 if cfg is QWEN3 else 3.40e9, rel=0.01)


def test_prefill_flops():
    s = 256
    layer = 2 * (2048 * 128 * 48 + 3 * 2048 * 6144) * s + 4 * 16 * 128 * s * (s + 1) // 2
    assert flops.prefill_flops(QWEN3, s) == 28 * layer + 2 * 2048 * 151936


def test_decode_attention_bytes_and_roofline():
    valid = [100, 1, 1024]
    # K and V: 2 · 8 kv heads · 128 · 2 B per position; q and out: 2 · 16 · 128 · 2 B per row
    want = 2 * 8 * 128 * 2 * 1125 + 2 * 16 * 128 * 2 * 3
    assert flops.decode_attn_bytes_per_layer(QWEN3, valid) == want
    peak = flops.peaks("TPU v5 lite")
    least = flops.decode_attn_least_s(QWEN3, valid, peak)
    assert least == pytest.approx(28 * want / 819e9)  # memory-bound: 4·16·128 FLOPs per 4 KiB
    assert flops.kv_bytes_per_token(QWEN3) == 114_688
    assert flops.kv_bytes_per_token(INTERNLM2) == 98_304


def test_peaks_are_keyed_by_device_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")
