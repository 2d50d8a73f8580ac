"""The benchmark's own counts: model FLOPs and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import harness, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_resnet50_flops_against_the_hand_count():
    ref = harness.load_module(CONFIGS / "resnet50.py", "t_resnet50")
    # 3 (forward and backward) x 2 FLOP per MAC x 4.1 GMAC x 256 images
    hand = 3 * 2 * 4.1e9 * 256
    got = ref.model_flops(_sizes("resnet50"), {"global_batch": 256})
    assert got == pytest.approx(hand, rel=5e-3)
    assert ref.conv_macs(_sizes("resnet50")) == 4_089_184_256


def test_internlm2_3l_flops_against_the_hand_count():
    ref = harness.load_module(CONFIGS / "internlm2_1_8b_3l.py", "t_lm")
    d, ff, v = 2048, 8192, 92544
    per_layer = d * d + 2 * d * 1024 + d * d + 3 * d * ff  # q, k, v, o, mlp
    n = 3 * per_layer + v * d  # layers and LM head; no embedding lookup
    assert ref.matmul_params(_sizes("internlm2_1_8b_3l")) == n
    tokens = 2 * 4096
    hand = (6 * n + 12 * 3 * 16 * 128 * 4096) * tokens
    got = ref.model_flops(_sizes("internlm2_1_8b_3l"),
                          {"global_batch": 2, "seq_len": 4096})
    assert got == hand
    assert got == pytest.approx(21.06e12, rel=1e-3)


def test_peaks_of_v5e():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_peaks_refuse_an_unknown_kind():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")
