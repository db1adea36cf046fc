"""FLOPs, bytes and peaks against hand-computed values at the two models'
shapes."""
import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _shape(name):
    return work.Shape.from_spec(
        json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen2_1p5b_token_and_decode_work():
    s = _shape("qwen2_1p5b")
    # q, o: 1536 x 1536; k, v: 1536 x 256; gate, up, down: 1536 x 8960
    assert s.layer_matmul_params == 2 * 1536 * 1536 + 2 * 1536 * 256 + \
        3 * 1536 * 8960 == 46_792_704
    # one token over one key:
    # 2 * 28 * 46_792_704 + 2 * 1536 * 151_936 + 4 * 28 * 12 * 128 * 1
    assert work.tokens_flops(s, 1, 1) == 3_087_310_848
    assert work.tokens_flops(s, 3, 10) == \
        3 * work.tokens_flops(s, 1, 1) + 4 * 28 * 12 * 128 * 7
    flops, nbytes = work.decode_attention_work(s, 1000)
    assert flops == 28 * 12 * 4 * 128 * 1000 == 172_032_000
    # per layer: K and V of 1000 tokens x 2 heads x 128 in bf16, q and out
    # of 12 heads x 128 in f32
    assert nbytes == 28 * (2 * 1000 * 2 * 128 * 2 + 2 * 12 * 128 * 4) \
        == 29_016_064


def test_olmo_1b_decode_and_prefill_work():
    s = _shape("olmo_1b")
    assert s.layer_matmul_params == 4 * 2048 * 2048 + 3 * 2048 * 8192 \
        == 67_108_864
    _, nbytes = work.decode_attention_work(s, 1000)
    assert nbytes == 16 * (2 * 1000 * 16 * 128 * 2 + 2 * 16 * 128 * 4) \
        == 131_334_144
    assert work.prefix_keys(0, 32) == 528
    assert work.prefix_keys(32, 64) == 64 * 65 // 2 - 528
    flops, nbytes = work.prefill_attention_work(s, 0, 32)
    assert flops == 4 * 16 * 16 * 128 * 528 == 69_206_016
    assert nbytes == 16 * (2 * 32 * 16 * 128 * 2 + 2 * 32 * 16 * 128 * 4) \
        == 12_582_912


def test_roofline_share_and_peaks():
    # 819 GB in one second on a v5e is the byte roofline exactly
    share, bound = work.roofline_share(1.0, 819e9, 1.0, "TPU v5 lite")
    assert share == pytest.approx(100.0) and bound == "bytes"
    share, bound = work.roofline_share(197e12, 1.0, 2.0, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "flops"
    assert work.roofline_share(0, 0, 1.0, "TPU v5 lite") == (None, None)
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9000")
