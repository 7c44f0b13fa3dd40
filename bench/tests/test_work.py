"""The yardstick's operation and byte counts against hand counts at both
configurations' published widths."""
import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,per_token", [
    # 24 x 2 x (896*896 + 2*896*128 + 896*896 + 3*896*4864)
    ("qwen25-0.5b-awq", 715_653_120),
    # 28 x 2 x (3584*3584 + 2*3584*512 + 3584*3584 + 3*3584*18944)
    ("qwen25-7b-awq", 13_050_576_896),
])
def test_token_flops(name, per_token):
    assert work.token_flops(cfg(name)) == per_token


@pytest.mark.parametrize("name,head", [
    ("qwen25-0.5b-awq", 2 * 896 * 151_936),
    ("qwen25-7b-awq", 2 * 3584 * 152_064),
])
def test_head_flops(name, head):
    assert work.head_flops(cfg(name)) == head


def test_awq_matmul_hand_count():
    # 0.5B up projection at a width-8 step of 32 rows: M 256, K 896, N 4864
    flops, nbytes = work.awq_matmul(256, 896, 4864, 64)
    assert flops == 2 * 256 * 896 * 4864
    assert nbytes == (256 * 896 * 2          # x, bf16
                      + 896 * 4864 // 2      # int4 codes
                      + 14 * 4864 * 5        # f32 scale + int8 zero a group
                      + 256 * 4864 * 4)      # f32 out


def test_awq_matmul_7b_down_decode_is_memory_bound():
    flops, nbytes = work.awq_matmul(32, 18944, 3584, 64)
    assert nbytes == (32 * 18944 * 2 + 18944 * 3584 // 2
                      + 296 * 3584 * 5 + 32 * 3584 * 4)
    _, bound = work.roofline_s(flops, nbytes, work.peaks("TPU v5 lite"))
    assert bound == "memory"


def test_paged_attention_hand_count():
    # 0.5B decode step: 32 rows of one query, 2 KV heads, 7 groups padded
    # to 8 query rows, 8 pages of 16 positions, head size 64
    flops, nbytes = work.paged_attention_chunk(32, 1, 2, 7, 64, 8, 16)
    assert flops == 4 * 32 * 2 * 8 * 128 * 64
    assert nbytes == 32 * 2 * 128 * (2 * 64 + 8) + 32 * 2 * 8 * 64 * 6
    # 7B chunk step: 64 queries x 7 groups = 448 rows, 128 pages
    flops, _ = work.paged_attention_chunk(32, 64, 4, 7, 128, 128, 16)
    assert flops == 4 * 32 * 4 * 448 * 2048 * 128


def test_roofline_bound_and_unknown_chip():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.roofline_s(197e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.roofline_s(1.0, 819e9, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(ValueError):
        work.peaks("TPU v9")
