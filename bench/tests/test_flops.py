"""The operation and byte counts against hand arithmetic at the
published widths."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.fixture(scope="module")
def granite():
    return json.loads((CONFIGS / "granite-moe-3b-a800m.json").read_text())


def test_granite_routed_flops_per_token(granite):
    # per layer: 2 x (q,k,v,o: 1536 x (1536 + 512 + 512 + 1536) = 6.29 M
    #                 + 8 experts x 3 x 1536 x 512 = 18.87 M
    #                 + router 1536 x 40 = 61 k)
    per_layer = 2 * (6_291_456 + 18_874_368 + 61_440)
    assert flops.dense_flops_per_token(granite) == 32 * per_layer
    assert flops.head_flops(granite) == 2 * 1536 * 49155


def test_causal_prefill_counts_half_the_scores(granite):
    # 2 matmuls x 2 ops x 24 heads x 64 x (2048 x 2049 / 2) pairs
    assert flops.attention_flops(granite, 2048, 2048, causal=True) \
        == 4 * 24 * 64 * 2_098_176
    ops, moved = flops.flash_attention_cost(granite, 4, 2048)
    assert ops == 4 * 4 * 24 * 64 * 2_098_176
    # q and o for 24 heads, k and v for 8, head_dim 64 (not 128), bf16
    assert moved == 4 * 2048 * 64 * (2 * 24 + 2 * 8) * 2


def test_decode_attention_reads_kv_once_per_kv_head(granite):
    ops, moved = flops.decode_attention_cost(granite, 64, 640)
    assert ops == 64 * 4 * 24 * 64 * 640
    assert moved == 64 * (2 * 640 * 8 * 64 + 2 * 24 * 64) * 2


def test_prefill_and_decode_totals(granite):
    B, S = 32, 256
    want = B * (S * 32 * 2 * (6_291_456 + 18_874_368 + 61_440)
                + 32 * 4 * 24 * 64 * (256 * 257 // 2)
                + 2 * 1536 * 49155)
    assert flops.prefill_flops(granite, B, S) == want
    step = 64 * (32 * 2 * (6_291_456 + 18_874_368 + 61_440)
                 + 32 * 4 * 24 * 64 * 300 + 2 * 1536 * 49155)
    assert flops.decode_flops(granite, 64, 300) == step


def test_least_time_names_its_bound():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_time(197e12, 1, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_time(1, 819e9, peak)
    assert (t, bound) == (1.0, "memory")
