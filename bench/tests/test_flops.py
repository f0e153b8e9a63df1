"""bench/flops.py against counts made by hand at both configurations."""

import pytest

from bench import flops
from bench.families import dense
from bench.tests.tiny import load_config

# per layer: q and o projections, k and v projections, three MLP mats
HAND = {
    "minicpm-2b": {
        "layer": 2 * 2304 * 36 * 64 + 2 * 2304 * 36 * 64 + 3 * 2304 * 5760,  # 61,046,784
        "dense_token": 2 * (40 * 61_046_784 + 2304 * 122_753),  # 5,449,388,544
        "attn_position": 4 * 36 * 64 * 40,  # 368,640
    },
    "mistral-nemo-12b-d10": {
        "layer": 2 * 5120 * 32 * 128 + 2 * 5120 * 8 * 128 + 3 * 5120 * 14336,  # 272,629,760
        "dense_token": 2 * (10 * 272_629_760 + 5120 * 131_072),  # 6,794,772,480
        "attn_position": 4 * 32 * 128 * 10,  # 163,840
    },
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_model_counts(name):
    conf = load_config(name)
    hand = HAND[name]
    assert dense.layer_matmul_params(conf) == hand["layer"]
    assert dense.flops_per_token(conf) == hand["dense_token"]
    assert dense.attention_flops(conf, 1) == hand["attn_position"]
    assert hand["dense_token"] == {"minicpm-2b": 5_449_388_544, "mistral-nemo-12b-d10": 6_794_772_480}[name]


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_chunk_is_causal_sum(name):
    conf = load_config(name)
    hand = HAND[name]
    # 4 tokens after 10 cached: they attend 11 + 12 + 13 + 14 = 50 positions
    assert flops.prefill_chunk_flops(conf, 4, 10) == 4 * hand["dense_token"] + 50 * hand["attn_position"]
    assert flops.step_flops(conf, [(4, 10)], [7, 9]) == (4 * hand["dense_token"] + 50 * hand["attn_position"] + 2 * hand["dense_token"] + 16 * hand["attn_position"])


def test_paged_kernel_cost_by_hand():
    conf = load_config("minicpm-2b")
    # one row attending 100 positions: 7 blocks of 16, K and V, bf16
    per_layer_bytes = 2 * 7 * 16 * 2304 * 2 + 2 * 2304 * 2
    fl, by = flops.paged_kernel_cost(conf, [100], 16)
    assert fl == 4 * 36 * 64 * 100 * 40 == 36_864_000
    assert by == per_layer_bytes * 40 == 41_656_320
    nemo = load_config("mistral-nemo-12b-d10")
    fl, by = flops.paged_kernel_cost(nemo, [16, 17], 16)
    assert fl == 4 * 32 * 128 * (16 + 17) * 10
    assert by == (2 * (1 + 2) * 16 * 1024 * 2 + 2 * 2 * 4096 * 2) * 10
