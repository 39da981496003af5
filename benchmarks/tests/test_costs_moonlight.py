"""lib/costs_moonlight.py against a hand count at the published widths."""

import json
import os

import pytest

from lib import costs_moonlight as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
        return json.load(f)


def test_the_layers_sizes(cfg):
    # one expert: three matrices of 2048 x 1408
    assert costs.expert_params(cfg) == 3 * 2048 * 1408 == 8_650_752
    assert costs.shared_params(cfg) == 2 * 8_650_752
    assert costs.router_params(cfg) == 2048 * 64
    # attention: query 2048 x 16*192, latent down 2048 x 576, up 512 x 16*256, out 16*128 x 2048
    assert costs.attention_params(cfg) == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert costs.expert_layers(cfg) == 6
    # ISSUE 27's count: 1.342 + 0.166 + 6 x 1.170 GB = 8.53 GB (it leaves the
    # attention of the dense layer to the 0.166)
    assert costs.weight_bytes(cfg) == pytest.approx(8.53e9, rel=0.005)


def test_decode_bytes_and_prefill_operations(cfg):
    # a pass that touched 61 experts: their bytes, the shared expert, the router
    assert costs.moe_decode_bytes(cfg, 61, 1) == 2 * (61 * 8_650_752 + 2 * 8_650_752 + 131_072)
    # one token through the six expert layers: 6 routed + 2 shared experts' worth
    assert costs.moe_flops(cfg, 1) == 2.0 * 6 * (8 * 8_650_752 + 131_072)
    # a cache row: 512 + 64 bfloat16 values; 1000 live positions in 7 layers
    assert costs.latent_row_bytes(cfg) == 1152
    assert costs.mla_decode_bytes(cfg, 1000) == 7 * 1152 * 1000
