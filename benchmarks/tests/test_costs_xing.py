"""lib/costs_xing.py against a hand count at the published widths, the
configuration file against the catalog's published keys, and the readers this
cell shares with Moonlight's against this configuration's own arithmetic."""

import json
import os

import pytest

from lib import costs_moonlight, costs_xing as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
CUT = {"num_hidden_layers": 6, "first_k_dense_replace": 1, "num_nextn_predict_layers": 0,
       "max_position_embeddings": 16384}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_every_published_key_is_there_and_only_four_are_cut(cfg):
    assert cfg["reduced"] == list(CUT)
    assert {k: cfg[k] for k in PUBLISHED} == dict(PUBLISHED, **CUT)
    for key in CUT:
        assert str(PUBLISHED[key]) + " published" in cfg["reduced_note"][key]
    # a whole period and at least four of the layers after the leading dense one
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert "chips that share a layer: 1" in cfg["deployment"]
    assert cfg["source"].endswith("XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    # what stands on recollection says so, item by item
    mixer = cfg["assumed"]["mixer"]
    assert set(mixer) == {"one_per_sublayer", "norm_before_phi", "h_pre", "h_post", "h_res",
                          "update", "seeded_values"}
    assert all("recalled" in mixer[k] or "assumed" in mixer[k] for k in mixer if k != "seeded_values")
    assert "NOTHING below was checked against a source" in cfg["assumed"]["provenance"]
    assert "recalled" in cfg["assumed"]["yarn"] and cfg["dtype"]["serving"]


def test_the_layers_sizes(cfg):
    # W_qa 3584 x 768, W_qb 768 x 32*192, W_kva 3584 x 576, W_kvb 512 x 32*256, W_o 32*128 x 3584
    assert costs.attention_params(cfg) == 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064
    assert costs.attention_params(cfg) == 28_409_856
    # phi: 4 x 3584 rows onto 4 + 4 + 16 coefficients
    assert costs.mixer_params(cfg) == 14_336 * 24 == 344_064
    assert costs_moonlight.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert costs_moonlight.shared_params(cfg) == 11_010_048
    assert costs_moonlight.router_params(cfg) == 3584 * 64
    assert costs_moonlight.expert_layers(cfg) == 5
    # ISSUE 31's count: 1.879 (embedding + head) + 0.256 (dense layer) + 5 x 1.490 GB
    dense = 28_409_856 + 3 * 3584 * 9216 + 2 * 344_064
    expert = 28_409_856 + 2 * 344_064 + 65 * 11_010_048 + 3584 * 64
    assert costs.weight_bytes(cfg) == 2 * (2 * 131072 * 3584 + dense + 5 * expert)
    assert costs.weight_bytes(cfg) == pytest.approx(9.585e9, rel=1e-4)
    # the floor, 1 + 4
    assert costs.weight_bytes(dict(cfg, num_hidden_layers=5)) == pytest.approx(8.095e9, rel=1e-4)


def test_the_mixers_least_traffic(cfg):
    # a token a sublayer: 14336 values read, 14336 + 3584 read, 14336 written, 2 B each
    assert costs.mixer_bytes(cfg, 1) == 12 * 2 * (14_336 + 14_336 + 3_584 + 14_336)
    assert costs.mixer_bytes(cfg, 1) == 12 * 93_184
    assert costs.mixer_flops(cfg, 1) == 12 * 2 * 14_336 * 24
    # a 16384-row prompt: 18.3 GB through the mixers, 22 ms at 819 GB/s
    assert costs.mixer_bytes(cfg, 16384) / 819e9 == pytest.approx(0.02237, rel=1e-3)


def test_moonlights_readers_count_this_configuration_right(cfg):
    # a pass that touched 40 experts: theirs, the ONE shared expert, the router
    assert costs_moonlight.moe_decode_bytes(cfg, 40, 1) == 2 * (41 * 11_010_048 + 229_376)
    # one token through the five expert layers: 4 routed + 1 shared experts' worth
    assert costs_moonlight.moe_flops(cfg, 1) == 2.0 * 5 * (5 * 11_010_048 + 229_376)
    # a cache row is Moonlight's: 512 + 64 bfloat16 values, in six layers
    assert costs_moonlight.latent_row_bytes(cfg) == 1152
    assert costs_moonlight.mla_decode_bytes(cfg, 1000) == 6 * 1152 * 1000
