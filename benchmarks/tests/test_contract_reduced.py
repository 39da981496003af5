"""The rule that replaces test_contract.py's `reduced == []` (that file is
the benchmark's and predates a reduced configuration; the stale assertion is
handed to the next `benchmark` issue in PERF.md, section 7): a configuration's
two `reduced` lists agree, and the file states, for each reduced key, what was
published and which deployment the cut stands for."""

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
WIDTHS = ("_dim", "_rank", "hidden_size", "intermediate_size", "num_experts_per_tok")


def test_reduced_lists_agree_and_state_what_was_published():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert key in body and not key.endswith(WIDTHS), key      # never a width
            assert key in body["reduced_note"], f"{c['name']}: what was {key} as published?"
        if c["reduced"]:
            assert "chips that share a layer" in body["deployment"]


def test_moonlight_is_cut_in_depth_alone():
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.json")) as f:
        body = json.load(f)
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 7 and "27 published" in body["reduced_note"]["num_hidden_layers"]
    # a whole period and at least four of the layers after the dense one
    assert body["num_hidden_layers"] - body["first_k_dense_replace"] >= 4
    published = {"hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "intermediate_size": 11264, "moe_intermediate_size": 1408,
                 "n_routed_experts": 64, "n_shared_experts": 2, "num_experts_per_tok": 6,
                 "vocab_size": 163840, "max_position_embeddings": 8192,
                 "routed_scaling_factor": 2.446, "rope_theta": 50000}
    assert {k: body[k] for k in published} == published
