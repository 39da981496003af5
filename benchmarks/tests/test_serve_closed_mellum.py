"""The `serve-closed-mellum` mode end to end on the CPU at a tiny size (the server
built by lib/mellum.py over two cache groups, the reference reference/mellum_ref.py,
requests checked beyond the window), its own copy of `serve-closed-model` left as
Moonlight's, `lib/costs_mellum.py` against hand counts, the new readers on hand-made
records and the new entries' contract. Counts and control flow only."""

import json
import math
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "mellum-mixedlen-offline"
TINY = {"architecture": "mellum", "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 8, "max_position_embeddings": 64,
        "moe_intermediate_size": 32, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 8,
        "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "sliding_window": 8,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                               "original_max_position_embeddings": 16, "beta_fast": 32,
                               "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1.0},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
        "tie_word_embeddings": False, "use_sliding_window": True, "vocab_size": 211,
        "assumed": {"initializer_range": 0.08}}


def config():
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": "serve-closed-mellum", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [6, 8, 12, 20, 28], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("mellum"), traffic, seconds=4.0)
    ctx.config = TINY
    module = mode("serve-closed-mellum")
    module.LONG_PROMPT = 20                       # beyond the tiny window of 8 and its ring of 12
    return module, module.run(ctx)


def test_the_mode_serves_mellum_and_judges_it_beyond_the_window(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "Mellum2-12B-A2.5B-Instruct" and facts["checked"] > 0
    assert facts["long_checked"] == min(module.LONG_CHECKED, facts["checked"]) == 2
    assert facts["long_offered"] == 2 and facts["long_floor"] == 20
    assert max(facts["checked_prompt_lens"]) >= 20
    assert facts["share_within_margin"] >= facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["max_logit_deficit"] is None or facts["max_logit_deficit"] <= facts["logit_margin"]
    # on the CPU both groups gather in both programs, and that alone makes the run not correct
    assert facts["decode_attention"] == {"full": "gather", "window": "gather"}
    assert len(run["why_incorrect"]) == 2 and "gathered" in run["why_incorrect"][0] \
        and "a prefill gathered" in run["why_incorrect"][1]
    assert "window group" in facts["prefix_cache"]


def test_both_pools_and_the_rows_attended_are_counted(served):
    _, run = served
    groups = run["cache_groups"]
    assert set(groups) == {"full", "window"}
    assert (groups["full"]["layers"], groups["window"]["layers"]) == (2, 6)
    # a ring of ceil(8 / 4) + 1 = 3 blocks a slot, 4 slots; a full row of 16
    assert groups["window"]["pages_a_slot"] == 3 and groups["window"]["blocks_total"] == 12
    assert groups["full"]["pages_a_slot"] == 16
    assert 0 < groups["window"]["peak_blocks_used"] <= 12
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("decode_rows_full", "decode_rows_window", "decode_moe_passes",
                       "router_tokens")}
    assert moved["decode_rows_full"] > 0 and moved["decode_moe_passes"] % 8 == 0
    # three window layers to a full one, never more than 8 rows each: under 3 x as many
    assert 0 < moved["decode_rows_window"] <= 3 * moved["decode_rows_full"]
    run.update(config=TINY, peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "expert_load_max_over_mean")(run) >= 1.0
    assert reader("layer_metrics", "kv_used_peak_share")(run) > 0
    assert reader("layer_metrics", "kv_window_pool_peak_share")(run) \
        == pytest.approx(100.0 * groups["window"]["peak_blocks_used"] / 12)
    for name in ("attn_window_time_share", "attn_full_time_share", "gqa_decode_hbm_roofline",
                 "attn_prefill_flops_roofline", "moe_decode_hbm_roofline.mellum",
                 "moe_prefill_flops_roofline.mellum", "moe_time_share"):
        assert reader("layer_metrics", name)(run) is None, name       # no trace, no number


def test_the_copy_is_the_modes_own_and_the_shared_reducer_is_untouched(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["mellum"] == ("mellum", "mellum_ref")
    from lib import scope_reduce
    assert module.scopes is not scope_reduce and module.base.scope_reduce is module.scopes
    tf_op = "jit(chunk_impl)/while/body/closed_call/attn/window/pallas_call:"
    assert scope_reduce.scope_of(tf_op) is None
    assert module.scopes.scope_of(tf_op) == "attn/window"
    assert module.scopes.scope_of("jit(prefill_impl)/attn/full/pallas_call:") == "attn/full"
    assert module.scopes.scope_of("jit(prefill_impl)/attn/project/dot_general:") == "attn/project"
    assert module.scopes.scope_of("jit(prefill_impl)/moe/experts/pallas_call:") == "moe/experts"


def test_scope_reduction_on_an_attn_trace(served):
    """`by_scope` over a few device operations named as the chip names them (the
    `tf_op`s of the traced run of PR 33: PERF.md section 5), with the mode's scopes."""
    module, _ = served
    ms = 1_000_000
    modules = [("jit_chunk_impl(1)", 0, 10 * ms), ("jit_prefill_impl(2)", 10 * ms, 20 * ms)]
    ops = [("%paged_attention_grouped.3 = custom-call()", 1 * ms, 2 * ms),
           ("%paged_attention_grouped.4 = custom-call()", 3 * ms, 1 * ms),
           ("%fusion.7 = fusion()", 4 * ms, 1 * ms),
           ("%grouped_swiglu.2 = custom-call()", 5 * ms, 3 * ms),
           ("%flash.1 = custom-call()", 11 * ms, 6 * ms),
           ("%flash.2 = custom-call()", 17 * ms, 2 * ms)]
    tf_op = {ops[0][0]: "jit(chunk_impl)/while/body/closed_call/attn/full/pallas_call:",
             ops[1][0]: "jit(chunk_impl)/while/body/closed_call/attn/window/pallas_call:",
             ops[2][0]: "jit(chunk_impl)/while/body/closed_call/attn/project/dot_general:",
             ops[3][0]: "jit(chunk_impl)/while/body/closed_call/moe/experts/pallas_call:",
             ops[4][0]: "jit(prefill_impl)/attn/full/pallas_call:",
             ops[5][0]: "jit(prefill_impl)/attn/window/pallas_call:"}
    tables = module.scopes.by_scope(ops, modules, 0, 40 * ms, tf_op)
    chunk, prefill = tables["jit_chunk_impl"], tables["jit_prefill_impl"]
    assert chunk["scopes"] == pytest.approx({"attn/full": 2e-3, "attn/window": 1e-3,
                                             "attn/project": 1e-3, "moe/experts": 3e-3})
    assert chunk["kernels"]["paged_attention_grouped"] == pytest.approx(3e-3)
    assert prefill["scopes"] == pytest.approx({"attn/full": 6e-3, "attn/window": 2e-3})
    run = {"scopes": tables, "trace": {"busy_s": 0.02}}
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(40.0)
    assert reader("layer_metrics", "attn_window_time_share")(run) == pytest.approx(15.0)


def test_scope_reduction_on_the_recorded_attn_trace(served):
    """tests/data/mellum_scopes.json.gz: one decode dispatch (8 steps x 8 layers) and
    the shortest prefill of the traced run of `mellum-mixedlen-offline`, seed 3300000707
    (my chip run, PR 33; the kernel was then the one-page walk), every device operation
    with its `tf_op`. The mode's copy of the reducer finds the attention's scopes and
    the grouped kernel; the shared reducer, which does not know `attn/`, finds none."""
    import gzip
    module, _ = served
    with gzip.open(os.path.join(BENCH, "tests", "data", "mellum_scopes.json.gz")) as f:
        rec = json.load(f)
    ops, modules = [tuple(e) for e in rec["ops"]], [tuple(m) for m in rec["modules"]]
    tables = module.scopes.by_scope(ops, modules, 0.0, 1e18, rec["tf_op"])
    chunk, prefill = tables["jit_chunk_impl"], tables["jit_prefill_impl"]
    assert set(chunk["scopes"]) == {"attn/project", "attn/full", "attn/window", "moe/router",
                                    "moe/dispatch", "moe/experts", "moe/combine", "head"}
    assert set(prefill["scopes"]) == set(chunk["scopes"])
    # a decode dispatch: the experts, then the two kinds of attention, whose time is the
    # grouped kernel's (it runs under its layer's scope and nothing else of weight does)
    assert max(chunk["scopes"], key=chunk["scopes"].get) == "moe/experts"
    kernel = chunk["kernels"]["paged_attention_grouped"]
    assert kernel == pytest.approx(0.02718, rel=0.01)
    assert kernel == pytest.approx(chunk["scopes"]["attn/full"] + chunk["scopes"]["attn/window"],
                                   rel=0.01)
    assert chunk["kernels"]["grouped_swiglu"] == pytest.approx(0.0724, rel=0.01)
    # the prefill's attention is the flash forward, banded under attn/window
    assert prefill["kernels"]["_causal_rows_call"] == pytest.approx(
        prefill["scopes"]["attn/full"] + prefill["scopes"]["attn/window"], rel=0.35)
    assert "paged_attention_grouped" not in prefill["kernels"]
    from lib import scope_reduce
    shared = scope_reduce.by_scope(ops, modules, 0.0, 1e18, rec["tf_op"])
    assert not any(name.startswith("attn/") for t in shared.values() for name in t["scopes"])
    run = {"scopes": tables, "trace": {"busy_s": 0.12}}
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(
        100 * (chunk["scopes"]["attn/full"] + prefill["scopes"]["attn/full"]) / 0.12)


def test_costs_mellum_against_hand_counts():
    from lib import costs_mellum as costs
    cfg = config()
    assert costs.expert_params(cfg) == 3 * 2304 * 896 == 6_193_152
    assert costs.router_params(cfg) == 2304 * 64
    assert costs.attention_params(cfg) == 2304 * (4096 + 512 + 512) + 4096 * 2304 == 21_233_664
    # a layer 417.8 M parameters, embedding + head 453 M: 7.59 GB in bfloat16
    layer = 21_233_664 + 147_456 + 64 * 6_193_152
    assert costs.weight_bytes(cfg) == 2 * (2 * 98_304 * 2304 + 8 * layer) == 7_589_855_232
    assert costs.cache_row_bytes(cfg) == 4 * 256 * 2 == 2048
    assert (costs.layers_of(cfg, "full_attention"), costs.layers_of(cfg, "sliding_attention")) == (2, 6)
    # one decode pass that touched all 64 experts: 793 MB of experts, 0.3 MB of router
    assert costs.moe_decode_bytes(cfg, 64, 1) == 2 * (64 * 6_193_152 + 147_456)
    # a token: 8 experts and the router in each of 8 layers, 2 operations a parameter
    assert costs.moe_flops(cfg, 1) == 2.0 * 8 * (8 * 6_193_152 + 147_456)
    # the triangle and the band, by hand at small numbers: 5 rows, window 3: 1+2+3+3+3
    assert costs.attended_pairs(5) == 15 and costs.attended_pairs(5, 3) == 12
    assert costs.attended_pairs(3, 3) == costs.attended_pairs(3) == 6
    assert costs.attended_pairs(2, 3) == 3
    # 15,360 rows: a full layer 118.0 M pairs, a window layer 15.2 M (`4 L W 4096` less the corner)
    assert costs.attended_pairs(15360) == 15360 * 15361 // 2
    assert costs.attended_pairs(15360, 1024) == 15360 * 1024 - 1024 * 1023 // 2
    per_pair = 4.0 * 32 * 128
    assert costs.attention_prefill_flops(cfg, 15360) == per_pair * (
        2 * costs.attended_pairs(15360) + 6 * costs.attended_pairs(15360, 1024))
    # the two full layers' attention at 15,360 rows against two layers' products
    assert costs.attention_prefill_flops(cfg, 15360) / 1e12 == pytest.approx(5.36, abs=0.01)
    assert costs.decode_rows_bytes(cfg, 1000) == 2_048_000


def hand_made_run():
    """A traced window of 6 s: 12 prefills (mean prompt 4160 rows) with 0.6 s under
    `attn/*` and 1.0 s under `moe/*`; 40 decode dispatches of 8 steps with 0.7 s in the
    grouped paged kernel and 3.2 s under `moe/*`; over the window 400 dispatches."""
    scopes = {"jit_prefill_impl": {"scopes": {"attn/window": 0.2, "attn/full": 0.4,
                                              "attn/project": 0.1, "moe/experts": 1.0},
                                   "kernels": {}, "attend_s": 0.0},
              "jit_chunk_impl": {"scopes": {"attn/window": 0.2, "attn/full": 0.3, "moe/experts": 3.2},
                                 "kernels": {"paged_attention_grouped": 0.7}, "attend_s": 0.0}}
    trace = {"busy_s": 5.0, "module_s": {"jit_prefill_impl": 3.0, "jit_chunk_impl": 2.0},
             "module_whole_s": {"jit_prefill_impl": 3.0, "jit_chunk_impl": 2.0},
             "module_runs": {"jit_prefill_impl": 12, "jit_chunk_impl": 40}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((256, 8064))]
    steps = 400 * 8
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100}, "counters1": {"dispatches": 500},
            "model0": {"decode_rows_full": 0, "decode_rows_window": 0,
                       "decode_experts_touched": 0, "decode_moe_passes": 0},
            "model1": {"decode_rows_full": steps * 48 * 2 * 4000, "decode_rows_window": steps * 48 * 6 * 900,
                       "decode_experts_touched": steps * 8 * 64, "decode_moe_passes": steps * 8},
            "cache_groups": {"window": {"blocks_total": 432, "peak_blocks_used": 300}},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_mellum as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "attn_window_time_share")(run) == pytest.approx(100 * 0.4 / 5.0)
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(100 * 0.7 / 5.0)
    # 40 traced dispatches of 8 steps x 48 slots x (2 x 4000 + 6 x 900) rows x 2048 B
    rows = 40 * 8 * 48 * (2 * 4000 + 6 * 900)
    gqa = reader("layer_metrics", "gqa_decode_hbm_roofline")(run)
    assert gqa == pytest.approx(100 * rows * 2048 / 819e9 / 0.7) and 0 < gqa < 100
    flops = 12 * (costs.attention_prefill_flops(cfg, 256) + costs.attention_prefill_flops(cfg, 8064)) / 2
    pre = reader("layer_metrics", "attn_prefill_flops_roofline")(run)
    assert pre == pytest.approx(100 * flops / 197e12 / 0.6) and 0 < pre < 100
    assert reader("layer_metrics", "kv_window_pool_peak_share")(run) == pytest.approx(100 * 300 / 432)
    least_s = 40 * 8 * costs.moe_decode_bytes(cfg, 8 * 64, 8) / 819e9
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.mellum")(run)
    assert moe == pytest.approx(100 * least_s / 3.2) and 0 < moe < 100
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.mellum")(run)
    assert moe_pre == pytest.approx(100 * 12 * costs.moe_flops(cfg, 4160) / 197e12 / 1.0)
    assert 0 < moe_pre < 100
    # a program without the scopes, the counters or the groups (the parent commit):
    # nothing, and no error
    bare = dict(run, scopes={m: dict(t, scopes={"moe/experts": 1.0}, kernels={})
                             for m, t in run["scopes"].items()},
                model0={}, model1={}, cache_groups=None)
    for name in ("attn_window_time_share", "attn_full_time_share", "gqa_decode_hbm_roofline",
                 "attn_prefill_flops_roofline", "kv_window_pool_peak_share",
                 "moe_decode_hbm_roofline.mellum"):
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(dict(run, scopes=None, cache_groups=None)) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="mellum2-12b-a2.5b", traffic="mixedlen-offline", chips=1)
    entry = next(c for c in bench["configs"] if c["name"] == "mellum2-12b-a2.5b")
    body = config()
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers", "layer_types",
                                                   "mlp_layer_types", "max_position_embeddings"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert "chips that share a layer" in body["deployment"] and body["source"] == entry["source"]
    # depth alone is cut: the widths, the heads, the window and the vocabulary as published
    published = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
                 "head_dim": 128, "moe_intermediate_size": 896, "intermediate_size": 7168,
                 "num_experts": 64, "num_experts_per_tok": 8, "sliding_window": 1024,
                 "vocab_size": 98304, "rms_norm_eps": 1e-6, "norm_topk_prob": True}
    assert {k: body[k] for k in published} == published
    assert body["num_hidden_layers"] == 8 == len(body["layer_types"]) == len(body["mlp_layer_types"])
    assert body["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert body["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert reported[-7:] == ["attn_window_time_share", "attn_full_time_share",
                             "gqa_decode_hbm_roofline", "attn_prefill_flops_roofline",
                             "kv_window_pool_peak_share", "moe_decode_hbm_roofline.mellum",
                             "moe_prefill_flops_roofline.mellum"]
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
            "expert_load_max_over_mean", "decode_step_ms.moonlight",
            "prefill_share.moonlight"} <= set(reported)
    # the readers that count with costs_moonlight's keys do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline", "mla_decode_hbm_roofline",
                "mla_attn_time_share", "hc_time_share"} & set(reported)
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "mixedlen-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == "serve-closed-mellum" and mix["clients"] == 56
    assert mix["requests"] == {"prompt_lens": [256, 512, 768, 1024, 3072, 4096, 8192, 15360],
                               "max_new_tokens": [128, 256, 512], "temperature": 0.8}
    engine = mix["engine"]
    assert engine == {"num_slots": 48, "prefill_buckets": [512, 1024, 4096, 8192, 16384],
                      "max_len": 16384, "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (8, 4, 1.0, 6.0)
