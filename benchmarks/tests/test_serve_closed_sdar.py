"""The `serve-closed-sdar` mode end to end on the CPU at a tiny size (the server built by
lib/sdar.py, generation by diffusion over blocks, the served blocks REPLAYED by
reference/sdar_ref.py and the five WRONG references told from the true one), its own copy
of `serve-closed-model` left as Moonlight's, the configuration's widths against the
catalog's, `lib/costs_sdar.py` against hand counts, the new readers on a hand-made run and
the new entries' contract. Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "sdar-blockgen-offline"
TINY = {"architecture": "sdar_moe", "attention_bias": False, "decoder_sparse_step": 1,
        "head_dim": 16, "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "max_position_embeddings": 64, "mlp_only_layers": [], "moe_intermediate_size": 32,
        "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 2, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 211,
        "assumed": {"initializer_range": 0.08, "generation": {
            "block_length": 4, "denoising_steps": 4, "confidence_threshold": 0.9,
            "remasking_strategy": "low_confidence_dynamic", "mask_token_id": 210}}}
NEW = ("denoise_passes_per_block", "denoise_pass_ms", "unmask_time_share",
       "gqa_decode_hbm_roofline.sdar", "attn_prefill_flops_roofline.sdar",
       "moe_decode_hbm_roofline.sdar", "moe_prefill_flops_roofline.sdar")


def config():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": "serve-closed-sdar", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [8, 12, 16, 20, 28], "max_new_tokens": [8, 12],
                            "temperature": 0.8, "token_ids_below": 200}}
    ctx = Ctx(tmp_path_factory.mktemp("sdar"), traffic, seconds=5.0)
    ctx.config = TINY
    module = mode("serve-closed-sdar")
    module.PAD_TO = 32
    # the server computes in bfloat16, and a tiny vocabulary's logits and confidences lie
    # close: the limits' constants are the chip's (PERF.md); here they are this size's
    module.POSITION_MARGIN, module.MIN_POSITION_SHARE = 0.1, 0.9
    module.base.LOGIT_MARGIN, module.MIN_JUDGED_WITHIN = 0.05, 0.9
    module.DRIFT_MARGIN = 0.05
    os.environ["SDAR_WRONG_REFERENCE"] = "all"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["SDAR_WRONG_REFERENCE"]


def test_the_mode_serves_block_diffusion_and_replays_it(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "SDAR-30B-A3B-Chat" and facts["checked"] == 12
    assert facts["long_checked"] == module.LONG_CHECKED == 6
    assert min(facts["checked_prompt_lens"][-6:]) >= facts["long_floor"]
    assert facts["fails"] == [] and facts["positions"] > 0 and facts["passes"] > 0
    assert facts["judged"] + facts["left_out"] == facts["positions"]
    assert facts["share_within_margin"] >= 0.97
    assert facts["position_within_margin"] >= 0.9 and facts["passes_judged"] > 0
    assert facts["confidence_within_margin"] >= 0.9
    assert 0 < facts["confidence_drift_rms"] < 0.05
    assert set(facts["limits"]) >= {"drift_margin", "position_margin", "min_judged_within"}
    # flat logits: every block the static schedule, a position a pass
    assert facts["fixed_at_counts"] == [facts["positions"] // 4] * 4
    # (blocks in flight when the counters were read are passes without a commit yet)
    assert facts["diffusion"]["passes_per_block"] == pytest.approx(5.0, rel=0.02)
    assert facts["tokens_fixed_by_threshold"] == 0 < facts["tokens_fixed_by_rank"]
    # on the CPU the pass and the prefill gather, and that alone makes the run not correct
    assert facts["decode_attention"] == {"full": "gather"}
    assert len(run["why_incorrect"]) == 2 and "gathered" in run["why_incorrect"][0] \
        and "a prefill gathered" in run["why_incorrect"][1]
    assert facts["prefix_cache"].startswith("off")
    # every request's record holds the pass that fixed each of its tokens
    ok = [r for r in run["measured"] if r["greedy"]]
    assert ok and all(len(r["fixed_at"]) == len(r["confidence"]) == len(r["output"])
                      == r["max_new_tokens"] for r in ok)
    assert all(max(map(max, (r["output"] for r in ok))) < 210 for r in ok)


def test_each_wrong_program_fails_a_limit_at_the_tiny_size(served):
    """float32 on both sides here, so `float8` is the one that moves little; the four that
    change the mathematics or the rule each fail a limit (the chip's readings: PERF.md)."""
    _, run = served
    wrong = run["facts"]["wrong_references"]
    assert set(wrong) == {"float8", "causal", "no_commit", "left_to_right", "block8"}
    assert wrong["left_to_right"]["fails"] == ["position"]
    # its tokens and its confidences are the true ones
    assert wrong["left_to_right"]["share_within_margin"] == 1.0
    assert wrong["left_to_right"]["confidence_drift_rms"] == 0.0
    for name in ("causal", "no_commit", "block8"):
        assert wrong[name]["fails"], (name, wrong[name])
    assert wrong["float8"]["positions"] == run["facts"]["positions"]


def test_the_counters_and_the_appended_readers_read_a_pass(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("block_passes", "blocks_committed", "decode_rows_full",
                       "decode_moe_passes", "router_tokens")}
    # a window cuts the blocks at its edges: five passes a block but for those
    assert moved["block_passes"] == pytest.approx(5 * moved["blocks_committed"], rel=0.02)
    assert moved["decode_moe_passes"] % 2 == 0 and moved["decode_rows_full"] > 0
    run.update(config=TINY, peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "expert_load_max_over_mean")(run) >= 1.0
    assert reader("layer_metrics", "kv_used_peak_share")(run) > 0
    assert reader("layer_metrics", "tokens_per_dispatch.offline")(run) > 0
    assert reader("layer_metrics", "prefills_per_chunk")(run) > 0
    assert 2.0 <= reader("layer_metrics", "denoise_passes_per_block")(run) <= 5.1
    for name in NEW[1:] + ("attn_full_time_share", "moe_time_share", "prefill_share.moonlight"):
        assert reader("layer_metrics", name)(run) is None, name       # no trace, no number


def test_the_copy_is_the_modes_own_and_the_stage_tables_feed_the_scope_readers(served, monkeypatch):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["sdar_moe"] == ("sdar", "sdar_ref")
    from lib import stage_times
    ms = 1_000_000
    modules = [("jit_chunk_impl(1)", 0, 10 * ms), ("jit_prefill_impl(2)", 10 * ms, 10 * ms)]
    ops = [("%paged_attention_grouped.3 = f32[] custom-call()", 1 * ms, 2 * ms),
           ("%fusion.7 = f32[] fusion()", 3 * ms, 1 * ms),
           ("%fusion.8 = f32[] fusion()", 4 * ms, 1 * ms),
           ("%grouped_swiglu.2 = f32[] custom-call()", 5 * ms, 3 * ms),
           ("%_causal_rows_call.1 = f32[] custom-call()", 11 * ms, 6 * ms)]
    tf_op = {ops[0][0]: "jit(chunk_impl)/while/body/closed_call/attn/full/pallas_call:",
             ops[1][0]: "jit(chunk_impl)/while/body/loop/unmask/sort:",
             ops[2][0]: "jit(chunk_impl)/while/body/loop/sample/reduce_max:",
             ops[3][0]: "jit(chunk_impl)/while/body/closed_call/moe/experts/pallas_call:",
             ops[4][0]: "jit(prefill_impl)/attn/full/pallas_call:"}
    tables = stage_times.by_stage(ops, modules, 0, 40 * ms, tf_op)
    monkeypatch.setattr(stage_times, "tables_at", lambda path: tables)
    scopes = module.StageTables.reduce_dir("anywhere")
    chunk, prefill = scopes["jit_chunk_impl"], scopes["jit_prefill_impl"]
    assert chunk["scopes"] == pytest.approx({"attn/full": 2e-3, "loop/unmask": 1e-3,
                                             "loop/sample": 1e-3, "moe/experts": 3e-3})
    assert chunk["kernels"] == pytest.approx({"paged_attention_grouped": 2e-3,
                                              "grouped_swiglu": 3e-3})
    assert prefill["scopes"] == pytest.approx({"attn/full": 6e-3})
    run = {"scopes": scopes, "trace": {"busy_s": 0.02}}
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(40.0)
    assert reader("layer_metrics", "moe_time_share")(run) == pytest.approx(15.0)
    monkeypatch.setattr(stage_times, "tables_at", lambda path: None)
    assert module.StageTables.reduce_dir("anywhere") is None


def test_the_accepted_stage_readers_the_cell_lists_read_a_pass(monkeypatch):
    """`head_time_share.offline`, `norm_time_share.offline` and `stage_named_share.offline`
    (PR 35's, appended to by this cell) and `unmask_time_share` read the block pass's
    stages from a traced run's tables: the head over S x B rows, the norms, what lies
    under no stage."""
    import sys
    from lib import stage_times
    ms = 1_000_000
    modules = [("jit_chunk_impl(1)", 0, 20 * ms)]
    ops = [("%fusion.1 = f32[] fusion()", 1 * ms, 3 * ms),
           ("%fusion.2 = f32[] fusion()", 4 * ms, 1 * ms),
           ("%fusion.3 = f32[] fusion()", 5 * ms, 2 * ms),
           ("%fusion.4 = f32[] fusion()", 7 * ms, 10 * ms),
           ("%copy.5 = f32[] copy()", 17 * ms, 4 * ms)]
    body = "jit(chunk_impl)/while/body/"
    tf_op = {ops[0][0]: body + "closed_call/head/dot_general:",
             ops[1][0]: body + "closed_call/norm/mul:",
             ops[2][0]: body + "loop/sample/reduce_max:",
             ops[3][0]: body + "closed_call/moe/experts/dot:"}
    tables = stage_times.by_stage(ops, modules, 0, 40 * ms, tf_op)
    monkeypatch.setattr(stage_times, "tables_at", lambda path: tables)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    run = {"trace": {"busy_s": 0.02}, "model1": {"blocks_committed": 5}}
    assert reader("layer_metrics", "head_time_share.offline")(run) == pytest.approx(15.0)
    assert reader("layer_metrics", "norm_time_share.offline")(run) == pytest.approx(5.0)
    assert reader("layer_metrics", "unmask_time_share")(run) == pytest.approx(10.0)
    assert reader("layer_metrics", "stage_named_share.offline")(run) == pytest.approx(80.0)
    for name in ("head_time_share.offline", "norm_time_share.offline", "stage_named_share.offline"):
        assert reader("layer_metrics", name)(dict(run, trace=None)) is None


def test_the_configurations_widths_are_the_catalogs():
    body = config()
    catalog = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
               "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
               "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
               "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts": 128, "num_experts_per_tok": 8, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
               "sliding_window": None, "tie_word_embeddings": False,
               "use_sliding_window": False, "vocab_size": 151936}
    assert {k: body[k] for k in catalog} == catalog
    assert body["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (body["num_hidden_layers"], body["max_position_embeddings"]) == (6, 4096)
    assert body["published"] == {"num_hidden_layers": 48, "max_position_embeddings": 32768}
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert "chips that share a layer: 1" in body["deployment"]
    assumed = body["assumed"]
    assert assumed["generation"] == {
        "block_length": 4, "denoising_steps": 4, "confidence_threshold": 0.9,
        "remasking_strategy": "low_confidence_dynamic", "mask_token_id": 151669}
    assert {"qk_norm", "unshifted_logits", "mask_logit", "generation_note", "provenance"} \
        <= set(assumed)
    from lib import sdar
    cfg = sdar.sdar_config(body)
    assert (cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.moe_intermediate,
            cfg.n_routed_experts, cfg.experts_per_tok, cfg.layers, cfg.vocab_size) \
        == (2048, 32, 4, 128, 768, 128, 8, 6, 151936)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (4, 4, 151669)
    with pytest.raises(ValueError, match="written for"):
        sdar.sdar_config(dict(body, decoder_sparse_step=2))


def test_costs_sdar_against_hand_counts():
    from lib import costs_sdar as costs
    cfg = config()
    assert costs.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert costs.router_params(cfg) == 2048 * 128 == 262_144
    assert costs.attention_params(cfg) == 2048 * (4096 + 512 + 512) + 4096 * 2048 == 18_874_368
    layer = 18_874_368 + 262_144 + 128 * 4_718_592
    assert layer == 623_116_288
    assert costs.weight_bytes(cfg) == 2 * (2 * 151_936 * 2048 + 6 * layer) == 8_722_055_168
    assert costs.weight_bytes(cfg) == pytest.approx(cfg["bytes"]["weights_bf16"], rel=1e-3)
    assert costs.cache_row_bytes(cfg) == 4 * 256 * 2 == 2048
    # one pass of a layer that touched all 128 experts: 1.208 GB of experts, 0.5 MB of router
    assert costs.moe_decode_bytes(cfg, 128, 1) == 2 * (128 * 4_718_592 + 262_144)
    assert costs.moe_flops(cfg, 1) == 2.0 * 6 * (8 * 4_718_592 + 262_144)
    # the block-causal triangle by hand: 8 rows in blocks of 4: 4 x 4 + 4 x 8
    assert costs.attended_pairs(8, 4) == 48 and costs.attended_pairs(4, 4) == 16
    assert costs.attended_pairs(12, 4) == 4 * (4 + 8 + 12)
    assert costs.whole_blocks(cfg, 1027) == 1024 and costs.whole_blocks(cfg, 3) == 0
    per_pair = 4.0 * 32 * 128
    assert costs.attention_prefill_flops(cfg, 3074) == per_pair * 6 * (16 * 768 * 769 // 2)
    assert costs.decode_rows_bytes(cfg, 1000) == 2_048_000


def hand_made_run():
    """A traced window of 6 s: 12 prefills (mean prompt 1664 rows) with 0.05 s under
    `attn/full` and 0.4 s under `moe/*`; 50 dispatches of 8 passes with 1.5 s in the
    block-row kernel and 4.0 s under `moe/*`; over the window 400 dispatches."""
    scopes = {"jit_prefill_impl": {"scopes": {"attn/full": 0.05, "attn/project": 0.1,
                                              "moe/experts": 0.4}, "kernels": {}, "attend_s": 0.0},
              "jit_chunk_impl": {"scopes": {"attn/full": 1.5, "moe/experts": 4.0, "loop/sample": 0.2},
                                 "kernels": {"paged_attention_grouped": 1.5}, "attend_s": 0.0}}
    trace = {"busy_s": 5.9, "module_s": {"jit_prefill_impl": 0.6, "jit_chunk_impl": 5.3},
             "module_whole_s": {"jit_prefill_impl": 0.6, "jit_chunk_impl": 5.3},
             "module_runs": {"jit_prefill_impl": 12, "jit_chunk_impl": 50}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((256, 3072))]
    passes = 400 * 8
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100}, "counters1": {"dispatches": 500},
            "model0": {"decode_rows_full": 0, "decode_experts_touched": 0, "decode_moe_passes": 0,
                       "block_passes": 0, "blocks_committed": 0},
            "model1": {"decode_rows_full": passes * 64 * 6 * 2500,
                       "decode_experts_touched": passes * 6 * 127, "decode_moe_passes": passes * 6,
                       "block_passes": passes * 64, "blocks_committed": passes * 64 // 5},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_sdar as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "denoise_passes_per_block")(run) == pytest.approx(5.0)
    assert reader("layer_metrics", "denoise_pass_ms")(run) == pytest.approx(1e3 * 5.3 / (50 * 8))
    # 50 traced dispatches of 8 passes x 64 slots x 6 layers x 2500 rows x 2048 B
    rows = 50 * 8 * 64 * 6 * 2500
    gqa = reader("layer_metrics", "gqa_decode_hbm_roofline.sdar")(run)
    assert gqa == pytest.approx(100 * rows * 2048 / 819e9 / 1.5) and 0 < gqa < 100
    flops = 12 * (costs.attention_prefill_flops(cfg, 256) + costs.attention_prefill_flops(cfg, 3072)) / 2
    pre = reader("layer_metrics", "attn_prefill_flops_roofline.sdar")(run)
    assert pre == pytest.approx(100 * flops / 197e12 / 0.05) and 0 < pre < 100
    least_s = 50 * 8 * costs.moe_decode_bytes(cfg, 6 * 127, 6) / 819e9
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.sdar")(run)
    assert moe == pytest.approx(100 * least_s / 4.0) and 0 < moe < 100
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.sdar")(run)
    assert moe_pre == pytest.approx(100 * 12 * costs.moe_flops(cfg, 1664) / 197e12 / 0.4)
    assert 0 < moe_pre < 100
    # a program without the scopes or the counters (the parent commit), or another
    # model's run: nothing, and no error
    bare = dict(run, scopes={m: dict(t, scopes={"head": 1.0}, kernels={})
                             for m, t in run["scopes"].items()}, model0={}, model1={})
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(dict(run, scopes=None, trace=None, model1={})) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by NAME: the next configuration appends behind these
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 7
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="sdar-30b-a3b-chat", traffic="blockgen-offline",
                        chips=1) and len(cell["why"]) <= 200
    assert "static" in cell["why"]          # the one schedule seeded logits measure
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert "static" in entry["why"]
    body = config()
    assert entry["name"] == "sdar-30b-a3b-chat" and entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"] and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert set(NEW) <= set(reported)
    assert set(reported) - set(NEW) == {
        "tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
        "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
        "expert_load_max_over_mean", "prefill_share.moonlight", "attn_full_time_share",
        "head_time_share.offline", "norm_time_share.offline", "stage_named_share.offline"}
    for m in (m for m in bench["per_layer"] if m["name"] in NEW):
        assert m["workloads"][0] == CELL and m["moves"] == "serve_tok_s"
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "blockgen-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == "serve-closed-sdar" and mix["clients"] == 72
    assert mix["requests"] == {"prompt_lens": [256, 512, 768, 1024, 1536, 2048, 2560, 3072],
                               "max_new_tokens": [256, 512, 1024], "temperature": 0.8,
                               "token_ids_below": 151643}
    assert mix["engine"] == {"num_slots": 64, "prefill_buckets": [512, 1024, 2048, 3072],
                             "max_len": 4096, "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (8, 4, 1.0, 6.0)
    assert mix["requests"]["token_ids_below"] < body["assumed"]["generation"]["mask_token_id"]
