"""The `serve-closed-command-a` mode end to end on the CPU at a tiny size (the server
built by lib/command_a.py over two cache groups and a SHARE of the experts, the
reference reference/command_a_ref.py given the same share, requests checked beyond the
window), its own copy of `serve-closed-model` left as Moonlight's, the wrong references'
facility, `lib/costs_command_a.py` against hand counts, the new readers on hand-made
records and on a stored trace, and the new entries' contract. Counts and control flow
only."""

import gzip
import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "command-a-reason-offline"
NEW = ("moe_held_pick_share", "moe_decode_hbm_roofline.commanda",
       "moe_prefill_flops_roofline.commanda", "moe_shared_time_share",
       "gqa_decode_hbm_roofline.commanda", "attn_prefill_flops_roofline.commanda")


def config():
    with open(os.path.join(BENCH, "configs", "command-a-plus-05-2026.json")) as f:
        return json.load(f)


def tiny():
    """The configuration file's keys at a small size: 4 of 16 experts held (ids 4..7),
    96 of 768 vocabulary rows, a window of 8."""
    cfg = config()
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               intermediate_size=32, num_experts=4, experts_held_first=4, vocab_size=96,
               num_experts_per_tok=4, num_shared_experts=2, sliding_window=8,
               max_position_embeddings=64,
               published=dict(cfg["published"], num_experts=16, vocab_size=768),
               assumed=dict(cfg["assumed"], initializer_range=0.08))
    return cfg


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": "serve-closed-command-a", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [6, 8, 12, 20, 28], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("command_a"), traffic, seconds=4.0)
    ctx.config = tiny()
    module = mode("serve-closed-command-a")
    module.LONG_PROMPT = 28                       # the longest of the tiny mix
    os.environ["COMMAND_A_WRONG_REFERENCE"] = "shared_summed,held_shifted"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["COMMAND_A_WRONG_REFERENCE"]


def test_the_mode_serves_the_share_and_judges_it_beyond_the_window(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "command-a-plus-05-2026" and facts["checked"] > 0
    assert facts["experts_held"] == {"first": 4, "count": 4, "of": 16}
    assert facts["vocab_slice"] == {"first": 0, "rows": 96, "of": 768}
    # an odd cycle of five: the greedy (even) requests see every length
    assert facts["greedy_prompt_lens_offered"] == [6, 8, 12, 20, 28]
    assert facts["long_checked"] >= 1 and facts["beyond_window_checked"] == 2
    assert facts["checks_missing"] == [] and max(facts["checked_prompt_lens"]) == 28
    # the two limits' readings, and the constants beside them
    assert facts["share_within_margin"] >= facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert (facts["early"], facts["min_judged_within"]) == (module.EARLY, module.MIN_JUDGED_WITHIN)
    assert facts["fails"] == [] and facts["judged_within_margin"] in (None, 1.0)
    # on the CPU both groups gather in both programs, and that alone makes the run not correct
    assert facts["decode_attention"] == {"full": "gather", "window": "gather"}
    assert len(run["why_incorrect"]) == 2 and "gathered" in run["why_incorrect"][0] \
        and "a prefill gathered" in run["why_incorrect"][1]
    # the facility judged two wrong references by the same two limits and touched no verdict
    wrong = facts["wrong_references"]
    assert set(wrong) == {"shared_summed", "held_shifted"}
    for reading in wrong.values():
        assert (reading["positions"], reading["judged"]) == (facts["positions"], facts["judged"])
        assert set(reading["fails"]) <= {"judged", "share"}
    # the shared experts summed: another function at every position
    assert wrong["shared_summed"]["fails"]
    assert wrong["shared_summed"]["share_within_margin"] < facts["share_within_margin"]


def test_limit_one_reads_the_first_positions_of_a_request_as_a_share():
    """`_two_limits` on hand-made deficits: a burst late in a request moves limit 2
    alone, three early positions over the margin in two hundred fail limit 1, and a
    position near a tie is judged by neither."""
    import numpy as np

    module = mode("serve-closed-command-a")
    lens, gap = (300, 300), np.full(600, 0.5)
    gap[5] = 0.0                                   # a tie among the early ones
    judged = np.concatenate([np.arange(n) < module.EARLY for n in lens]) \
        & (gap >= module.base.PICK_GAP)
    assert judged.sum() == 2 * module.EARLY - 1
    clean = np.zeros(600)
    assert module._two_limits(clean, judged)["fails"] == []
    burst = clean.copy()
    burst[200:230] = 0.4                           # 5% of all positions, none judged
    burst[5] = 0.4                                 # ... and the tie
    read = module._two_limits(burst, judged)
    assert read["fails"] == [] and read["judged_within_margin"] == 1.0
    assert read["share_within_margin"] == pytest.approx(1 - 31 / 600)
    burst[200:240] = 0.4
    assert module._two_limits(burst, judged)["fails"] == ["share"]
    early = clean.copy()
    early[[1, 2]] = module.base.LOGIT_MARGIN + 0.001
    assert module._two_limits(early, judged)["fails"] == []        # 2 of 191
    early[300] = 0.2
    read = module._two_limits(early, judged)
    assert read["fails"] == ["judged"] and read["max_logit_deficit"] == 0.2
    assert module._two_limits(clean, np.zeros(600, bool))["judged_within_margin"] is None


def test_the_picks_the_pools_and_the_rows_attended_are_counted(served):
    _, run = served
    groups = run["cache_groups"]
    assert (groups["full"]["layers"], groups["window"]["layers"]) == (1, 3)
    assert groups["window"]["pages_a_slot"] == 3 and groups["window"]["blocks_total"] == 12
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("moe_picks_routed", "moe_picks_held", "decode_moe_picks_routed",
                       "decode_moe_picks_held", "router_tokens", "decode_rows_full",
                       "decode_rows_window", "decode_moe_passes")}
    assert moved["moe_picks_routed"] == 4 * moved["router_tokens"] > 0
    assert 0 < moved["moe_picks_held"] < moved["moe_picks_routed"]
    assert moved["moe_picks_held"] == sum(b - a for a, b in zip(
        run["model0"]["expert_tokens"], run["model1"]["expert_tokens"]))
    assert len(run["model1"]["expert_tokens"]) == 4
    assert 0 < moved["decode_moe_picks_routed"] <= moved["moe_picks_routed"]
    assert moved["decode_moe_passes"] % 4 == 0
    assert 0 < moved["decode_rows_window"] <= 3 * moved["decode_rows_full"]
    run.update(config=tiny(), peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    share = reader("layer_metrics", "moe_held_pick_share")(run)
    assert share == pytest.approx(moved["moe_picks_held"] / moved["moe_picks_routed"])
    assert 0.05 < share < 0.6                       # 4 of 16 held: 0.25 were it even
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "expert_load_max_over_mean")(run) >= 1.0
    assert reader("layer_metrics", "kv_window_pool_peak_share")(run) \
        == pytest.approx(100.0 * groups["window"]["peak_blocks_used"] / 12)
    for name in NEW[1:] + ("attn_window_time_share", "attn_full_time_share", "moe_time_share"):
        assert reader("layer_metrics", name)(run) is None, name       # no trace, no number


def test_the_copy_is_the_modes_own_and_the_shared_reducer_is_untouched(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["cohere2_moe"] == ("command_a", "command_a_ref")
    from lib import scope_reduce
    assert module.scopes is not scope_reduce and module.base.scope_reduce is module.scopes
    assert scope_reduce.scope_of("jit(prefill_impl)/norm/rsqrt:") is None
    for tf_op, scope in (
            ("jit(prefill_impl)/cond/branch_1_fun/moe/dispatch/eq:", "moe/dispatch"),
            ("jit(chunk_impl)/while/body/closed_call/moe/combine/mul:", "moe/combine"),
            ("jit(chunk_impl)/while/body/closed_call/attn/window/pallas_call:", "attn/window"),
            ("jit(prefill_impl)/norm/rsqrt:", "norm"), ("jit(prefill_impl)/embed/gather:", "embed"),
            ("jit(prefill_impl)/head/dot_general:", "head")):
        assert module.scopes.scope_of(tf_op) == scope


def test_scope_reduction_on_the_recorded_trace(served):
    """tests/data/command_a_scopes.json.gz: one decode dispatch (8 steps x 4 layers) and
    the shortest prefill of the traced run of `command-a-reason-offline`, seed 3800000101
    (my chip run, PR 38), every device operation with its `tf_op`. The mode's copy of the
    reducer finds every scope the block names and the two kernels of a step."""
    module, _ = served
    with gzip.open(os.path.join(BENCH, "tests", "data", "command_a_scopes.json.gz")) as f:
        rec = json.load(f)
    ops, modules = [tuple(e) for e in rec["ops"]], [tuple(m) for m in rec["modules"]]
    tables = module.scopes.by_scope(ops, modules, 0.0, 1e18, rec["tf_op"])
    chunk, prefill = tables["jit_chunk_impl"], tables["jit_prefill_impl"]
    every = {"embed", "norm", "attn/project", "attn/window", "attn/full", "moe/router",
             "moe/dispatch", "moe/experts", "moe/shared", "moe/combine", "head"}
    assert set(chunk["scopes"]) == set(prefill["scopes"]) == every
    # a decode dispatch: the held experts' bytes first, then the shared experts' and the
    # window layers' walk; the attention's time is the grouped kernel's
    order = sorted(chunk["scopes"], key=chunk["scopes"].get, reverse=True)
    assert order[0] == "moe/experts" and set(order[1:4]) == {"moe/shared", "attn/window",
                                                             "attn/project"}
    assert chunk["kernels"]["grouped_swiglu_sliced"] == pytest.approx(
        chunk["scopes"]["moe/experts"], rel=0.01)
    assert chunk["kernels"]["paged_attention_grouped"] == pytest.approx(
        chunk["scopes"]["attn/full"] + chunk["scopes"]["attn/window"], rel=0.01)
    # the step: 118.4 ms a dispatch of 8, of which the sliced expert kernel 58.2
    assert chunk["kernels"]["grouped_swiglu_sliced"] == pytest.approx(0.05824, rel=0.01)
    assert chunk["kernels"]["paged_attention_grouped"] == pytest.approx(0.02523, rel=0.01)
    # the prefill (a 2,048-row bucket): the parallel block's dense half is the larger
    assert prefill["scopes"]["moe/shared"] + prefill["scopes"]["attn/project"] \
        > 2 * prefill["scopes"]["moe/experts"]
    assert prefill["kernels"]["_causal_rows_call"] > 0
    assert "paged_attention_grouped" not in prefill["kernels"]
    run = {"scopes": tables, "trace": {"busy_s": 0.17}}
    assert reader("layer_metrics", "moe_shared_time_share")(run) == pytest.approx(
        100 * (chunk["scopes"]["moe/shared"] + prefill["scopes"]["moe/shared"]) / 0.17)
    assert reader("layer_metrics", "attn_window_time_share")(run) == pytest.approx(
        100 * (chunk["scopes"]["attn/window"] + prefill["scopes"]["attn/window"]) / 0.17)


def test_costs_command_a_against_hand_counts():
    from lib import costs_command_a as costs
    cfg = config()
    assert costs.expert_params(cfg) == 3 * 4096 * 4096 == 50_331_648
    assert costs.shared_params(cfg) == 4 * 50_331_648 == cfg["bytes"]["shared_experts_parameters"]
    assert costs.router_params(cfg) == 4096 * 128 == cfg["bytes"]["router_parameters"]
    assert costs.attention_params(cfg) == 4096 * (16384 + 1024 + 1024) + 16384 * 4096 \
        == 142_606_336 == cfg["bytes"]["attention_parameters"]
    # a held layer 1,149.8 M parameters, the embedding slice 134.2 M: 9.47 GB in bfloat16
    assert costs.weight_bytes(cfg) == cfg["bytes"]["weights_bf16"] == 9_466_544_128
    assert cfg["bytes"]["held_layer_parameters"] == 1_149_763_584
    assert cfg["bytes"]["whole_model_parameters"] == 218_254_802_944
    assert costs.cache_row_bytes(cfg) == 8 * 256 * 2 == 4096
    assert (costs.layers_of(cfg, "full_attention"), costs.layers_of(cfg, "sliding_attention")) == (1, 3)
    assert costs.held_pick_share(cfg) == 0.125
    # one decode pass that touched all 16 held experts: 1.61 GB of experts, 0.40 of shared
    assert costs.moe_decode_bytes(cfg, 16, 1) == 2 * (16 * 50_331_648 + 201_326_592 + 524_288)
    # a token: shared experts and router in each of 4 layers, one expert a held pick
    assert costs.moe_flops(cfg, 1, 4) == 2.0 * (4 * (201_326_592 + 524_288) + 4 * 50_331_648)
    assert costs.attended_pairs(5) == 15 and costs.attended_pairs(5, 3) == 12
    assert costs.attended_pairs(8192, 4096) == 8192 * 4096 - 4096 * 4095 // 2
    per_pair = 4.0 * 128 * 128
    assert costs.attention_prefill_flops(cfg, 8192) == per_pair * (
        costs.attended_pairs(8192) + 3 * costs.attended_pairs(8192, 4096))
    assert costs.decode_rows_bytes(cfg, 1000) == 4_096_000


def hand_made_run():
    """A traced window of 6 s: 12 prefills (mean prompt 4608 rows) with 1.1 s under
    `attn/window` + `attn/full` and 1.0 s under `moe/*` (0.6 of it `moe/shared`); 30 decode
    dispatches of 8 steps with 0.8 s in the grouped paged kernel and 2.4 s under `moe/*`
    (0.5 of it `moe/shared`); over the window 300 dispatches."""
    scopes = {"jit_prefill_impl": {"scopes": {"attn/window": 0.9, "attn/full": 0.2,
                                              "attn/project": 0.6, "moe/experts": 0.4,
                                              "moe/shared": 0.6},
                                   "kernels": {}, "attend_s": 0.0},
              "jit_chunk_impl": {"scopes": {"attn/window": 0.55, "attn/full": 0.25,
                                            "moe/experts": 1.9, "moe/shared": 0.5},
                                 "kernels": {"paged_attention_grouped": 0.8}, "attend_s": 0.0}}
    trace = {"busy_s": 5.9, "module_s": {"jit_prefill_impl": 2.2, "jit_chunk_impl": 3.7},
             "module_whole_s": {"jit_prefill_impl": 2.2, "jit_chunk_impl": 3.7},
             "module_runs": {"jit_prefill_impl": 12, "jit_chunk_impl": 30}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((1024, 8192))]
    steps = 300 * 8
    prefill_tokens = 100 * 4608
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100}, "counters1": {"dispatches": 400},
            "model0": {"decode_rows_full": 0, "decode_rows_window": 0,
                       "decode_experts_touched": 0, "decode_moe_passes": 0,
                       "moe_picks_routed": 0, "moe_picks_held": 0,
                       "decode_moe_picks_routed": 0, "decode_moe_picks_held": 0},
            "model1": {"decode_rows_full": steps * 32 * 5000, "decode_rows_window": steps * 32 * 3 * 3800,
                       "decode_experts_touched": steps * 4 * 15, "decode_moe_passes": steps * 4,
                       "moe_picks_routed": (steps * 32 + prefill_tokens) * 4 * 8,
                       "moe_picks_held": (steps * 32 + prefill_tokens) * 4,
                       "decode_moe_picks_routed": steps * 32 * 4 * 8,
                       "decode_moe_picks_held": steps * 32 * 4},
            "cache_groups": {"window": {"blocks_total": 1056, "peak_blocks_used": 958}},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_command_a as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(0.125)
    assert reader("layer_metrics", "moe_shared_time_share")(run) == pytest.approx(100 * 1.1 / 5.9)
    # 30 traced dispatches of 8 steps x 32 slots x (5000 + 3 x 3800) rows x 4096 B
    rows = 30 * 8 * 32 * (5000 + 3 * 3800)
    gqa = reader("layer_metrics", "gqa_decode_hbm_roofline.commanda")(run)
    assert gqa == pytest.approx(100 * rows * 4096 / 819e9 / 0.8) and 0 < gqa < 100
    flops = 12 * (costs.attention_prefill_flops(cfg, 1024) + costs.attention_prefill_flops(cfg, 8192)) / 2
    pre = reader("layer_metrics", "attn_prefill_flops_roofline.commanda")(run)
    assert pre == pytest.approx(100 * flops / 197e12 / 1.1) and 0 < pre < 100
    least_s = 30 * 8 * costs.moe_decode_bytes(cfg, 4 * 15, 4) / 819e9
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.commanda")(run)
    assert moe == pytest.approx(100 * least_s / 2.4) and 0 < moe < 100
    # a mean prompt of 4608 rows, an eighth of its 4 x 8 picks a token held
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.commanda")(run)
    assert moe_pre == pytest.approx(
        100 * 12 * costs.moe_flops(cfg, 4608, 4608 * 4) / 197e12 / 1.0)
    assert 0 < moe_pre < 100
    # a program without the scopes or the counters (the parent commit): nothing, no error
    bare = dict(run, scopes={m: dict(t, scopes={"ffn/dense": 1.0}, kernels={})
                             for m, t in run["scopes"].items()},
                model0={}, model1={}, cache_groups=None)
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(dict(run, scopes=None, model0={}, model1={})) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="command-a-plus-05-2026",
                        traffic="reason-offline", chips=1)
    entry = bench["configs"][-1]
    body = config()
    assert entry["name"] == "command-a-plus-05-2026" and entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"] == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size",
        "max_position_embeddings"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert body["deployment"]["chips that share a layer"] == 8
    # every width as published; the counts that are a chip's share beside the published ones
    published = {"hidden_size": 4096, "num_attention_heads": 128, "num_key_value_heads": 8,
                 "head_dim": 128, "intermediate_size": 4096, "num_experts_per_tok": 8,
                 "num_shared_experts": 4, "sliding_window": 4096, "layer_norm_eps": 1e-5,
                 "rope_theta": 50000, "norm_topk_prob": True, "logit_scale": 1,
                 "prefix_dense_intermediate_size": 16384, "rms_norm_eps": None}
    assert {k: body[k] for k in published} == published
    assert (body["num_experts"], body["published"]["num_experts"]) == (16, 128)
    assert (body["vocab_size"], body["published"]["vocab_size"]) == (32768, 262144)
    assert body["num_hidden_layers"] == 4 and body["published"]["num_hidden_layers"] == 32
    assert body["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert body["max_position_embeddings"] == 10240
    # the catalog's row: every key of its `config` is in the file, changed only if reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == entry["name"])
        assert row["source_url"] == entry["source"]
        differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
        assert differs == set(body["reduced"])
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert tuple(reported[-6:]) == NEW
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NEW)
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
            "expert_load_max_over_mean", "decode_step_ms.moonlight", "prefill_share.moonlight",
            "attn_window_time_share", "attn_full_time_share",
            "kv_window_pool_peak_share"} <= set(reported)
    # the readers that count with another model's costs do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline",
                "moe_decode_hbm_roofline.mellum", "moe_prefill_flops_roofline.mellum",
                "gqa_decode_hbm_roofline", "attn_prefill_flops_roofline",
                "mla_attn_time_share", "hc_time_share"} & set(reported)
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == CELL and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "reason-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == "serve-closed-command-a" and mix["clients"] == 40
    assert mix["requests"] == {"prompt_lens": [1024, 2048, 3072, 4096, 5120, 6144, 7168, 8192],
                               "max_new_tokens": [256, 512, 1024], "temperature": 0.8}
    assert mix["engine"] == {"num_slots": 32, "prefill_buckets": [2048, 4096, 6144, 8192],
                             "max_len": 10240, "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (8, 4, 1.0, 6.0)
