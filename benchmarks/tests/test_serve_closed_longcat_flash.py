"""The `serve-closed-longcat-flash` mode end to end on the CPU at a tiny size (the server
built by lib/longcat_flash.py over one latent group of 2 x layers cache layers and a SHARE
of the experts behind a router that also scores identity experts, the reference
reference/longcat_flash_ref.py with the same share), its own copy of `serve-closed-model`
left as Moonlight's, the wrong programs' facility, `lib/costs_longcat_flash.py` against hand
counts, the new readers on hand-made records, and the new entries' contract, found by NAME.
Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "longcat-flash-agentgen-offline"
CONFIG = "longcat-flash-omni"
MODE = "serve-closed-longcat-flash"
NEW = ("moe_identity_pick_share", "moe_real_picks_p95", "scmoe_time_share",
       "ffn_dense_time_share", "mla_decode_hbm_roofline.longcat",
       "moe_decode_hbm_roofline.longcat", "ffn_dense_decode_hbm_roofline",
       "moe_prefill_flops_roofline.longcat")
COUNTED = ("moe_identity_pick_share", "moe_real_picks_p95")


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny():
    """The configuration file's keys at a small size: 2 double layers, 2 of 8 experts held
    (ids 2, 3) behind a router of 8 + 4 outputs at 4 picks, 96 of 768 vocabulary rows."""
    cfg = config()
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=96,
               expert_ffn_hidden_size=32, n_routed_experts=2, experts_held_first=2,
               zero_expert_num=4, moe_topk=4, vocab_size=96, num_layers=2,
               max_position_embeddings=64,
               published=dict(cfg["published"], n_routed_experts=8, vocab_size=768),
               assumed=dict(cfg["assumed"], initializer_range=0.08, router_bias_std=0.05))
    return cfg


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": MODE, "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [6, 8, 16, 20, 32], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("longcat_flash"), traffic, seconds=4.0)
    ctx.config = tiny()
    module = mode(MODE)
    module.EARLY, module.MIN_JUDGED = 4, 2
    os.environ["LONGCAT_FLASH_WRONG_REFERENCE"] = \
        "no_identity,renormalised,shortcut_early,held_shifted,products_bf16"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["LONGCAT_FLASH_WRONG_REFERENCE"]


def test_the_mode_serves_the_share_and_judges_it(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "LongCat-Flash-Omni" and facts["checked"] > 0
    assert facts["experts_held"] == {"first": 2, "count": 2, "of": 8}
    assert facts["vocab_slice"] == {"first": 0, "rows": 96, "of": 768}
    assert (facts["identity_experts"], facts["router_width"], facts["cache_layers"]) == (4, 12, 4)
    assert facts["padded_prompts_checked"] >= 1          # 6, 8 and 20 pad to 16 and 32
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert (facts["early"], facts["checked"]) == (4, min(6, facts["checked"]))
    # on the CPU everything gathers and the experts are ragged products, and that alone
    # makes the run not correct
    assert facts["decode_attention"] == "gather"
    assert facts["expert_product_path"] == "ragged_dot"
    assert any("gathered" in why for why in run["why_incorrect"])
    assert any("did not run as the kernel" in why for why in run["why_incorrect"])
    # limit 3: the program's own expert layer on the checked rows against the reference's
    layer = facts["shortcut"]
    assert layer["rows"] + layer["rows_left_out"] == min(module.SHORTCUT_ROWS, facts["positions"])
    assert layer["error"] == facts["shortcut_error"] < module.MAX_SHORTCUT_ERROR
    assert "shortcut" not in facts["fails"]
    assert 0 <= layer["rows_with_held_pick"] <= layer["rows"]
    # the facility judged the wrong programs by the same limits and touched no verdict
    wrong = facts["wrong_references"]
    assert set(wrong) == {"no_identity", "renormalised", "shortcut_early", "held_shifted",
                          "products_bf16"}
    for name, reading in wrong.items():
        assert reading["positions"] == facts["positions"]
        assert set(reading["fails"]) <= {"early", "all", "shortcut"}
    assert set(layer["wrong"]) == {"no_identity", "renormalised", "held_shifted", "products_bf16"}
    for name in ("no_identity", "renormalised"):
        assert layer["wrong"][name] > 10 * module.MAX_SHORTCUT_ERROR
        assert "shortcut" in wrong[name]["fails"]
    assert wrong["shortcut_early"]["shortcut_error"] is None
    # the histogram of real picks: every routed token of the window once a layer
    hist = facts["real_picks_hist"]
    moved = run["model1"]["router_tokens"] - run["model0"]["router_tokens"]
    assert len(hist) == 5 and sum(hist) == moved > 0
    assert facts["real_picks_mean"] == pytest.approx(
        sum(i * n for i, n in enumerate(hist)) / moved)
    assert 0 < facts["identity_pick_share"] < 1


def test_the_counters_of_the_picks_and_the_readers_that_read_them(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("moe_picks_routed", "moe_picks_held", "moe_identity_picks",
                       "moe_expert_picks", "moe_held_picks", "router_tokens",
                       "decode_router_tokens", "mla_decode_rows", "decode_moe_passes")}
    assert moved["moe_picks_routed"] == 4 * moved["router_tokens"]
    assert moved["moe_identity_picks"] + moved["moe_expert_picks"] == moved["moe_picks_routed"]
    assert moved["moe_picks_held"] == moved["moe_held_picks"] <= moved["moe_expert_picks"]
    assert moved["mla_decode_rows"] > 0 and moved["mla_decode_rows"] % 4 == 0
    run.update(config=tiny(), peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    share = reader("layer_metrics", "moe_identity_pick_share")(run)
    assert share == pytest.approx(100.0 * moved["moe_identity_picks"] / moved["moe_picks_routed"])
    # the accepted reader counts over the router's WHOLE width: identity picks among them
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(
        moved["moe_picks_held"] / moved["moe_picks_routed"])
    assert 0 <= reader("layer_metrics", "moe_real_picks_p95")(run) <= 4
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    for name in NEW:
        if name not in COUNTED:
            assert reader("layer_metrics", name)(run) is None, name   # no trace, no number


def test_the_copy_is_the_modes_own_and_the_stages_are_the_harnesss(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["longcat_flash"] == ("longcat_flash", "longcat_flash_ref")
    from lib import stage_times
    for tf_op, stage in (
            ("jit(chunk_impl)/while/body/closed_call/moe/identity/mul:", "moe/identity"),
            ("jit(prefill_impl)/moe/experts/pallas_call:", "moe/experts"),
            ("jit(chunk_impl)/while/body/closed_call/ffn/dense/dot_general:", "ffn/dense"),
            ("jit(chunk_impl)/while/body/closed_call/mla/attend/pallas_call:", "mla/attend"),
            ("jit(chunk_impl)/while/body/closed_call/norm/mul:", "norm")):
        assert stage_times.stage_of(tf_op) == stage
    assert module.StageTables.reduce_dir(os.path.join(BENCH, "tests", "no_such_dir")) is None


def test_the_limits_on_hand_made_deficits():
    import numpy as np

    module = mode(MODE)
    n = 600
    early = np.zeros(n, bool)
    early[:96] = early[300:396] = True
    request = np.arange(n) // 300
    clean = np.zeros(n)
    assert module._limits(clean, early, request)["fails"] == []
    start = clean.copy()
    start[300:340] = 0.1                      # 40 of ONE request's 96 early ones
    read = module._limits(start, early, request)
    assert read["fails"] == ["early"] and read["judged_within_by_request"] == [
        1.0, pytest.approx(56 / 96)]
    burst = clean.copy()
    burst[100:200] = 0.4                      # 100 of 600, none judged early
    assert module._limits(burst, early, request)["fails"] == ["all"]
    few = early & (np.arange(n) % 300 < 10)   # ten judged a request: nobody reads limit 1
    assert module._limits(start, few, request)["judged_within_margin"] is None
    # limit 3 is a number: the served rounding passes, a wrong layer and no number do not
    assert module._limits(clean, early, request, 3e-4)["fails"] == []
    assert module._limits(clean, early, request, 2.9e-3)["fails"] == ["shortcut"]
    assert module._limits(clean, early, request, float("nan"))["fails"] == ["shortcut"]


def test_the_shortcut_number_tells_a_wrong_layer_on_hand_made_rows():
    """Limit 3 on rows made here: the program's own layer in bfloat16 is the reference's to
    bfloat16's rounding, and every WRONG program that changes the layer reads far above."""
    import jax.numpy as jnp
    import numpy as np
    from lib import longcat_flash as builder
    from reference import longcat_flash_ref

    module = mode(MODE)
    cfg = tiny()
    model_cfg = builder.longcat_flash_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.bfloat16)
    rows = np.random.default_rng(3).standard_normal((200, 64)).astype(np.float32)
    read = module.shortcut_readings(builder.program, longcat_flash_ref, model_cfg, cfg, params,
                                    rows, 12345, module.LAYER_WRONG)
    assert read["rows"] + read["rows_left_out"] == 200 and read["rows"] > 150
    assert read["error"] < module.MAX_SHORTCUT_ERROR
    assert read["expert_product_path"] == "ragged_dot"
    for name in ("no_identity", "bias_weighs", "renormalised", "held_shifted"):
        assert read["wrong"][name] > 3 * module.MAX_SHORTCUT_ERROR, (name, read["wrong"])
    assert read["wrong"]["products_bf16"] > read["error"] / 4
    assert 0 < read["real_picks_mean"] < 4


def test_costs_longcat_flash_against_hand_counts():
    from lib import costs_longcat_flash as costs
    cfg = config()
    assert costs.cache_layers(cfg) == 8 and costs.router_width(cfg) == 768
    assert costs.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert costs.router_params(cfg) == 6144 * 768 == 4_718_592
    assert costs.dense_params(cfg) == 3 * 6144 * 12288 == 226_492_416
    assert costs.latent_params(cfg) == 90_570_752       # the file's count less the 2,048 of two norms
    assert costs.latent_row_bytes(cfg) == 1152 and costs.mla_decode_bytes(cfg, 7) == 7 * 1152
    assert costs.moe_decode_bytes(cfg, 10, 4) == 2 * (10 * 37_748_736 + 4 * 4_718_592)
    assert costs.moe_flops(cfg, 1, 3) == 2.0 * (4 * 4_718_592 + 3 * 37_748_736)
    assert costs.dense_decode_bytes(cfg, 4) == 2 * 4 * 2 * 226_492_416
    # 10.35 GB: the matrices alone are within a thousandth of the file's count with vectors
    assert costs.weight_bytes(cfg) == pytest.approx(cfg["bytes"]["weights_bf16"], rel=1e-3)
    b = cfg["bytes"]
    assert b["layer_without_experts_parameters"] == 638_874_368
    assert b["weights_parameters"] == 5_172_749_312 and b["weights_bf16"] == 10_345_498_624
    assert b["latent_arena_bytes"] == (64 * 40 + 1) * 128 * 640 * 2 * 8 == 3_356_753_920
    assert b["before_workspace_bytes"] == b["weights_bf16"] + b["latent_arena_bytes"]
    assert b["published_model_parameters"] == 28 * (638_874_368 + 512 * 37_748_736) \
        + 2 * 131072 * 6144 + 6144


def hand_made_run():
    """A traced window of 6 s: 20 prefills with 0.3 s under `moe/*` and 1.0 s under
    `ffn/dense`; 30 decode dispatches of 8 steps (3.6 s) with 1.1 s under `moe/*`, 1.2 s under
    `ffn/dense` and 0.9 s in the latent kernel; over the window 300 dispatches and 150
    prefills of a mean 1,824 rows."""
    scopes = {"jit_prefill_impl": {"scopes": {"moe/experts": 0.2, "moe/router": 0.1,
                                              "ffn/dense": 1.0, "mla/attend": 0.4},
                                   "kernels": {"_causal_rows_call": 0.4}, "attend_s": 0.4,
                                   "busy_s": 2.2},
              "jit_chunk_impl": {"scopes": {"moe/experts": 0.8, "moe/identity": 0.1,
                                            "moe/combine": 0.2, "ffn/dense": 1.2,
                                            "mla/attend": 0.9, "head": 0.1},
                                 "kernels": {"latent_paged_attention": 0.9,
                                             "grouped_swiglu": 0.8},
                                 "attend_s": 0.9, "busy_s": 3.6}}
    trace = {"busy_s": 5.8, "module_s": {"jit_prefill_impl": 2.2, "jit_chunk_impl": 3.6},
             "module_whole_s": {"jit_prefill_impl": 2.2, "jit_chunk_impl": 3.6},
             "module_runs": {"jit_prefill_impl": 20, "jit_chunk_impl": 30}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((256, 3392))]
    steps, tokens = 300 * 8, 150 * 1824
    routed = (steps * 64 + tokens) * 4 * 12
    hist = [0] * 13
    hist[6], hist[8], hist[10], hist[11] = 100, 700, 170, 30
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100, "prefills": 50},
            "counters1": {"dispatches": 400, "prefills": 200},
            "model0": dict(dict.fromkeys(
                ("mla_decode_rows", "decode_experts_touched", "decode_moe_passes",
                 "moe_picks_routed", "moe_picks_held", "moe_identity_picks",
                 "decode_moe_picks_routed", "decode_moe_picks_held"), 0),
                moe_real_picks_hist=[0] * 13),
            "model1": {"mla_decode_rows": steps * 64 * 8 * 2300,
                       "decode_experts_touched": steps * 4 * 10, "decode_moe_passes": steps * 4,
                       "moe_picks_routed": routed, "moe_picks_held": routed // 48,
                       "moe_identity_picks": routed // 3,
                       "decode_moe_picks_routed": steps * 64 * 4 * 12,
                       "decode_moe_picks_held": steps * 64 * 4 * 12 // 48,
                       "moe_real_picks_hist": hist},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_longcat_flash as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "moe_identity_pick_share")(run) == pytest.approx(100 / 3, rel=1e-4)
    assert reader("layer_metrics", "moe_real_picks_p95")(run) == 10
    assert reader("layer_metrics", "scmoe_time_share")(run) == pytest.approx(100 * 1.1 / 3.6)
    assert reader("layer_metrics", "ffn_dense_time_share")(run) == pytest.approx(100 * 2.2 / 5.8)
    mla = reader("layer_metrics", "mla_decode_hbm_roofline.longcat")(run)
    assert mla == pytest.approx(100 * 30 * 8 * 64 * 8 * 2300 * 1152 / 819e9 / 0.9)
    assert 0 < mla < 100
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.longcat")(run)
    assert moe == pytest.approx(100 * 30 * 8 * costs.moe_decode_bytes(cfg, 40, 4) / 819e9 / 1.1)
    assert 0 < moe < 100
    dense = reader("layer_metrics", "ffn_dense_decode_hbm_roofline")(run)
    assert dense == pytest.approx(100 * 30 * 8 * costs.dense_decode_bytes(cfg, 4) / 819e9 / 1.2)
    assert 0 < dense < 100
    pre = reader("layer_metrics", "moe_prefill_flops_roofline.longcat")(run)
    assert pre == pytest.approx(
        100 * 20 * costs.moe_flops(cfg, 1824, 1824 * 4 * 12 / 48) / 197e12 / 0.3, rel=1e-3)
    assert 0 < pre < 100
    # the accepted readers this cell is appended to read the same tables
    assert reader("layer_metrics", "mla_attn_time_share")(run) == pytest.approx(100 * 1.3 / 5.8)
    assert reader("layer_metrics", "moe_time_share")(run) == pytest.approx(100 * 1.4 / 5.8)
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(1 / 48, rel=1e-4)
    assert reader("layer_metrics", "prefills_per_chunk")(run) == pytest.approx(0.5)
    # a program without the scopes or the counters (the parent commit): nothing, no error
    bare = dict(run, scopes={m: dict(t, scopes={"attn/full": 1.0}, kernels={})
                             for m, t in run["scopes"].items()}, model0={}, model1={})
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(
            dict(run, scopes=None, model0={}, model1={})) is None
    # another model's configuration: the readers that count with this model's costs say
    # nothing of it
    other = dict(run, config={"num_shared_experts": 4})
    for name in ("mla_decode_hbm_roofline.longcat", "moe_decode_hbm_roofline.longcat",
                 "ffn_dense_decode_hbm_roofline", "moe_prefill_flops_roofline.longcat"):
        assert reader("layer_metrics", name)(other) is None, name


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="agentgen-offline", chips=1)
    assert len(cell["why"]) <= 200
    assert bench["workloads"][-1] is cell and len(bench["workloads"]) == 11
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    body = config()
    assert entry["source"] == body["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == body["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert body["deployment"]["chips that share a layer"] == 32
    assert "encoders" in body["deployment"]["not_built"]
    # every width as published; the counts that are a chip's share beside the published ones
    published = {"hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
                 "num_attention_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
                 "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "rope_theta": 10000000,
                 "mla_scale_q_lora": True, "mla_scale_kv_lora": True}
    assert {k: body[k] for k in published} == published
    assert (body["n_routed_experts"], body["published"]["n_routed_experts"]) == (16, 512)
    assert (body["vocab_size"], body["published"]["vocab_size"]) == (16384, 131072)
    assert (body["num_layers"], body["max_position_embeddings"]) == (4, 5120)
    assert (body["experts_held_first"], body["vocab_first_row"]) == (0, 0)
    for key in ("provenance", "untied_head", "router", "rotation", "softmax_scale", "mla_scales",
                "initializer_range", "router_bias_std", "weights", "weights_read"):
        assert key in body["assumed"], key
    # the catalog's row: every key of its `config` is in the file, changed only if reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Omni")
        assert row["source_url"] == entry["source"]
        differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
        assert differs == set(body["reduced"])
    # the program's config from the file
    from lib import longcat_flash
    from paddle_tpu.models import _experts
    program = longcat_flash.longcat_flash_config(body)
    assert program.experts_held == (0, 16) and program.n_routed_experts == 512
    assert _experts.router_width(program) == 768 and program.experts_per_tok == 12
    assert program.vocab_slice == (0, 16384, 131072) and program.cache_layers == 8
    assert (program.mla_q_scale, round(program.mla_kv_scale ** 2)) == (2.0, 12)
    assert program.router_scoring == "softmax" and not program.router_renormalize
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert set(NEW) <= set(reported)
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "stage_named_share.offline",
            "moe_time_share", "mla_attn_time_share", "expert_load_max_over_mean",
            "moe_held_pick_share", "head_time_share.offline",
            "norm_time_share.offline"} <= set(reported)
    # the readers that count with another model's costs, stages or name do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline", "mla_decode_hbm_roofline",
                "mla_decode_hbm_roofline.kimi", "decode_step_ms.moonlight",
                "prefill_share.moonlight", "moe_shared_time_share", "hc_time_share",
                "attn_full_time_share", "kda_time_share"} & set(reported)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert m["unit"] == ("picks" if name == "moe_real_picks_p95" else "%")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["scmoe_time_share"] == layers["moe_time_share"] == "routed and shared experts"
    assert layers["mla_decode_hbm_roofline.longcat"] == layers["mla_attn_time_share"]
    assert layers["ffn_dense_time_share"] == layers["head_time_share.offline"]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == CELL and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "agentgen-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == MODE and mix["clients"] == 72
    assert mix["requests"] == {"prompt_lens": [256, 512, 1024, 1536, 2048, 2560, 3072, 3584],
                               "max_new_tokens": [512, 1024, 1536], "temperature": 0.8}
    assert mix["engine"] == {"num_slots": 64, "prefill_buckets": [512, 1024, 2048, 4096],
                             "max_len": 5120, "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (24, 4, 1.0, 6.0)
    # a slot's 40 pages hold the longest prompt and the longest answer
    assert max(mix["requests"]["prompt_lens"]) + max(mix["requests"]["max_new_tokens"]) \
        == mix["engine"]["max_len"] == body["max_position_embeddings"]
