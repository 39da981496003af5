"""The `serve-closed-kimi-linear` mode end to end on the CPU at a tiny size (the server
built by lib/kimi_linear.py over a latent group and two state groups and a SHARE of the
experts, the reference reference/kimi_linear_ref.py token by token with the same share),
its own copy of `serve-closed-model` left as Moonlight's, the wrong programs' facility,
`lib/costs_kimi_linear.py` against hand counts, the new readers on hand-made records, and
the new entries' contract, found by NAME. Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "kimi-linear-longgen-offline"
CONFIG = "kimi-linear-48b-a3b"
NEW = ("kda_time_share", "kda_decode_hbm_roofline", "kda_prefill_flops_roofline",
       "state_pool_peak_share", "mla_decode_hbm_roofline.kimi", "moe_decode_hbm_roofline.kimi",
       "moe_prefill_flops_roofline.kimi")


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny():
    """The configuration file's keys at a small size: 1 dense + 4 layers with one latent,
    4 of 8 experts held (ids 2..5), 96 of 768 vocabulary rows."""
    cfg = config()
    lin = dict(cfg["linear_attn_config"], num_heads=4, head_dim=16)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, num_experts=4, experts_held_first=2, vocab_size=96,
               num_experts_per_token=2, num_hidden_layers=5, model_max_length=64,
               linear_attn_config=lin,
               published=dict(cfg["published"], num_experts=8, vocab_size=768),
               assumed=dict(cfg["assumed"], initializer_range=0.08, kda_decay_rank=16,
                            kda_gate_rank=16))
    return cfg


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": "serve-closed-kimi-linear", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [6, 8, 16, 20, 32], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("kimi_linear"), traffic, seconds=4.0)
    ctx.config = tiny()
    module = mode("serve-closed-kimi-linear")
    module.EARLY, module.LATE = 4, 2
    os.environ["KIMI_WRONG_REFERENCE"] = "no_decay,kinds_shifted,bucket_end,state_bf16"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["KIMI_WRONG_REFERENCE"]


def test_the_mode_serves_the_share_through_the_state_groups_and_judges_it(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "Kimi-Linear-48B-A3B-Instruct" and facts["checked"] > 0
    assert facts["experts_held"] == {"first": 2, "count": 4, "of": 8}
    assert facts["vocab_slice"] == {"first": 0, "rows": 96, "of": 768}
    assert facts["padded_prompts_checked"] >= 1          # 6, 8 and 20 pad to 16 and 32
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    # bfloat16 weights and activations at toy widths: the share limit's own reading
    assert facts["share_within_margin"] >= facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert (facts["early"], facts["late"]) == (4, 2)
    # three cache groups of the one manager: the latent rows and the two state groups
    groups = run["cache_groups"]
    assert list(groups) == ["latent", "state", "conv"]
    assert (groups["latent"]["layers"], groups["state"]["layers"]) == (1, 4)
    assert groups["state"]["pages_a_slot"] == groups["conv"]["pages_a_slot"] == 1
    assert groups["state"]["dtype"] == "float32" and groups["state"]["blocks_total"] == 4
    assert run["state"]["blocks_total"] == 8 and run["state"]["recurrence_path"] == "xla"
    assert 0 < run["state"]["peak_blocks_used"] <= 8
    assert reader("layer_metrics", "state_pool_peak_share")(run) == pytest.approx(
        100.0 * run["state"]["peak_blocks_used"] / 8)
    assert facts["prefix_cache"].startswith("off")
    # on the CPU everything gathers, and that alone makes the run not correct
    assert facts["decode_attention"] == {"latent": "gather"}
    assert any("gathered" in why for why in run["why_incorrect"])
    assert any("recurrence" in why for why in run["why_incorrect"])
    # the facility judged the wrong programs by the same limits and touched no verdict
    wrong = facts["wrong_references"]
    assert set(wrong) == {"no_decay", "kinds_shifted", "bucket_end", "state_bf16"}
    for name, reading in wrong.items():
        assert reading["positions"] == facts["positions"]
        assert set(reading["fails"]) <= {"early", "all", "late"} | (
            {"state_step"} if name == "state_bf16" else set())
    assert wrong["kinds_shifted"]["share_within_margin"] < 1.0
    # limit 4: the served blocks one step on, by the program's own step, against the
    # float32 recurrence; a state kept in bfloat16 is told by it and by nothing else
    step = facts["state_step"]
    assert (step["path"], step["slots"], step["layers"], step["state_dtype"]) == (
        "xla", 4, 4, "float32")
    assert step["error"] == facts["state_step_error"] < 1e-6 < module.MAX_STATE_STEP_ERROR
    assert module.MAX_STATE_STEP_ERROR < 1e-4 < step["error_state_bf16"]
    assert "state_step" not in facts["fails"]
    assert "state_step" in wrong["state_bf16"]["fails"]
    assert wrong["state_bf16"]["state_step_error"] == step["error_state_bf16"]


def test_the_counters_of_the_state_and_the_picks(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("kda_state_steps", "kda_prefill_rows", "mla_decode_rows",
                       "moe_picks_routed", "moe_picks_held", "router_tokens",
                       "decode_router_tokens")}
    assert moved["kda_state_steps"] > 0 and moved["kda_state_steps"] % 4 == 0
    assert moved["kda_prefill_rows"] > 0 and moved["kda_prefill_rows"] % 4 == 0
    # four expert layers, one latent layer: a live slot a step counts 4 router tokens
    assert moved["kda_state_steps"] == moved["decode_router_tokens"]
    assert moved["mla_decode_rows"] > moved["kda_state_steps"] // 4
    assert moved["moe_picks_routed"] == 2 * moved["router_tokens"]
    assert 0 < moved["moe_picks_held"] < moved["moe_picks_routed"]
    run.update(config=tiny(), peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert 0.1 < reader("layer_metrics", "moe_held_pick_share")(run) < 0.9
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    for name in NEW:
        if name != "state_pool_peak_share":
            assert reader("layer_metrics", name)(run) is None, name   # no trace, no number


def test_the_copy_is_the_modes_own_and_the_stage_tables_know_kda(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["kimi_linear"] == ("kimi_linear", "kimi_linear_ref")
    from lib import stage_times
    stages = stage_times.STAGES + ("kda/*",)
    assert "kda/*" not in stage_times.STAGES
    for tf_op, stage in (
            ("jit(chunk_impl)/while/body/closed_call/kda/recur/pallas_call:", "kda/recur"),
            ("jit(prefill_impl)/kda/recur/while/body/dot_general:", "kda/recur"),
            ("jit(prefill_impl)/kda/conv/mul:", "kda/conv"),
            ("jit(chunk_impl)/while/body/closed_call/mla/attend/pallas_call:", "mla/attend"),
            ("jit(chunk_impl)/while/body/closed_call/moe/combine/gather:", "moe/combine")):
        assert stage_times.stage_of(tf_op, stages) == stage
    assert stage_times.stage_of("jit(prefill_impl)/kda/conv/mul:") is None
    assert module.StageTables.reduce_dir(os.path.join(BENCH, "tests", "no_such_dir")) is None


def test_the_limits_on_hand_made_deficits():
    import numpy as np

    module = mode("serve-closed-kimi-linear")
    n = 600
    early = np.zeros(n, bool)
    early[:96] = early[300:396] = True
    late = np.zeros(n, bool)
    late[-64:] = True
    request = np.arange(n) // 300
    clean = np.zeros(n)
    assert module._limits(clean, early, late, request)["fails"] == []
    drift = clean.copy()
    drift[-64:-50] = 0.15                     # 14 of the last 64 over the late margin
    read = module._limits(drift, early, late, request)
    assert read["fails"] == ["late"] and read["late_within_margin"] == pytest.approx(50 / 64)
    start = clean.copy()
    start[300:340] = 0.04                     # 40 of ONE request's 96 early ones: its hand-over
    read = module._limits(start, early, late, request)
    assert read["fails"] == ["early"] and read["judged_within_by_request"] == [
        1.0, pytest.approx(56 / 96)]
    burst = clean.copy()
    burst[100:200] = 0.4                      # 100 of 600, none judged early or late
    assert module._limits(burst, early, late, request)["fails"] == ["all"]
    few = early & (np.arange(n) % 300 < 10)   # ten judged a request: nobody reads limit 1
    assert module._limits(start, few, late, request)["judged_within_margin"] is None
    none = module._limits(clean, np.zeros(n, bool), np.zeros(n, bool), request)
    assert none["judged_within_margin"] is None and none["late_within_margin"] is None
    assert none["fails"] == []
    # limit 4 is a number: float32's rounding passes, bfloat16's and no number do not
    assert module._limits(clean, early, late, request, 3e-7)["fails"] == []
    assert module._limits(clean, early, late, request, 1.2e-3)["fails"] == ["state_step"]
    assert module._limits(clean, early, late, request, float("nan"))["fails"] == ["state_step"]


@pytest.mark.parametrize("kept_in", ["float32", "bfloat16"])
def test_the_state_step_tells_a_state_kept_below_float32(kept_in):
    """Limit 4 on a hand-made arena: the program's own step on float32 blocks is the
    reference's to float32 rounding; the SAME program over a state arena kept in bfloat16
    (the group's type a config key) reads a thousand times the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import kimi_linear as builder
    from reference import kimi_linear_ref

    module = mode("serve-closed-kimi-linear")
    cfg = tiny()
    cfg["assumed"] = dict(cfg["assumed"], kda_state_dtype=kept_in)
    model_cfg = builder.kimi_linear_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.bfloat16)
    specs = {spec.name: spec for spec in model_cfg.cache_specs()}
    key = jax.random.split(jax.random.PRNGKey(3), 2)
    state = (0.1 * jax.random.normal(key[0], (4, 1, 6) + tuple(specs["state"].state_shape))
             ).astype(kept_in)
    conv = (0.1 * jax.random.normal(key[1], (4, 1, 6) + tuple(specs["conv"].state_shape))
            ).astype(jnp.bfloat16)
    read = module.state_step_readings(builder.program, kimi_linear_ref, model_cfg, params,
                                      (None, state, conv), [5, 9, 11], 12345)
    assert (read["slots"], read["layers"], read["state_dtype"]) == (5, 4, kept_in)
    assert 1e-4 < read["error_state_bf16"] < 1e-2
    if kept_in == "float32":
        assert read["error"] < 1e-6
    else:
        assert read["error"] > 100 * module.MAX_STATE_STEP_ERROR
    assert np.isfinite(read["error"])


def test_costs_kimi_linear_against_hand_counts():
    from lib import costs_kimi_linear as costs
    cfg = config()
    assert costs.kinds(cfg) == (10, 3)
    assert costs.expert_params(cfg) == 3 * 2304 * 1024 == 7_077_888
    assert costs.router_params(cfg) == 2304 * 256 and costs.shared_params(cfg) == 7_077_888
    assert costs.held_pick_share(cfg) == 0.125 and costs.expert_layers(cfg) == 12
    # a slot's state of a layer: 32 x 128 x 128 float32 and 3 rows of 12288 bfloat16
    assert costs.kda_state_bytes(cfg) == 2_097_152 and costs.kda_history_bytes(cfg) == 73_728
    # the roofline of `kda/recur` counts what moves under `kda/recur`: the state alone
    assert costs.kda_decode_bytes(cfg, 128 * 10) == 2 * 1280 * 2_097_152
    assert costs.kda_prefill_flops(cfg, 10) == 10 * 32 * 6.0 * 128 * 128
    assert costs.latent_row_bytes(cfg) == 1152 and costs.mla_decode_bytes(cfg, 7) == 7 * 1152
    assert costs.moe_decode_bytes(cfg, 32, 1) == 2 * (32 * 7_077_888 + 7_077_888 + 589_824)
    assert costs.moe_flops(cfg, 1, 12) == 2.0 * (12 * (7_077_888 + 589_824) + 12 * 7_077_888)
    assert costs.latent_params(cfg) == 29_114_368      # the file's count less kv_norm's 512
    assert costs.kda_params(cfg) == 3 * 2304 * 4096 + 4096 * 2304 \
        + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    # 6.90 GB: the matrices alone are within a thousandth of the file's count with vectors
    assert costs.weight_bytes(cfg) == pytest.approx(cfg["bytes"]["weights_bf16"], rel=1e-3)
    assert cfg["bytes"]["weights_bf16"] == 6_901_094_016
    assert cfg["bytes"]["state_bytes_a_slot"] == 10 * (2_097_152 + 73_728)


def hand_made_run():
    """A traced window of 6 s: 20 prefills with 1.2 s under `kda/recur` and 0.5 s under
    `moe/*`; 30 decode dispatches of 8 steps with 2.0 s under `kda/recur`, 0.4 s under the
    other `kda/*`, 0.4 s in the latent kernel and 1.8 s under `moe/*`; over the window 300
    dispatches and 150 prefills of a mean 1,664 rows."""
    scopes = {"jit_prefill_impl": {"scopes": {"kda/recur": 1.2, "kda/project": 0.2,
                                              "moe/experts": 0.3, "moe/shared": 0.2,
                                              "mla/attend": 0.1},
                                   "kernels": {"_causal_rows_call": 0.1}, "attend_s": 0.1},
              "jit_chunk_impl": {"scopes": {"kda/recur": 2.0, "kda/conv": 0.2, "kda/gate": 0.2,
                                            "mla/attend": 0.3, "moe/experts": 1.6,
                                            "moe/shared": 0.2},
                                 "kernels": {"latent_paged_attention": 0.4, "kda_step": 2.0},
                                 "attend_s": 0.3}}
    trace = {"busy_s": 5.9, "module_s": {"jit_prefill_impl": 2.0, "jit_chunk_impl": 3.9},
             "module_whole_s": {"jit_prefill_impl": 2.0, "jit_chunk_impl": 3.9},
             "module_runs": {"jit_prefill_impl": 20, "jit_chunk_impl": 30}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((256, 3072))]
    steps, tokens = 300 * 8, 150 * 1664
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100, "prefills": 50},
            "counters1": {"dispatches": 400, "prefills": 200},
            "model0": dict.fromkeys(
                ("kda_state_steps", "kda_prefill_rows", "mla_decode_rows",
                 "decode_experts_touched", "decode_moe_passes", "moe_picks_routed",
                 "moe_picks_held", "decode_moe_picks_routed", "decode_moe_picks_held"), 0),
            "model1": {"kda_state_steps": steps * 128 * 10, "kda_prefill_rows": tokens * 10,
                       "mla_decode_rows": steps * 128 * 3 * 2500,
                       "decode_experts_touched": steps * 12 * 31, "decode_moe_passes": steps * 12,
                       "moe_picks_routed": (steps * 128 + tokens) * 12 * 8,
                       "moe_picks_held": (steps * 128 + tokens) * 12,
                       "decode_moe_picks_routed": steps * 128 * 12 * 8,
                       "decode_moe_picks_held": steps * 128 * 12},
            "state": {"blocks_total": 256, "peak_blocks_used": 256},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_kimi_linear as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "kda_time_share")(run) == pytest.approx(100 * 3.8 / 5.9)
    # 30 traced dispatches of 8 steps x 128 slots x 10 layers x 2 x 2,097,152 B
    kda = reader("layer_metrics", "kda_decode_hbm_roofline")(run)
    assert kda == pytest.approx(100 * 30 * 8 * 1280 * 2 * 2_097_152 / 819e9 / 2.0)
    assert 0 < kda < 100
    pre = reader("layer_metrics", "kda_prefill_flops_roofline")(run)
    assert pre == pytest.approx(100 * 20 * costs.kda_prefill_flops(cfg, 16640) / 197e12 / 1.2)
    assert 0 < pre < 100
    assert reader("layer_metrics", "state_pool_peak_share")(run) == 100.0
    mla = reader("layer_metrics", "mla_decode_hbm_roofline.kimi")(run)
    assert mla == pytest.approx(100 * 30 * 8 * 128 * 3 * 2500 * 1152 / 819e9 / 0.4)
    assert 0 < mla < 100
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.kimi")(run)
    assert moe == pytest.approx(
        100 * 30 * 8 * costs.moe_decode_bytes(cfg, 12 * 31, 12) / 819e9 / 1.8)
    assert 0 < moe < 100
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.kimi")(run)
    assert moe_pre == pytest.approx(
        100 * 20 * costs.moe_flops(cfg, 1664, 1664 * 12) / 197e12 / 0.5)
    assert 0 < moe_pre < 100
    # the accepted readers this cell is appended to read the same tables
    assert reader("layer_metrics", "mla_attn_time_share")(run) == pytest.approx(100 * 0.4 / 5.9)
    assert reader("layer_metrics", "moe_time_share")(run) == pytest.approx(100 * 2.3 / 5.9)
    assert reader("layer_metrics", "moe_shared_time_share")(run) == pytest.approx(100 * 0.4 / 5.9)
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(0.125)
    assert reader("layer_metrics", "decode_step_ms.moonlight")(run) == pytest.approx(
        1e3 * 3.9 / (30 * 8))
    assert reader("layer_metrics", "prefill_share.moonlight")(run) == pytest.approx(100 * 2.0 / 5.9)
    assert reader("layer_metrics", "prefills_per_chunk")(run) == pytest.approx(0.5)
    # a program without the scopes or the counters (the parent commit): nothing, no error
    bare = dict(run, scopes={m: dict(t, scopes={"ffn/dense": 1.0}, kernels={})
                             for m, t in run["scopes"].items()},
                model0={}, model1={}, state=None)
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(
            dict(run, scopes=None, model0={}, model1={}, state=None)) is None
    # another model's configuration: the two expert readers say nothing of it
    other = dict(run, config={"num_shared_experts": 4})
    assert reader("layer_metrics", "moe_decode_hbm_roofline.kimi")(other) is None
    assert reader("layer_metrics", "moe_prefill_flops_roofline.kimi")(other) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="longgen-offline", chips=1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    body = config()
    assert entry["source"] == body["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == body["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "model_max_length"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert body["deployment"]["chips that share a layer"] == 8
    assert body["deployment"]["summary"] == (
        "eight chips share each layer, two pipeline stages; this is a chip of the first")
    # every width as published; the counts that are a chip's share beside the published ones
    published = {"hidden_size": 2304, "intermediate_size": 9216, "moe_intermediate_size": 1024,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_attention_heads": 32, "num_experts_per_token": 8,
                 "num_shared_experts": 1, "first_k_dense_replace": 1, "mla_use_nope": True,
                 "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "q_lora_rank": None}
    assert {k: body[k] for k in published} == published
    lin = body["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27] and len(lin["kda_layers"]) == 20
    assert (body["num_experts"], body["published"]["num_experts"]) == (32, 256)
    assert (body["vocab_size"], body["published"]["vocab_size"]) == (20480, 163840)
    assert (body["num_hidden_layers"], body["model_max_length"]) == (13, 6144)
    for key in ("kda_decay_rank", "kda_gate_rank", "kda_a_log_dt_bias", "kda_state_dtype",
                "kda_l2_eps", "router_bias", "initializer_range", "provenance"):
        assert key in body["assumed"], key
    # the catalog's row: every key of its `config` is in the file, changed only if reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["source_url"] == entry["source"]
        differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
        assert differs == set(body["reduced"])
    # the program's config from the file: the kinds by the published lists
    from lib import kimi_linear
    program = kimi_linear.kimi_linear_config(body)
    assert program.kda_layers == (1, 2, 3, 5, 6, 7, 9, 10, 11, 13)
    assert program.full_attn_layers == (4, 8, 12)
    assert program.experts_held == (0, 32) and program.n_routed_experts == 256
    assert program.vocab_slice == (0, 20480, 163840) and program.state_shape == (32, 128, 128)
    assert program.conv_shape == (1, 288, 128)
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert set(NEW) <= set(reported)
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
            "moe_shared_time_share", "moe_held_pick_share", "expert_load_max_over_mean",
            "mla_attn_time_share", "decode_step_ms.moonlight", "prefill_share.moonlight",
            "head_time_share.offline", "norm_time_share.offline"} <= set(reported)
    # the readers that count with another model's costs or stages do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline", "mla_decode_hbm_roofline",
                "moe_decode_hbm_roofline.commanda", "stage_named_share.offline",
                "hc_time_share", "attn_full_time_share"} & set(reported)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s" and m["unit"] == "%"
        module_path = os.path.join(BENCH, "layer_metrics", name + ".py")
        assert os.path.exists(module_path)
        assert reader("layer_metrics", name).__module__ or True
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["kda_time_share"] == "linear attention"
    assert layers["state_pool_peak_share"] == layers["kv_used_peak_share"] == "paged KV cache"
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "longgen-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == "serve-closed-kimi-linear" and mix["clients"] == 136
    assert mix["requests"] == {"prompt_lens": [256, 512, 768, 1024, 1536, 2048, 3072, 4096],
                               "max_new_tokens": [512, 1024, 1536], "temperature": 0.8}
    # ISSUE 45's NAMED outputs, the longest answers of any cell: its fall-back is for
    # `num_slots` alone
    for name in ("reason-offline", "blockgen-offline"):
        with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
            assert sum(json.load(f)["requests"]["max_new_tokens"]) < sum(
                mix["requests"]["max_new_tokens"])
    assert mix["engine"] == {"num_slots": 128, "prefill_buckets": [512, 1024, 2048, 4096],
                             "max_len": 6144, "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (24, 4, 1.0, 6.0)
