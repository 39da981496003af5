"""The `serve-closed-qwen3-next` mode end to end on the CPU at a tiny size (the server built
by lib/qwen3_next.py over the attention layers' rows and two state groups and a SHARE of the
experts, the reference reference/qwen3_next_ref.py token by token with the same share), its
own copy of `serve-closed-model` left as Moonlight's, the wrong programs' facility (every
one of the eight NOT CORRECT by some limit), `lib/costs_qwen3_next.py` against hand counts,
the new readers on hand-made records, the state-step limit on a hand-made arena, the
reference's draws against the engine's sampler, and the new entries' contract, found by
NAME. Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "qwen3-next-longmix-offline"
CONFIG = "qwen3-next-80b-a3b"
MODE = "serve-closed-qwen3-next"
NEW = ("gdn_time_share", "gdn_decode_hbm_roofline", "gdn_prefill_flops_roofline",
       "gqa_decode_hbm_roofline.qwen3next", "attn_prefill_flops_roofline.qwen3next",
       "moe_decode_hbm_roofline.qwen3next", "moe_prefill_flops_roofline.qwen3next")
WRONG = ("no_output_gate", "full_rotary", "norm_not_centred", "gate_before_norm",
         "key_heads_unshared", "shared_gate_off", "state_bf16", "scan_bf16")


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny():
    """The configuration file's keys at a small size: 4 layers, every second one of attention
    (so that a recurrent layer lies BEHIND an attention layer and a hand-over reads what the
    attention's gate moves), 4 of 8 experts held (ids 2..5), 96 of 768 vocabulary rows, 2
    key heads under 4 value heads."""
    cfg = config()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_experts=4, experts_held_first=2,
               vocab_size=96, num_experts_per_tok=3, num_hidden_layers=4,
               full_attention_interval=2, max_position_embeddings=64,
               published=dict(cfg["published"], num_experts=8, vocab_size=768),
               assumed=dict(cfg["assumed"], initializer_range=0.08))
    return cfg


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": MODE, "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4, "kv_blocks": 41},
               "requests": {"prompt_lens": [6, 8, 16, 20, 32], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("qwen3_next"), traffic, seconds=4.0)
    ctx.config = tiny()
    module = mode(MODE)
    module.EARLY, module.LATE, module.CHUNK = 4, 2, 8
    module.base.PAD_TO = 8
    os.environ["QWEN3_NEXT_WRONG_REFERENCE"] = "all"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["QWEN3_NEXT_WRONG_REFERENCE"]


def test_the_mode_serves_the_share_through_the_state_groups_and_judges_it(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "Qwen3-Next-80B-A3B-Instruct" and facts["checked"] > 0
    assert facts["experts_held"] == {"first": 2, "count": 4, "of": 8}
    assert facts["vocab_slice"] == {"first": 0, "rows": 96, "of": 768}
    assert facts["padded_prompts_checked"] >= 1          # 6, 8 and 20 pad to 16 and 32
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert (facts["early"], facts["late"]) == (4, 2)
    # three cache groups of the one manager: the attention rows, SIZED FROM THE TRAFFIC
    # (40 + 1 blocks where slab-equivalent is 4 x 16 + 1), and the two state groups
    groups = run["cache_groups"]
    assert list(groups) == ["full", "gdn", "conv"]
    assert (groups["full"]["layers"], groups["gdn"]["layers"]) == (2, 2)
    assert groups["full"]["blocks_total"] == 40
    assert groups["gdn"]["pages_a_slot"] == groups["conv"]["pages_a_slot"] == 1
    assert groups["gdn"]["dtype"] == "float32" and groups["gdn"]["blocks_total"] == 4
    assert run["state"]["blocks_total"] == 8 and run["state"]["recurrence_path"] == "xla"
    assert run["state"]["prefill_recurrence_path"] == "xla"
    assert 0 < run["state"]["peak_blocks_used"] <= 8
    assert reader("layer_metrics", "state_pool_peak_share")(run) == pytest.approx(
        100.0 * run["state"]["peak_blocks_used"] / 8)
    assert facts["prefix_cache"].startswith("off")
    # on the CPU everything gathers, and that alone makes the run not correct
    assert facts["decode_attention"] == {"full": "gather"}
    assert any("gathered" in why for why in run["why_incorrect"])
    assert any("recurrence" in why for why in run["why_incorrect"])
    # limit 6: what the ENGINE's own prefill programs left in the engine's own arena
    hand = facts["handover"]
    assert list(hand["by_bucket"]) == [16, 32] and hand["executables_added"] == 0
    assert (hand["by_bucket"][16]["prompt_len"], hand["by_bucket"][32]["prompt_len"]) == (15, 31)
    assert all(read["prompt_len"] % 8 for read in hand["by_bucket"].values())   # off a chunk's edge
    assert hand["error"] == facts["handover_error"] == max(
        read[kind] for read in hand["by_bucket"].values()
        for kind in ("state", "history", "rows")) < module.MAX_HANDOVER_ERROR
    assert "handover" not in facts["fails"] and "handover_program" not in facts["fails"]
    # limit 7: the program's scan at each bucket's shape against the recurrence token by token
    scan = facts["scan"]
    assert list(scan["by_bucket"]) == [16, 32] and scan["layers"] == 2
    assert scan["paths"] == {16: "xla", 32: "xla"}
    assert scan["error"] == facts["scan_error"] < 1e-5 < module.MAX_SCAN_ERROR
    assert module.MAX_SCAN_ERROR < scan["error_products_bf16"] < 3e-2
    assert facts["sampler_top_lattice_lanes"] == 0        # once in 2^24 lanes
    # limit 5: sampled requests under their own Gumbel draws, rebuilt from their seeds
    assert 0 < facts["sampled_checked"] <= module.SAMPLED_REQUESTS
    assert 0.0 <= facts["sampled_within_margin"] <= 1.0
    records = [r for r in run["records"] if r.get("sampled")]
    assert records and all(r["output"] is None and not r["greedy"] for r in records)
    assert all(len(r["sampled"]["tokens"]) == r["max_new_tokens"] for r in records if r["ok"])
    # limit 4: the served blocks one step on, by the program's own step
    step = facts["state_step"]
    assert (step["path"], step["slots"], step["layers"], step["state_dtype"]) == (
        "xla", 4, 2, "float32")
    assert step["error"] == facts["state_step_error"] < 1e-6 < module.MAX_STATE_STEP_ERROR
    assert module.MAX_STATE_STEP_ERROR < 1e-4 < step["error_state_bf16"]
    assert "state_step" not in facts["fails"]
    # the served program passes every limit that is a NUMBER (a token limit is a share of
    # a few dozen positions here, served in bfloat16 at a width of 64: a flipped pick or two)
    assert not {"state_step", "handover", "handover_program", "scan"} & set(facts["fails"])


@pytest.mark.parametrize("name", WRONG)
def test_every_wrong_program_is_not_correct(served, name):
    """The facility judged the eight WRONG programs by the same limits and touched no
    verdict: each fails at least one, the two a NUMBER tells by that number alone."""
    module, run = served
    facts = run["facts"]
    wrong = facts["wrong_references"]
    assert set(wrong) == set(WRONG) == set(module.base.ARCHITECTURES) ^ set(
        module.base.ARCHITECTURES) | set(WRONG)
    reading = wrong[name]
    assert reading["positions"] == facts["positions"]
    assert reading["sampled_positions"] == facts["sampled_positions"] > 0
    assert reading["fails"], (name, reading)
    if name == "state_bf16":
        assert reading["fails"] == ["state_step"]
        assert reading["state_step_error"] == facts["state_step"]["error_state_bf16"]
    elif name == "scan_bf16":
        assert reading["fails"] == ["scan"]
        assert reading["scan_error"] == facts["scan"]["error_products_bf16"]
    else:
        assert set(reading["fails"]) <= {"early", "all", "late", "sampled", "handover"}
        assert reading["handover_error"] == facts["handover"]["wrong"][name]
    if name in ("full_rotary", "key_heads_unshared", "norm_not_centred", "no_output_gate",
                "gate_before_norm", "shared_gate_off"):
        # what the prefill leaves behind is another state or other rows
        assert "handover" in reading["fails"]


def test_the_counters_of_the_state_and_the_picks(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("gdn_state_steps", "gdn_prefill_rows", "gdn_prefill_chunks",
                       "decode_rows_full", "moe_picks_routed", "moe_picks_held",
                       "router_tokens", "decode_router_tokens")}
    assert moved["gdn_state_steps"] > 0 and moved["gdn_state_steps"] % 2 == 0
    assert moved["gdn_prefill_rows"] > 0 and moved["gdn_prefill_rows"] % 2 == 0
    assert moved["gdn_prefill_chunks"] > 0 and moved["gdn_prefill_chunks"] % 2 == 0
    # four expert layers, two recurrent layers: a live slot a step counts 4 router tokens
    assert moved["gdn_state_steps"] * 4 == moved["decode_router_tokens"] * 2
    assert moved["decode_rows_full"] > moved["gdn_state_steps"] // 2
    assert moved["moe_picks_routed"] == 3 * moved["router_tokens"]
    assert 0 < moved["moe_picks_held"] < moved["moe_picks_routed"]
    run.update(config=tiny(), peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert 0.1 < reader("layer_metrics", "moe_held_pick_share")(run) < 0.9
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    for name in NEW:
        assert reader("layer_metrics", name)(run) is None, name   # no trace, no number


def test_the_copy_is_the_modes_own_and_the_stage_tables_know_gdn(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert mode("serve-closed-model").PAD_TO == 2048 and mode(MODE).base.PAD_TO == 6144
    assert module.base.ARCHITECTURES["qwen3_next"] == ("qwen3_next", "qwen3_next_ref")
    from lib import stage_times
    stages = stage_times.STAGES + ("gdn/*",)
    assert "gdn/*" not in stage_times.STAGES
    for tf_op, stage in (
            ("jit(chunk_impl)/while/body/closed_call/gdn/recur/pallas_call:", "gdn/recur"),
            ("jit(prefill_impl)/gdn/recur/kda_chunk/pallas_call:", "gdn/recur"),
            ("jit(prefill_impl)/gdn/conv/mul:", "gdn/conv"),
            ("jit(prefill_impl)/attn/gate/logistic:", "attn/gate"),
            ("jit(prefill_impl)/attn/project/norm/rsqrt:", "norm"),
            ("jit(chunk_impl)/while/body/closed_call/attn/full/pallas_call:", "attn/full"),
            ("jit(chunk_impl)/while/body/closed_call/moe/combine/gather:", "moe/combine")):
        assert stage_times.stage_of(tf_op, stages) == stage
    assert stage_times.stage_of("jit(prefill_impl)/gdn/conv/mul:") is None
    assert module.StageTables.reduce_dir(os.path.join(BENCH, "tests", "no_such_dir")) is None


def test_the_limits_on_hand_made_deficits():
    import numpy as np

    module = mode(MODE)
    n = 600
    early = np.zeros(n, bool)
    early[:96] = early[300:396] = True
    late = np.zeros(n, bool)
    late[-64:] = True
    request = np.arange(n) // 300
    clean = np.zeros(n)
    assert module._limits(clean, early, late, request)["fails"] == []
    drift = clean.copy()
    drift[-64:-40] = 2 * module.LATE_MARGIN     # 24 of the last 64 over the late margin
    read = module._limits(drift, early, late, request)
    assert read["fails"] == ["late"] and read["late_within_margin"] == pytest.approx(40 / 64)
    start = clean.copy()
    start[300:360] = 2 * module.base.LOGIT_MARGIN   # 60 of ONE request's 96 early ones
    read = module._limits(start, early, late, request)
    assert read["fails"] == ["early"] and read["judged_within_by_request"] == [
        1.0, pytest.approx(36 / 96)]
    burst = clean.copy()
    burst[100:300] = 10 * module.SHARE_MARGIN   # 200 of 600, none judged early or late
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
    # limit 5 is a share of the sampled requests' positions
    drawn = np.zeros(400)
    assert module._limits(clean, early, late, request, 3e-7, drawn)["fails"] == []
    drawn[:40] = 2 * module.SAMPLED_MARGIN     # a tenth of them over the margin
    read = module._limits(clean, early, late, request, 3e-7, drawn)
    assert read["fails"] == ["sampled"] and read["sampled_within_margin"] == pytest.approx(0.9)
    assert module._limits(clean, early, late, request, None, np.zeros(0))["fails"] == []
    # limit 6 is a number too
    assert module._limits(clean, early, late, request, 3e-7, None, 0.02)["fails"] == []
    assert module._limits(clean, early, late, request, 3e-7, None, 0.7)["fails"] == ["handover"]
    # limit 7 is a number as well
    assert module._limits(clean, early, late, request, scan_error=3e-5)["fails"] == []
    assert module._limits(clean, early, late, request, scan_error=3e-3)["fails"] == ["scan"]
    # the logits are of unit size: Kimi-Linear's margins, not granite's sixteenths
    assert module.base.LOGIT_MARGIN == 0.03 and module.SHARE_MARGIN == 0.1



@pytest.mark.parametrize("kept_in", ["float32", "bfloat16"])
def test_the_state_step_tells_a_state_kept_below_float32(kept_in):
    """Limit 4 on a hand-made arena: the program's own step on float32 blocks is the
    reference's to float32 rounding; the SAME program over a state arena kept in bfloat16
    (the group's type a config key) reads a hundred times the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import qwen3_next as builder
    from reference import qwen3_next_ref

    module = mode(MODE)
    cfg = tiny()
    cfg["assumed"] = dict(cfg["assumed"], gdn_state_dtype=kept_in)
    model_cfg = builder.qwen3_next_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.bfloat16)
    specs = {spec.name: spec for spec in model_cfg.cache_specs()}
    key = jax.random.split(jax.random.PRNGKey(3), 2)
    state = (0.1 * jax.random.normal(key[0], (2, 1, 6) + tuple(specs["gdn"].state_shape))
             ).astype(kept_in)
    conv = (0.1 * jax.random.normal(key[1], (2, 1, 6) + tuple(specs["conv"].state_shape))
            ).astype(jnp.bfloat16)
    read = module.state_step_readings(builder.program, qwen3_next_ref, model_cfg, params,
                                      (None, state, conv), [5, 9, 11], 12345)
    assert (read["slots"], read["layers"], read["state_dtype"]) == (5, 2, kept_in)
    assert 1e-4 < read["error_state_bf16"] < 1e-2
    if kept_in == "float32":
        assert read["error"] < 1e-6
    else:
        assert read["error"] > 100 * module.MAX_STATE_STEP_ERROR
    assert np.isfinite(read["error"])


def test_the_handover_tells_a_rotated_key_and_key_heads_read_wrongly():
    """Limit 6 on an engine built here: what its scheduler's prefill of a prompt that ends
    mid-bucket left in its arena against the reference at the prompt's last row, a bucket
    at a time, and the wrong references' readings beside it; limit 7 on the same prompts."""
    import jax.numpy as jnp
    from lib import qwen3_next as builder
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from reference import qwen3_next_ref

    module = mode(MODE)
    cfg = tiny()
    model_cfg = builder.qwen3_next_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.float32)
    engine = ServingEngine(params, model_cfg, ServingConfig(
        num_slots=2, prefill_buckets=(16, 32), max_len=64, block_size=4))
    read = module.handover_readings(
        engine, qwen3_next_ref, cfg, params, 12345, 8,
        ("full_rotary", "key_heads_unshared", "norm_not_centred", "no_output_gate"))
    assert list(read["by_bucket"]) == [16, 32]
    # a cold engine: the two buckets' programs and the first token's sampler
    assert read["executables_added"] == 3
    assert [len(tokens) for _, tokens in read["prompts"]] == [
        read["by_bucket"][b]["prompt_len"] for b in (16, 32)]
    assert engine.kv.free_count == 2                 # the probes' slots are given back
    # float32 weights (rows and history ride in the pool's bfloat16): rounding alone
    for at in read["by_bucket"].values():
        assert at["state"] < 1e-2 and at["history"] < 1e-2 and at["rows"] < 1e-2
    assert read["wrong"]["full_rotary"] > 0.1            # the keys' other 12 of 16 values
    assert read["wrong"]["key_heads_unshared"] > 0.1     # another state
    assert read["wrong"]["norm_not_centred"] > 0.1
    # the second recurrent layer lies behind an attention layer: its state moves with the gate
    assert read["wrong"]["no_output_gate"] > 0.1
    again = module.handover_readings(engine, qwen3_next_ref, cfg, params, 12345, 8)
    assert again["executables_added"] == 0 and again["error"] == read["error"]
    scan = module.scan_readings(builder.program, qwen3_next_ref, model_cfg, params,
                                read["prompts"])
    assert scan["error"] < 1e-5 and module.MAX_SCAN_ERROR < scan["error_products_bf16"] < 3e-2
    assert module._limits(*_clean(), scan_error=scan["error"])["fails"] == []
    assert module._limits(*_clean(), scan_error=scan["error_products_bf16"])["fails"] == ["scan"]


def _clean():
    import numpy as np
    return np.zeros(4), np.zeros(4, bool), np.zeros(4, bool), np.zeros(4, int)


def test_the_references_draws_are_the_samplers():
    """Limit 5's noise is the reference's own (reference/granite_hybrid_ref.py's threefry and
    Gumbel transform, which qwen3_next_ref hands on): its draws against the program's
    sampler position by position, bit for bit, over THIS cell's lanes."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.serving import sampling
    from reference import granite_hybrid_ref, qwen3_next_ref as ref

    assert ref.gumbel_draws is granite_hybrid_ref.gumbel_draws
    like = np.zeros((4, 18992), np.float32)
    for seed in (3, 2**31 + 11):
        noise, top = ref.gumbel_draws(np.uint32(seed), like)
        key = sampling.sample_key(jnp.uint32(seed))
        for position in range(4):
            np.testing.assert_array_equal(np.asarray(noise[position]),
                                          np.asarray(sampling.sample_gumbel(key, 18992)))
            key = sampling.sample_split(key)
        assert top == 0 and np.isfinite(np.asarray(noise)).all()


def test_costs_qwen3_next_against_hand_counts():
    from lib import costs_qwen3_next as costs
    cfg = config()
    assert costs.kinds(cfg) == (9, 3) and costs.expert_layers(cfg) == 12
    assert costs.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    assert costs.router_params(cfg) == 2048 * 512
    assert costs.shared_params(cfg) == 3_145_728 + 2048
    assert costs.held_pick_share(cfg) == 0.125
    # a slot's state of a layer: 32 x 128 x 128 float32 and 3 rows of 8192 bfloat16
    assert costs.gdn_state_bytes(cfg) == 2_097_152 and costs.gdn_history_bytes(cfg) == 49_152
    # the roofline of `gdn/recur` counts what moves under it: the state alone
    assert costs.gdn_decode_bytes(cfg, 64 * 9) == 2 * 576 * 2_097_152
    assert costs.gdn_prefill_flops(cfg, 9) == 9 * 32 * 6.0 * 128 * 128
    assert costs.kv_row_bytes(cfg) == 2048 and costs.decode_rows_bytes(cfg, 7) == 7 * 2048
    # the causal triangle: 3 layers x 16 heads x 4 x 256 a pair of rows
    assert costs.attn_prefill_flops(cfg, [4]) == 3 * 16 * 4.0 * 256 * 10
    assert costs.attn_prefill_flops(cfg, [4, 2]) == 3 * 16 * 4.0 * 256 * 13
    assert costs.moe_decode_bytes(cfg, 64, 1) == 2 * (64 * 3_145_728 + 3_147_776 + 1_048_576)
    assert costs.moe_flops(cfg, 1, 15) == 2.0 * (12 * (3_147_776 + 1_048_576) + 15 * 3_145_728)
    assert costs.gdn_params(cfg) == 2048 * 12352 + 4096 * 2048
    assert costs.attention_params(cfg) == 27_262_976
    # the file's count has the vectors too (norms, filters, decays): 0.03% more
    assert 0 < cfg["bytes"]["weights_bf16"] - costs.weight_bytes(cfg) < 1_000_000
    assert cfg["bytes"]["weights_parameters"] == 2_929_374_400
    assert cfg["bytes"]["state_bytes_a_slot"] == 9 * (2_097_152 + 49_152) == 19_316_736
    assert cfg["bytes"]["gated_deltanet_mixer_parameters"] == 33_718_464
    assert cfg["bytes"]["gated_attention_mixer_parameters"] == 27_263_488
    assert cfg["bytes"]["attention_bytes_a_token"] == 6144


def hand_made_run():
    """A traced window of 6 s: 20 prefills (the trace's own: ten of 2,048 rows and ten of
    8,192) with 0.5 s under `gdn/recur`, 0.4 s under `attn/full` and 0.8 s under `moe/*`;
    25 decode dispatches of 8 steps with 1.2 s under `gdn/recur`, 0.6 s under the other
    `gdn/*`, 0.9 s in the grouped paged kernel and 1.0 s under `moe/*`; over the window 250
    dispatches and 170 prefills."""
    scopes = {"jit_prefill_impl": {"scopes": {"gdn/recur": 0.5, "gdn/project": 0.4,
                                              "moe/experts": 0.6, "moe/shared": 0.2,
                                              "attn/full": 0.4, "attn/gate": 0.01},
                                   "kernels": {"_causal_rows_call": 0.4, "kda_chunk": 0.45}},
              "jit_chunk_impl": {"scopes": {"gdn/recur": 1.2, "gdn/conv": 0.2, "gdn/gate": 0.1,
                                            "gdn/project": 0.3, "attn/full": 0.95,
                                            "moe/experts": 0.8, "moe/shared": 0.2},
                                 "kernels": {"paged_attention_grouped": 0.9, "kda_step": 1.1}}}
    trace = {"busy_s": 5.9, "window_s": 6.0,
             "module_s": {"jit_prefill_impl": 2.3, "jit_chunk_impl": 3.6},
             "module_whole_s": {"jit_prefill_impl": 2.3, "jit_chunk_impl": 3.6},
             "module_runs": {"jit_prefill_impl": 20, "jit_chunk_impl": 25}}
    records = [{"ok": True, "sent": 0.1 * i, "first": 0.25 * i + 0.1,
                "prompt_len": 2048 if i % 2 else 8192} for i in range(20)]
    records += [{"ok": True, "sent": 7.0 + i, "first": 8.0 + i, "prompt_len": 16384}
                for i in range(10)]                       # behind the trace: not its prompts
    steps, tokens = 250 * 8, 170 * 6656
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100, "prefills": 50},
            "counters1": {"dispatches": 350, "prefills": 220},
            "model0": dict.fromkeys(
                ("gdn_state_steps", "gdn_prefill_rows", "decode_rows_full",
                 "decode_experts_touched", "decode_moe_passes", "moe_picks_routed",
                 "moe_picks_held", "decode_moe_picks_routed", "decode_moe_picks_held"), 0),
            "model1": {"gdn_state_steps": steps * 64 * 9, "gdn_prefill_rows": tokens * 9,
                       "decode_rows_full": steps * 64 * 7000 * 3,
                       "decode_experts_touched": steps * 12 * 40, "decode_moe_passes": steps * 12,
                       "moe_picks_routed": (steps * 64 + tokens) * 12 * 10,
                       "moe_picks_held": (steps * 64 + tokens) * 12 * 10 // 8,
                       "decode_moe_picks_routed": steps * 64 * 12 * 10,
                       "decode_moe_picks_held": steps * 64 * 12 * 10 // 8},
            "state": {"blocks_total": 128, "peak_blocks_used": 128},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_qwen3_next as costs, traced_prompts
    run = hand_made_run()
    cfg = run["config"]
    # the trace's own prompts: those whose first token fell inside its six seconds
    assert sorted(set(traced_prompts.lens(run))) == [2048, 8192]
    assert len(traced_prompts.lens(run)) == 20
    assert reader("layer_metrics", "gdn_time_share")(run) == pytest.approx(100 * 2.7 / 5.9)
    # 25 traced dispatches of 8 steps x 64 slots x 9 layers x 2 x 2,097,152 B
    gdn = reader("layer_metrics", "gdn_decode_hbm_roofline")(run)
    assert gdn == pytest.approx(100 * 25 * 8 * 576 * 2 * 2_097_152 / 819e9 / 1.2)
    assert 0 < gdn < 100
    rows = 10 * (2048 + 8192)
    pre = reader("layer_metrics", "gdn_prefill_flops_roofline")(run)
    assert pre == pytest.approx(100 * costs.gdn_prefill_flops(cfg, rows * 9) / 197e12 / 0.5)
    assert 0 < pre < 100
    assert reader("layer_metrics", "state_pool_peak_share")(run) == 100.0
    gqa = reader("layer_metrics", "gqa_decode_hbm_roofline.qwen3next")(run)
    assert gqa == pytest.approx(100 * 25 * 8 * 64 * 7000 * 3 * 2048 / 819e9 / 0.9)
    assert 0 < gqa < 100
    attn = reader("layer_metrics", "attn_prefill_flops_roofline.qwen3next")(run)
    assert attn == pytest.approx(
        100 * 10 * costs.attn_prefill_flops(cfg, [2048, 8192]) / 197e12 / 0.4)
    assert 0 < attn < 100
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.qwen3next")(run)
    assert moe == pytest.approx(
        100 * 25 * 8 * costs.moe_decode_bytes(cfg, 12 * 40, 12) / 819e9 / 1.0)
    assert 0 < moe < 100
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.qwen3next")(run)
    assert moe_pre == pytest.approx(
        100 * costs.moe_flops(cfg, rows, rows * 12 * 10 / 8) / 197e12 / 0.8)
    assert 0 < moe_pre < 100
    # a trace that cut a prefill at its edge: the count scales, the prompts stay the trace's
    cut = dict(run, trace=dict(run["trace"], module_s=dict(run["trace"]["module_s"],
                                                           jit_prefill_impl=2.3 * 0.95)))
    assert reader("layer_metrics", "attn_prefill_flops_roofline.qwen3next")(cut) \
        == pytest.approx(0.95 * attn)
    # the accepted readers this cell is appended to read the same tables
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(100 * 1.35 / 5.9)
    assert reader("layer_metrics", "moe_time_share")(run) == pytest.approx(100 * 1.8 / 5.9)
    assert reader("layer_metrics", "moe_shared_time_share")(run) == pytest.approx(100 * 0.4 / 5.9)
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(0.125)
    assert reader("layer_metrics", "decode_step_ms.moonlight")(run) == pytest.approx(
        1e3 * 3.6 / (25 * 8))
    assert reader("layer_metrics", "prefill_share.moonlight")(run) == pytest.approx(100 * 2.3 / 5.9)
    assert reader("layer_metrics", "prefills_per_chunk")(run) == pytest.approx(170 / 250)
    # a program without the scopes or the counters (the parent commit): nothing, no error
    bare = dict(run, scopes={m: dict(t, scopes={"ffn/dense": 1.0}, kernels={})
                             for m, t in run["scopes"].items()},
                model0={}, model1={}, state=None)
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(
            dict(run, scopes=None, model0={}, model1={}, state=None)) is None
        assert reader("layer_metrics", name)(dict(run, trace=None)) is None
    # another model's configuration: the readers that count with this one's costs say nothing
    other = dict(run, config={"num_shared_experts": 4})
    for name in NEW[3:]:
        assert reader("layer_metrics", name)(other) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="longmix-offline", chips=1)
    assert len(cell["why"]) <= 200
    assert len(bench["workloads"]) >= 13 and len(bench["configs"]) >= 11
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    body = config()
    assert entry["source"] == body["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == body["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert body["deployment"]["chips that share a layer"] == 8
    assert body["deployment"]["summary"].startswith(
        "a v5e-32: eight chips share each layer, four pipeline stages")
    # every width as published; the counts that are a chip's share beside the published ones
    published = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
                 "num_key_value_heads": 2, "linear_num_key_heads": 16,
                 "linear_num_value_heads": 32, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
                 "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
                 "num_experts_per_tok": 10, "full_attention_interval": 4,
                 "partial_rotary_factor": 0.25, "rope_theta": 10000000, "rms_norm_eps": 1e-6,
                 "norm_topk_prob": True, "tie_word_embeddings": False}
    assert {k: body[k] for k in published} == published
    assert (body["num_experts"], body["published"]["num_experts"]) == (64, 512)
    assert (body["vocab_size"], body["published"]["vocab_size"]) == (18992, 151936)
    assert (body["num_hidden_layers"], body["max_position_embeddings"]) == (12, 17408)
    assert (body["published"]["num_hidden_layers"],
            body["published"]["max_position_embeddings"]) == (48, 262144)
    for key in ("provenance", "norm", "gdn_projections", "gdn_conv", "gdn_l2", "gdn_l2_eps",
                "gdn_beta_and_decay", "gdn_key_sharing", "gdn_gate", "gdn_a_log_dt_bias",
                "gdn_state_dtype", "attention_query_gate", "attention_rotary", "router",
                "shared_expert", "multi_token_prediction", "initializer_range", "layer_kinds"):
        assert key in body["assumed"], key
    # the catalog's row: every key of its `config` is in the file, changed only if reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == entry["source"]
        differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
        assert differs == set(body["reduced"])
    # the program's config from the file: the kinds by the interval
    from lib import qwen3_next
    program = qwen3_next.qwen3_next_config(body)
    assert [i for i, t in enumerate(program.layer_types) if t == "full_attention"] == [3, 7, 11]
    assert program.experts_held == (0, 64) and program.n_routed_experts == 512
    assert program.vocab_slice == (0, 18992, 151936) and program.state_shape == (32, 128, 128)
    assert program.rotary_dim == 64 and program.attention.head_dim == 256
    assert program.attention.attention_scale is None and program.conv_width == 8192
    assert [s.name for s in program.cache_specs()] == ["full", "gdn", "conv"]
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert set(NEW) <= set(reported)
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
            "moe_shared_time_share", "moe_held_pick_share", "expert_load_max_over_mean",
            "decode_step_ms.moonlight", "prefill_share.moonlight",
            "head_time_share.offline", "norm_time_share.offline", "state_pool_peak_share",
            "engine_build_s", "attn_full_time_share"} <= set(reported)
    # the readers that count with another model's costs or stages do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline", "mla_decode_hbm_roofline",
                "mla_attn_time_share", "moe_decode_hbm_roofline.kimi", "stage_named_share.offline",
                "kda_time_share", "ssd_time_share", "gqa_decode_hbm_roofline",
                "gqa_decode_hbm_roofline.granite"} & set(reported)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        # a later cell may be appended behind this one: by name, not by position or count
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s" and m["unit"] == "%"
        assert m["source"] == "device_trace"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = reader("layer_metrics", name)
        assert module
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["gdn_time_share"] == layers["gdn_decode_hbm_roofline"] \
        == layers["gdn_prefill_flops_roofline"] == "gated delta-rule mixer"
    assert layers["moe_decode_hbm_roofline.qwen3next"] == layers["moe_time_share"]
    assert layers["gqa_decode_hbm_roofline.qwen3next"] == layers["gqa_decode_hbm_roofline"] \
        == layers["attn_prefill_flops_roofline.qwen3next"]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "longmix-offline.json")) as f:
        mix = json.load(f)
    # ISSUE 56's NAMED traffic, letter for letter, or a named fall-back (kv_blocks 4096 + 1;
    # then 56 slots with 64 clients)
    assert mix["mode"] == MODE
    assert mix["requests"] == {
        "prompt_lens": [1024, 2048, 3072, 4096, 6144, 8192, 12288, 16384],
        "max_new_tokens": [256, 512, 1024], "temperature": 0.8}
    engine = dict(mix["engine"])
    assert (engine.pop("num_slots"), mix["clients"]) in ((64, 72), (56, 64))
    assert engine.pop("kv_blocks") in (4609, 4097)
    assert engine == {"prefill_buckets": [2048, 4096, 8192, 12288, 16384], "max_len": 17408,
                      "block_size": 128}
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (24, 4, 1.0, 6.0)
    # the row pool is sized from the traffic: the mean reservation and three deviations
    import itertools
    import statistics
    pages = [-(-(p + a) // 128) for p, a in itertools.product(
        mix["requests"]["prompt_lens"], mix["requests"]["max_new_tokens"])]
    mean, dev = statistics.fmean(pages), statistics.pstdev(pages)
    assert mean == pytest.approx(56.7, abs=0.05)
    wanted = 64 * mean + 3 * dev * 64 ** 0.5
    assert 64 * mean == pytest.approx(3627, abs=1) and wanted - 64 * mean == pytest.approx(942, abs=3)
    assert wanted < 4608 < 64 * 136
