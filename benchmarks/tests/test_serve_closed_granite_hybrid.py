"""The `serve-closed-granite-hybrid` mode end to end on the CPU at a tiny size (the server
built by lib/granite_hybrid.py over the attention layer's rows and two state groups and a
SHARE of the experts, the reference reference/granite_hybrid_ref.py token by token with the
same share), its own copy of `serve-closed-model` left as Moonlight's, the wrong programs'
facility, `lib/costs_granite_hybrid.py` against hand counts, the new readers on hand-made
records, the state-step limit on a hand-made arena, and the new entries' contract, found by
NAME. Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "granite-h-shortchat-offline"
CONFIG = "granite-4.0-h-small"
MODE = "serve-closed-granite-hybrid"
NEW = ("ssd_time_share", "ssd_decode_hbm_roofline", "ssd_prefill_flops_roofline",
       "moe_decode_hbm_roofline.granite", "moe_prefill_flops_roofline.granite",
       "gqa_decode_hbm_roofline.granite")
KINDS = ["mamba", "attention", "mamba", "mamba"]


def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def tiny():
    """The configuration file's keys at a small size: 4 layers with one of attention, 4 of 8
    experts held (ids 2..5), 96 of 768 vocabulary rows."""
    cfg = config()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
               mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8, intermediate_size=32,
               shared_intermediate_size=48, num_local_experts=4, experts_held_first=2,
               vocab_size=96, num_experts_per_tok=3, num_hidden_layers=4, layer_types=KINDS,
               max_position_embeddings=64,
               published=dict(cfg["published"], num_local_experts=8, vocab_size=768),
               assumed=dict(cfg["assumed"], initializer_range=0.08))
    return cfg


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": MODE, "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 4},
               "requests": {"prompt_lens": [6, 8, 16, 20, 32], "max_new_tokens": [3, 7],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("granite_hybrid"), traffic, seconds=4.0)
    ctx.config = tiny()
    module = mode(MODE)
    module.EARLY, module.LATE = 4, 2
    os.environ["GRANITE_WRONG_REFERENCE"] = "no_decay,residual_one,bucket_end,state_bf16,scan_bf16"
    try:
        return module, module.run(ctx)
    finally:
        del os.environ["GRANITE_WRONG_REFERENCE"]


def test_the_mode_serves_the_share_through_the_state_groups_and_judges_it(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "granite-4.0-h-small" and facts["checked"] > 0
    assert facts["experts_held"] == {"first": 2, "count": 4, "of": 8}
    assert facts["vocab_slice"] == {"first": 0, "rows": 96, "of": 768}
    assert facts["padded_prompts_checked"] >= 1          # 6, 8 and 20 pad to 16 and 32
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert (facts["early"], facts["late"]) == (4, 2)
    # three cache groups of the one manager: the attention rows and the two state groups
    groups = run["cache_groups"]
    assert list(groups) == ["full", "ssm", "conv"]
    assert (groups["full"]["layers"], groups["ssm"]["layers"]) == (1, 3)
    assert groups["ssm"]["pages_a_slot"] == groups["conv"]["pages_a_slot"] == 1
    assert groups["ssm"]["dtype"] == "float32" and groups["ssm"]["blocks_total"] == 4
    assert run["state"]["blocks_total"] == 8 and run["state"]["recurrence_path"] == "xla"
    assert 0 < run["state"]["peak_blocks_used"] <= 8
    assert reader("layer_metrics", "state_pool_peak_share")(run) == pytest.approx(
        100.0 * run["state"]["peak_blocks_used"] / 8)
    assert facts["prefix_cache"].startswith("off")
    # on the CPU everything gathers, and that alone makes the run not correct
    assert facts["decode_attention"] == {"full": "gather"}
    assert any("gathered" in why for why in run["why_incorrect"])
    assert any("recurrence" in why for why in run["why_incorrect"])
    # the facility judged the wrong programs by the same limits and touched no verdict
    wrong = facts["wrong_references"]
    assert set(wrong) == {"no_decay", "residual_one", "bucket_end", "state_bf16", "scan_bf16"}
    for name, reading in wrong.items():
        assert reading["positions"] == facts["positions"]
        assert reading["sampled_positions"] == facts["sampled_positions"] > 0
        assert set(reading["fails"]) <= {"early", "all", "late", "sampled", "handover"} | (
            {"state_step"} if name == "state_bf16" else
            {"scan"} if name == "scan_bf16" else set())
    # limit 6: what the ENGINE's own prefill programs left in the engine's own arena, one
    # prompt a bucket admitted behind the window by the scheduler that served the window,
    # against the reference at the prompt's last row; the bucket's end is another state
    hand = facts["handover"]
    assert list(hand["by_bucket"]) == [16, 32] and hand["executables_added"] == 0
    assert 8 < hand["by_bucket"][16]["prompt_len"] < 16 < hand["by_bucket"][32]["prompt_len"] < 32
    assert all(read["prompt_len"] % 8 for read in hand["by_bucket"].values())   # off a chunk's edge
    assert hand["error"] == facts["handover_error"] == max(
        read[kind] for read in hand["by_bucket"].values()
        for kind in ("state", "history", "rows")) < module.MAX_HANDOVER_ERROR
    assert "handover" not in facts["fails"] and "handover" in wrong["bucket_end"]["fails"]
    assert "handover_program" not in facts["fails"]
    assert wrong["bucket_end"]["handover_error"] == hand["wrong"]["bucket_end"] > 0.1
    assert hand["wrong"]["state_bf16"] < module.MAX_HANDOVER_ERROR
    # limit 7: the program's chunked scan at each bucket's shape (chunks of 8: two and four
    # of them) on its own operands against the recurrence token by token; operands rounded to
    # bfloat16 are told by it and by nothing else
    scan = facts["scan"]
    assert list(scan["by_bucket"]) == [16, 32] and scan["layers"] == 3
    assert scan["error"] == facts["scan_error"] < 1e-5 < module.MAX_SCAN_ERROR
    assert module.MAX_SCAN_ERROR < 1e-3 < scan["error_products_bf16"] < 2e-2
    assert "scan" not in facts["fails"] and wrong["scan_bf16"]["fails"] == ["scan"]
    assert wrong["scan_bf16"]["scan_error"] == scan["error_products_bf16"]
    assert facts["sampler_top_lattice_lanes"] == 0        # once in 2^24 lanes
    assert wrong["residual_one"]["share_within_margin"] < 1.0
    # limit 5: sampled requests under their own Gumbel draws, rebuilt from their seeds; a
    # program with another residual draws other tokens along the same sequence
    assert 0 < facts["sampled_checked"] <= module.SAMPLED_REQUESTS
    assert 0.0 <= facts["sampled_within_margin"] <= 1.0
    assert all(0.0 <= r["sampled_within_margin"] <= 1.0 and r["sampled_max_deficit"] >= 0.0
               for r in wrong.values())
    records = [r for r in run["records"] if r.get("sampled")]
    assert records and all(r["output"] is None and not r["greedy"] for r in records)
    assert all(len(r["sampled"]["tokens"]) == r["max_new_tokens"] for r in records if r["ok"])
    # limit 4: the served blocks one step on, by the program's own step, against the
    # float32 recurrence; a state kept in bfloat16 is told by it and by nothing else
    step = facts["state_step"]
    assert (step["path"], step["slots"], step["layers"], step["state_dtype"]) == (
        "xla", 4, 3, "float32")
    assert step["error"] == facts["state_step_error"] < 1e-6 < module.MAX_STATE_STEP_ERROR
    assert module.MAX_STATE_STEP_ERROR < 1e-4 < step["error_state_bf16"]
    assert "state_step" not in facts["fails"]
    assert "state_step" in wrong["state_bf16"]["fails"]
    assert wrong["state_bf16"]["state_step_error"] == step["error_state_bf16"]


def test_the_counters_of_the_state_and_the_picks(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("ssd_state_steps", "ssd_prefill_rows", "decode_rows_full",
                       "moe_picks_routed", "moe_picks_held", "router_tokens",
                       "decode_router_tokens")}
    assert moved["ssd_state_steps"] > 0 and moved["ssd_state_steps"] % 3 == 0
    assert moved["ssd_prefill_rows"] > 0 and moved["ssd_prefill_rows"] % 3 == 0
    # four expert layers, three mamba layers: a live slot a step counts 4 router tokens
    assert moved["ssd_state_steps"] * 4 == moved["decode_router_tokens"] * 3
    assert moved["decode_rows_full"] > moved["ssd_state_steps"] // 3
    assert moved["moe_picks_routed"] == 3 * moved["router_tokens"]
    assert 0 < moved["moe_picks_held"] < moved["moe_picks_routed"]
    run.update(config=tiny(), peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert 0.1 < reader("layer_metrics", "moe_held_pick_share")(run) < 0.9
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    for name in NEW:
        assert reader("layer_metrics", name)(run) is None, name   # no trace, no number


def test_the_copy_is_the_modes_own_and_the_stage_tables_know_ssd(served):
    module, _ = served
    assert mode("serve-closed-model").ARCHITECTURES \
        == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert mode("serve-closed-model").PAD_TO == 2048 and module.base.PAD_TO == 512
    assert module.base.ARCHITECTURES["granite_hybrid"] == ("granite_hybrid",
                                                           "granite_hybrid_ref")
    from lib import stage_times
    stages = stage_times.STAGES + ("ssd/*",)
    assert "ssd/*" not in stage_times.STAGES
    for tf_op, stage in (
            ("jit(chunk_impl)/while/body/closed_call/ssd/step/pallas_call:", "ssd/step"),
            ("jit(prefill_impl)/ssd/scan/while/body/dot_general:", "ssd/scan"),
            ("jit(prefill_impl)/ssd/conv/mul:", "ssd/conv"),
            ("jit(chunk_impl)/while/body/closed_call/attn/full/pallas_call:", "attn/full"),
            ("jit(chunk_impl)/while/body/closed_call/moe/combine/gather:", "moe/combine")):
        assert stage_times.stage_of(tf_op, stages) == stage
    assert stage_times.stage_of("jit(prefill_impl)/ssd/conv/mul:") is None
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
    drawn[:8] = 2 * module.SAMPLED_MARGIN      # 2% of them over the margin
    read = module._limits(clean, early, late, request, 3e-7, drawn)
    assert read["fails"] == ["sampled"] and read["sampled_within_margin"] == pytest.approx(0.98)
    assert module._limits(clean, early, late, request, None, np.zeros(0))["fails"] == []
    # limit 6 is a number too
    assert module._limits(clean, early, late, request, 3e-7, None, 0.02)["fails"] == []
    assert module._limits(clean, early, late, request, 3e-7, None, 0.7)["fails"] == ["handover"]
    # the margins are on logits after / 16: a sixteenth of the other modes'
    assert module.base.LOGIT_MARGIN <= 0.03 / 8 and module.SHARE_MARGIN <= 0.1 / 8


@pytest.mark.parametrize("kept_in", ["float32", "bfloat16"])
def test_the_state_step_tells_a_state_kept_below_float32(kept_in):
    """Limit 4 on a hand-made arena: the program's own step on float32 blocks is the
    reference's to float32 rounding; the SAME program over a state arena kept in bfloat16
    (the group's type a config key) reads a hundred times the limit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import granite_hybrid as builder
    from reference import granite_hybrid_ref

    module = mode(MODE)
    cfg = tiny()
    cfg["assumed"] = dict(cfg["assumed"], mamba_state_dtype=kept_in)
    model_cfg = builder.granite_hybrid_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.bfloat16)
    specs = {spec.name: spec for spec in model_cfg.cache_specs()}
    key = jax.random.split(jax.random.PRNGKey(3), 2)
    state = (0.1 * jax.random.normal(key[0], (3, 1, 6) + tuple(specs["ssm"].state_shape))
             ).astype(kept_in)
    conv = (0.1 * jax.random.normal(key[1], (3, 1, 6) + tuple(specs["conv"].state_shape))
            ).astype(jnp.bfloat16)
    read = module.state_step_readings(builder.program, granite_hybrid_ref, model_cfg, params,
                                      (None, state, conv), [5, 9, 11], 12345)
    assert (read["slots"], read["layers"], read["state_dtype"]) == (5, 3, kept_in)
    assert 1e-4 < read["error_state_bf16"] < 1e-2
    if kept_in == "float32":
        assert read["error"] < 1e-6
    else:
        assert read["error"] > 100 * module.MAX_STATE_STEP_ERROR
    assert np.isfinite(read["error"])


def test_the_handover_tells_a_history_not_carried_and_a_rotated_key():
    """Limit 6 on an engine built here: what its scheduler's prefill of a prompt that ends
    mid-bucket left in its arena against the reference at the prompt's last row, a bucket
    at a time, and the wrong references' readings beside it; limit 7 on the same prompts."""
    import jax.numpy as jnp
    from lib import granite_hybrid as builder
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from reference import granite_hybrid_ref

    module = mode(MODE)
    cfg = tiny()
    model_cfg = builder.granite_hybrid_config(cfg)
    params = builder.serving_params(cfg, 7, jnp.float32)
    engine = ServingEngine(params, model_cfg, ServingConfig(
        num_slots=2, prefill_buckets=(16, 32), max_len=64, block_size=4))
    read = module.handover_readings(
        engine, granite_hybrid_ref, cfg, params, 12345, model_cfg.mamba_chunk,
        ("conv_reset", "bucket_end", "rotary", "attn_scale", "no_decay", "scan_bf16"))
    assert list(read["by_bucket"]) == [16, 32]
    # a cold engine: the two buckets' programs and the first token's sampler
    assert read["executables_added"] == 3
    assert [len(tokens) for _, tokens in read["prompts"]] == [
        read["by_bucket"][b]["prompt_len"] for b in (16, 32)]
    assert engine.kv.free_count == 2                 # the probes' slots are given back
    # float32 weights (rows and history ride in the pool's bfloat16): rounding alone
    for at in read["by_bucket"].values():
        assert at["state"] < 1e-2 and at["history"] < 1e-2 and at["rows"] < 1e-2
    assert read["wrong"]["conv_reset"] == pytest.approx(1.0)       # a history of zeros
    assert read["wrong"]["bucket_end"] > 0.1 and read["wrong"]["rotary"] > 0.1
    assert read["wrong"]["no_decay"] > 0.1
    assert read["wrong"]["attn_scale"] < 0.1       # the keys and values do not move: tokens'
    assert read["wrong"]["scan_bf16"] < 0.02       # under limit 6's floor: limit 7's
    again = module.handover_readings(engine, granite_hybrid_ref, cfg, params, 12345,
                                     model_cfg.mamba_chunk)
    assert again["executables_added"] == 0 and again["error"] == read["error"]
    scan = module.scan_readings(builder.program, granite_hybrid_ref, model_cfg, params,
                                read["prompts"])
    assert scan["error"] < 1e-5 and 1e-3 < scan["error_products_bf16"] < 2e-2
    assert module._limits(*_clean(), scan_error=scan["error"])["fails"] == []
    assert module._limits(*_clean(), scan_error=scan["error_products_bf16"])["fails"] == ["scan"]


def _clean():
    import numpy as np
    return np.zeros(4), np.zeros(4, bool), np.zeros(4, bool), np.zeros(4, int)


def test_the_references_draws_are_the_samplers_and_finite_where_it_is_not():
    """Limit 5's noise is the reference's own: its threefry against Random123's published
    vectors, its draws against the program's sampler position by position, bit for bit (the
    counters and the float32 lattice are the contract), and the top of the lattice, where
    the sampler's log(u) reads +inf, finite."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.serving import sampling
    from reference import granite_hybrid_ref as ref

    for key, counter, words in (((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
                                ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF),
                                 (0x1CB996FC, 0xBB002BE7)),
                                ((0x13198A2E, 0x03707344), (0x243F6A88, 0x85A308D3),
                                 (0xC4923A9C, 0x483DF7A0))):
        got = ref.threefry2x32(*map(np.uint32, key), *map(np.uint32, counter))
        assert tuple(int(w) for w in got) == words
    like = np.zeros((5, 96), np.float32)
    for seed in (1, 77, 2**31 + 5):
        noise, top = ref.gumbel_draws(np.uint32(seed), like)
        key = sampling.sample_key(jnp.uint32(seed))
        for position in range(5):
            np.testing.assert_array_equal(np.asarray(noise[position]),
                                          np.asarray(sampling.sample_gumbel(key, 96)))
            key = sampling.sample_split(key)
        assert top == 0 and np.isfinite(np.asarray(noise)).all()
    bits = np.asarray([0, 0xFF, 0xFFFFFE00, 0xFFFFFF00, 0xFFFFFFFF], np.uint32)
    edge = np.asarray(ref.gumbel_of_bits(bits))
    assert np.isfinite(edge).all() and edge[0] == edge[1] < -2.8 and edge[3] == edge[4]
    assert edge[3] == pytest.approx(25 * np.log(2.0), rel=1e-6) and edge[3] > edge[2]
    # the sampler's own transform on the same bits: the same but at the top, which is +inf
    k = (jnp.asarray(bits) >> 8).astype(jnp.float32)
    theirs = np.asarray(-jnp.log(-jnp.log((k + 0.5) * jnp.float32(2.0 ** -24))))
    np.testing.assert_array_equal(edge[:3], theirs[:3])
    assert np.isposinf(theirs[3:]).all()


def test_costs_granite_hybrid_against_hand_counts():
    from lib import costs_granite_hybrid as costs
    cfg = config()
    assert costs.kinds(cfg) == (9, 1) and costs.expert_layers(cfg) == 10
    assert costs.expert_params(cfg) == 3 * 4096 * 768 == 9_437_184
    assert costs.router_params(cfg) == 4096 * 72 and costs.shared_params(cfg) == 18_874_368
    assert costs.held_pick_share(cfg) == 0.5
    # a slot's state of a layer: 128 x 64 x 128 float32 and 3 rows of 8448 bfloat16
    assert costs.ssd_state_bytes(cfg) == 4_194_304 and costs.ssd_history_bytes(cfg) == 50_688
    # the roofline of `ssd/step` counts what moves under `ssd/step`: the state alone
    assert costs.ssd_decode_bytes(cfg, 96 * 9) == 2 * 864 * 4_194_304
    assert costs.ssd_prefill_flops(cfg, 9) == 9 * 128 * 6.0 * 64 * 128
    assert costs.kv_row_bytes(cfg) == 4096 and costs.decode_rows_bytes(cfg, 7) == 7 * 4096
    assert costs.moe_decode_bytes(cfg, 36, 1) == 2 * (36 * 9_437_184 + 18_874_368 + 294_912)
    assert costs.moe_flops(cfg, 1, 50) == 2.0 * (10 * (18_874_368 + 294_912) + 50 * 9_437_184)
    assert costs.mamba_params(cfg) == 4096 * 16768 + 8192 * 4096 == 102_236_160
    assert costs.attention_params(cfg) == 41_943_040
    # 9.51 GB: the matrices are the file's count (it leaves the vectors out too)
    assert costs.weight_bytes(cfg) == cfg["bytes"]["weights_bf16"] == 9_513_336_832
    assert cfg["bytes"]["state_bytes_a_slot"] == 9 * (4_194_304 + 50_688)
    assert cfg["bytes"]["mamba_expert_layer_parameters"] == 461_144_064
    assert cfg["bytes"]["attention_expert_layer_parameters"] == 400_850_944


def hand_made_run():
    """A traced window of 6 s: 50 prefills with 0.3 s under `ssd/scan` and 0.8 s under
    `moe/*`; 25 decode dispatches of 8 steps with 2.4 s under `ssd/step`, 0.6 s under the
    other `ssd/*`, 0.1 s in the grouped paged kernel and 2.1 s under `moe/*`; over the window
    250 dispatches and 450 prefills of a mean 731 rows."""
    scopes = {"jit_prefill_impl": {"scopes": {"ssd/scan": 0.3, "ssd/project": 0.4,
                                              "moe/experts": 0.6, "moe/shared": 0.2,
                                              "attn/full": 0.05},
                                   "kernels": {"_causal_rows_call": 0.05}},
              "jit_chunk_impl": {"scopes": {"ssd/step": 2.4, "ssd/conv": 0.2, "ssd/gate": 0.1,
                                            "ssd/project": 0.3, "attn/full": 0.1,
                                            "moe/experts": 1.9, "moe/shared": 0.2},
                                 "kernels": {"paged_attention_grouped": 0.1, "ssd_step": 2.3}}}
    trace = {"busy_s": 5.9, "module_s": {"jit_prefill_impl": 1.6, "jit_chunk_impl": 4.3},
             "module_whole_s": {"jit_prefill_impl": 1.6, "jit_chunk_impl": 4.3},
             "module_runs": {"jit_prefill_impl": 50, "jit_chunk_impl": 25}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((438, 1024))]
    steps, tokens = 250 * 8, 450 * 731
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 8, "config": config(),
            "counters0": {"dispatches": 100, "prefills": 50},
            "counters1": {"dispatches": 350, "prefills": 500},
            "model0": dict.fromkeys(
                ("ssd_state_steps", "ssd_prefill_rows", "decode_rows_full",
                 "decode_experts_touched", "decode_moe_passes", "moe_picks_routed",
                 "moe_picks_held", "decode_moe_picks_routed", "decode_moe_picks_held"), 0),
            "model1": {"ssd_state_steps": steps * 96 * 9, "ssd_prefill_rows": tokens * 9,
                       "decode_rows_full": steps * 96 * 900,
                       "decode_experts_touched": steps * 10 * 36, "decode_moe_passes": steps * 10,
                       "moe_picks_routed": (steps * 96 + tokens) * 10 * 10,
                       "moe_picks_held": (steps * 96 + tokens) * 10 * 5,
                       "decode_moe_picks_routed": steps * 96 * 10 * 10,
                       "decode_moe_picks_held": steps * 96 * 10 * 5},
            "state": {"blocks_total": 192, "peak_blocks_used": 192},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_new_readers_on_a_hand_made_run():
    from lib import costs_granite_hybrid as costs
    run = hand_made_run()
    cfg = run["config"]
    assert reader("layer_metrics", "ssd_time_share")(run) == pytest.approx(100 * 3.7 / 5.9)
    # 25 traced dispatches of 8 steps x 96 slots x 9 layers x 2 x 4,194,304 B
    ssd = reader("layer_metrics", "ssd_decode_hbm_roofline")(run)
    assert ssd == pytest.approx(100 * 25 * 8 * 864 * 2 * 4_194_304 / 819e9 / 2.4)
    assert 0 < ssd < 100
    pre = reader("layer_metrics", "ssd_prefill_flops_roofline")(run)
    assert pre == pytest.approx(100 * 50 * costs.ssd_prefill_flops(cfg, 731 * 9) / 197e12 / 0.3)
    assert 0 < pre < 100
    assert reader("layer_metrics", "state_pool_peak_share")(run) == 100.0
    gqa = reader("layer_metrics", "gqa_decode_hbm_roofline.granite")(run)
    assert gqa == pytest.approx(100 * 25 * 8 * 96 * 900 * 4096 / 819e9 / 0.1)
    assert 0 < gqa < 100
    moe = reader("layer_metrics", "moe_decode_hbm_roofline.granite")(run)
    assert moe == pytest.approx(
        100 * 25 * 8 * costs.moe_decode_bytes(cfg, 10 * 36, 10) / 819e9 / 2.1)
    assert 0 < moe < 100
    moe_pre = reader("layer_metrics", "moe_prefill_flops_roofline.granite")(run)
    assert moe_pre == pytest.approx(
        100 * 50 * costs.moe_flops(cfg, 731, 731 * 10 * 5) / 197e12 / 0.8)
    assert 0 < moe_pre < 100
    # the accepted readers this cell is appended to read the same tables
    assert reader("layer_metrics", "attn_full_time_share")(run) == pytest.approx(100 * 0.15 / 5.9)
    assert reader("layer_metrics", "moe_time_share")(run) == pytest.approx(100 * 2.9 / 5.9)
    assert reader("layer_metrics", "moe_shared_time_share")(run) == pytest.approx(100 * 0.4 / 5.9)
    assert reader("layer_metrics", "moe_held_pick_share")(run) == pytest.approx(0.5)
    assert reader("layer_metrics", "decode_step_ms.moonlight")(run) == pytest.approx(
        1e3 * 4.3 / (25 * 8))
    assert reader("layer_metrics", "prefill_share.moonlight")(run) == pytest.approx(100 * 1.6 / 5.9)
    assert reader("layer_metrics", "prefills_per_chunk")(run) == pytest.approx(450 / 250)
    # a program without the scopes or the counters (the parent commit): nothing, no error
    bare = dict(run, scopes={m: dict(t, scopes={"ffn/dense": 1.0}, kernels={})
                             for m, t in run["scopes"].items()},
                model0={}, model1={}, state=None)
    for name in NEW:
        assert reader("layer_metrics", name)(bare) is None, name
        assert reader("layer_metrics", name)(
            dict(run, scopes=None, model0={}, model1={}, state=None)) is None
    # another model's configuration: the readers that count with this one's costs say nothing
    other = dict(run, config={"num_shared_experts": 4})
    for name in ("moe_decode_hbm_roofline.granite", "moe_prefill_flops_roofline.granite",
                 "gqa_decode_hbm_roofline.granite"):
        assert reader("layer_metrics", name)(other) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="shortchat-offline", chips=1)
    assert len(cell["why"]) <= 200
    assert len(bench["workloads"]) >= 12 and sum(w["chips"] == 4 for w in bench["workloads"]) >= 1
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    body = config()
    assert entry["source"] == body["source"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == body["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size", "max_position_embeddings"]
    assert all(key in body["reduced_note"] for key in body["reduced"])
    assert body["deployment"]["chips that share a layer"] == 2
    assert body["deployment"]["summary"].startswith("a v5e-8: two chips share each layer, four")
    # every width as published; the counts that are a chip's share beside the published ones
    published = {"hidden_size": 4096, "intermediate_size": 768, "shared_intermediate_size": 1536,
                 "num_attention_heads": 32, "num_key_value_heads": 8, "mamba_n_heads": 128,
                 "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
                 "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
                 "num_experts_per_tok": 10, "embedding_multiplier": 12,
                 "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
                 "logits_scaling": 16, "rms_norm_eps": 1e-5, "position_embedding_type": "nope",
                 "tie_word_embeddings": True}
    assert {k: body[k] for k in published} == published
    assert len(body["layer_types"]) == 40 and body["published"]["layer_types"] == body["layer_types"]
    assert [i for i, t in enumerate(body["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert (body["num_local_experts"], body["published"]["num_local_experts"]) == (36, 72)
    assert (body["vocab_size"], body["published"]["vocab_size"]) == (50176, 100352)
    assert (body["num_hidden_layers"], body["max_position_embeddings"]) == (10, 2560)
    assert (body["published"]["num_hidden_layers"],
            body["published"]["max_position_embeddings"]) == (40, 131072)
    for key in ("initializer_range", "in_proj_order", "conv_split_order", "gated_norm",
                "mamba_a_log_d_dt_bias", "mamba_state_dtype", "expert_halves", "provenance"):
        assert key in body["assumed"], key
    # the catalog's row: every key of its `config` is in the file, changed only if reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
        assert row["source_url"] == entry["source"]
        differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
        assert differs == set(body["reduced"])
    # the program's config from the file: the kinds by the published list
    from lib import granite_hybrid
    program = granite_hybrid.granite_hybrid_config(body)
    assert program.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert program.experts_held == (0, 36) and program.n_routed_experts == 72
    assert program.vocab_slice == (0, 50176, 100352) and program.state_shape == (128, 64, 128)
    assert program.attention.attention_scale == 0.0078125
    assert [s.name for s in program.cache_specs()] == ["full", "ssm", "conv"]
    reported = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert set(NEW) <= set(reported)
    assert {"tokens_per_dispatch.offline", "prefills_per_chunk", "kv_used_peak_share",
            "tick_host_ms.offline", "idle_named_share.offline", "moe_time_share",
            "moe_shared_time_share", "moe_held_pick_share", "expert_load_max_over_mean",
            "head_time_share.offline", "norm_time_share.offline", "state_pool_peak_share",
            "engine_build_s", "attn_full_time_share"} <= set(reported)
    # the readers that count with another model's costs or stages do not list this cell
    assert not {"moe_decode_hbm_roofline", "moe_prefill_flops_roofline", "mla_decode_hbm_roofline",
                "mla_attn_time_share", "moe_decode_hbm_roofline.kimi", "stage_named_share.offline",
                "kda_time_share", "gqa_decode_hbm_roofline"} & set(reported)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        # a later cell may be appended behind this one: by name, not by position or count
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s" and m["unit"] == "%"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert reader("layer_metrics", name)
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["ssd_time_share"] == "state-space mixer"
    assert layers["moe_decode_hbm_roofline.granite"] == layers["moe_time_share"]
    assert layers["gqa_decode_hbm_roofline.granite"] == layers["gqa_decode_hbm_roofline"]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "shortchat-offline.json")) as f:
        mix = json.load(f)
    # ISSUE 54's NAMED traffic: `num_slots` (and `clients` = slots + 8) alone may differ
    assert mix["mode"] == MODE
    assert mix["requests"] == {"prompt_lens": [128, 256, 384, 512, 768, 1024, 2048],
                               "max_new_tokens": [128, 256, 384, 512], "temperature": 0.8}
    engine = dict(mix["engine"])
    slots = engine.pop("num_slots")
    assert engine == {"prefill_buckets": [256, 512, 1024, 2048], "max_len": 2560,
                      "block_size": 128}
    assert slots % 8 == 0 and slots <= 96 and mix["clients"] == slots + 8
    assert (mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (4, 1.0, 6.0)
    assert mix["ramp_s"] >= 20
