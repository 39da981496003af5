"""The `serve-closed-model` mode end to end on the CPU at a tiny size: the
server built from the configuration's `architecture`, the model's counters at
both ends of the window, the check against the configuration's own reference.
Counts and control flow only."""

from test_rehearsal import Ctx, mode, reader

TINY = {"architecture": "DeepseekV3ForCausalLM", "vocab_size": 211, "hidden_size": 64,
        "num_hidden_layers": 3, "num_attention_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts_per_tok": 2, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "rope_theta": 50000,
        "max_position_embeddings": 64, "q_lora_rank": None, "n_group": 1, "topk_group": 1,
        "scoring_func": "sigmoid", "norm_topk_prob": True, "moe_layer_freq": 1,
        "tie_word_embeddings": False, "attention_bias": False, "hidden_act": "silu",
        "assumed": {"initializer_range": 0.08}}


def test_the_mode_serves_the_model_and_reads_its_counters(tmp_path):
    traffic = {"mode": "serve-closed-model", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 8},
               "requests": {"prompt_lens": [8, 12, 20, 28], "max_new_tokens": [3, 5, 8],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path, traffic)
    ctx.config = TINY
    run = mode("serve-closed-model").run(ctx)
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "Moonlight-16B-A3B" and facts["checked"] > 0
    # the two limits of the check: most positions within the margin, and the
    # positions clear of a tie in the picks (if any) every one
    assert facts["share_within_margin"] >= facts["min_share_within"]
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["max_logit_deficit"] is None or facts["max_logit_deficit"] <= facts["logit_margin"]
    # on the CPU the decode step gathers, and that alone makes the run not
    # correct: on the chip the latent kernel has to be what ran
    assert run["why_incorrect"] == ["the decode step gathered: the latent kernel did not run"]
    routed = sum(facts["expert_tokens_in_window"])
    assert routed > 0 and routed % 2 == 0                     # 2 experts a token
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("router_tokens", "decode_router_tokens", "decode_moe_passes")}
    assert routed == 2 * moved["router_tokens"]
    assert 0 < moved["decode_router_tokens"] <= moved["router_tokens"]
    run.update(config=TINY, peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "expert_load_max_over_mean")(run) >= 1.0
    assert reader("layer_metrics", "kv_used_peak_share")(run) > 0
    # no trace, no number: the readers of the device's time report nothing
    for name in ("moe_time_share", "moe_decode_hbm_roofline", "moe_prefill_flops_roofline",
                 "mla_attn_time_share", "mla_decode_hbm_roofline", "decode_step_ms.moonlight",
                 "prefill_share.moonlight"):
        assert reader("layer_metrics", name)(run) is None, name
