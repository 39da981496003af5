"""The `serve-closed-xing` mode end to end on the CPU at a tiny size (the
server built by lib/xing.py, the reference reference/xing_ref.py, the mixer's
counters at both ends of the window), its own copy of `serve-closed-model` left
as Moonlight's on disk and in memory, and the three `hc_*` readers on hand-made
records. Counts and control flow only."""

import json
import os

import pytest

from test_rehearsal import Ctx, mode, reader

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
TINY = {"architecture": "xing4_0", "vocab_size": 211, "hidden_size": 64, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
        "q_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts_per_tok": 2, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": YARN, "max_position_embeddings": 64, "hc_mult": 4,
        "hc_sinkhorn_iters": 6, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 0, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
        "norm_topk_prob": True, "moe_layer_freq": 1, "ep_size": 1,
        "tie_word_embeddings": False, "attention_bias": False, "hidden_act": "silu",
        "assumed": {"initializer_range": 0.08}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    traffic = {"mode": "serve-closed-xing", "clients": 5, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0,
               "engine": {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64,
                          "block_size": 8},
               "requests": {"prompt_lens": [8, 12, 20, 28], "max_new_tokens": [3, 5, 8],
                            "temperature": 0.8}}
    ctx = Ctx(tmp_path_factory.mktemp("xing"), traffic)
    ctx.config = TINY
    module = mode("serve-closed-xing")
    return module, module.run(ctx)


def test_the_mode_serves_xing_and_judges_it_by_its_own_reference(served):
    module, run = served
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    facts = run["facts"]
    assert facts["model"] == "Xing4.0-29B-A4B" and facts["checked"] > 0
    assert facts["share_within_margin"] >= facts["min_share_within"] == module.base.MIN_SHARE_WITHIN
    assert facts["judged"] + facts["left_out"] == facts["positions"] > 0
    assert facts["max_logit_deficit"] is None or facts["max_logit_deficit"] <= facts["logit_margin"]
    # on the CPU the decode step gathers, and that alone makes the run not correct
    assert run["why_incorrect"] == ["the decode step gathered: the latent kernel did not run"]


def test_the_mixers_counters_ride_in_the_models_counters(served):
    _, run = served
    moved = {k: run["model1"][k] - run["model0"][k]
             for k in ("router_tokens", "hc_passes", "hc_rowsum_dev_ppm")}
    assert moved["router_tokens"] > 0
    # two sublayers a layer, in every prefill and every decode step with a live slot
    assert moved["hc_passes"] > 0 and moved["hc_passes"] % (2 * TINY["num_hidden_layers"]) == 0
    assert 0 <= moved["hc_rowsum_dev_ppm"] <= 5 * moved["hc_passes"]
    facts = run["facts"]
    assert facts["hc_passes"] == run["model1"]["hc_passes"]
    assert 0 <= facts["hc_rowsum_dev_ppm_a_pass"] <= 5 < facts["hc_rowsum_ppm_limit"]
    assert facts["moe_kernel_passes"] == 0                 # the CPU: `ragged_dot` ran
    run.update(config=TINY, peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "expert_load_max_over_mean")(run) >= 1.0
    # no trace, no number
    for name in ("hc_time_share", "hc_prefill_hbm_roofline", "hc_decode_us_per_step",
                 "moe_time_share", "mla_attn_time_share", "decode_step_ms.moonlight"):
        assert reader("layer_metrics", name)(run) is None, name


def test_a_mixer_whose_rows_do_not_sum_to_one_is_not_correct(served, monkeypatch):
    """The third limit: the in-graph counter over the limit alone refuses a run."""
    module, run = served
    ok = dict(run, why_incorrect=[], correct=True, facts=dict(run["facts"]))
    monkeypatch.setattr(module.base, "run", lambda ctx: ok)
    assert module.run(None)["correct"]
    passes = run["model1"]["hc_passes"]
    bad = dict(ok, model1=dict(run["model1"], hc_rowsum_dev_ppm=4244 * passes),
               why_incorrect=[], correct=True)
    monkeypatch.setattr(module.base, "run", lambda ctx: bad)
    out = module.run(None)
    assert not out["correct"] and "4244.0 parts per million" in out["why_incorrect"][0]
    gone = dict(ok, model1={}, why_incorrect=[], correct=True)
    monkeypatch.setattr(module.base, "run", lambda ctx: gone)
    assert not module.run(None)["correct"]


def test_the_copy_is_the_modes_own_and_moonlights_stays_moonlights(served):
    module, _ = served
    moonlight = mode("serve-closed-model")
    assert moonlight.ARCHITECTURES == {"DeepseekV3ForCausalLM": ("moonlight", "moonlight_ref")}
    assert module.base.ARCHITECTURES["xing4_0"] == ("xing", "xing_ref")
    from lib import scope_reduce
    assert module.scopes is not scope_reduce and module.base.scope_reduce is module.scopes
    tf_op = "jit(chunk_impl)/while/body/closed_call/hc/coeff/div:"
    assert scope_reduce.scope_of(tf_op) is None            # the shared reducer: untouched
    assert module.scopes.scope_of(tf_op) == "hc/coeff"
    assert module.scopes.scope_of("jit(prefill_impl)/hc/post/add:") == "hc/post"
    assert module.scopes.scope_of("jit(prefill_impl)/mla/attend/cond/pallas_call:") == "mla/attend"
    assert module.scopes.scope_of("jit(prefill_impl)/arch/pre/add:") is None


def hand_made_run():
    """A traced window of 6 s: 10 prefills (mean prompt 6000 tokens) with 0.3 s
    under `hc/*`, 4 decode dispatches of 32 steps with 0.0128 s under `hc/*`."""
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    scopes = {"jit_prefill_impl": {"scopes": {"hc/coeff": 0.1, "hc/pre": 0.05, "hc/post": 0.15,
                                              "moe/experts": 1.0}, "kernels": {}, "attend_s": 0.0},
              "jit_chunk_impl": {"scopes": {"hc/coeff": 0.0064, "hc/pre": 0.0032, "hc/post": 0.0032,
                                            "mla/attend": 0.5}, "kernels": {}, "attend_s": 0.5}}
    trace = {"busy_s": 5.0, "module_s": {"jit_prefill_impl": 3.0, "jit_chunk_impl": 2.0},
             "module_whole_s": {"jit_prefill_impl": 3.0, "jit_chunk_impl": 2.0},
             "module_runs": {"jit_prefill_impl": 10, "jit_chunk_impl": 4}}
    records = [{"ok": True, "sent": 1.0 + i, "prompt_len": n} for i, n in enumerate((4000, 8000))]
    return {"scopes": scopes, "trace": trace, "records": records, "t0": 0.0, "seconds": 51.0,
            "decode_chunk": 32, "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_the_hc_readers_on_a_hand_made_run():
    run = hand_made_run()
    # all `hc/*` time of both programs over busy time
    assert reader("layer_metrics", "hc_time_share")(run) == pytest.approx(100 * 0.3128 / 5.0)
    # 10 prompts of 6000 tokens x 12 sublayers x 93,184 B at 819 GB/s over 0.3 s
    least_s = 10 * 6000 * 12 * 93_184 / 819e9
    assert reader("layer_metrics", "hc_prefill_hbm_roofline")(run) \
        == pytest.approx(100 * least_s / 0.3)
    assert 0 < 100 * least_s / 0.3 < 100
    # 0.0128 s over 4 x 32 steps
    assert reader("layer_metrics", "hc_decode_us_per_step")(run) == pytest.approx(100.0)
    # a program without the scopes (the parent commit): nothing, and no error
    bare = dict(run, scopes={m: dict(t, scopes={"moe/experts": 1.0})
                             for m, t in run["scopes"].items()})
    for name in ("hc_time_share", "hc_prefill_hbm_roofline", "hc_decode_us_per_step"):
        assert reader("layer_metrics", name)(bare) is None
        assert reader("layer_metrics", name)(dict(run, scopes=None)) is None


def test_the_new_entries_keep_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell == dict(cell, name="xing-longdoc-offline", config="xing4.0-29b-a4b",
                        traffic="longdoc-offline", chips=1)
    assert bench["configs"][-1]["name"] == "xing4.0-29b-a4b"
    reported = [m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", [])]
    assert reported[-3:] == ["hc_time_share", "hc_prefill_hbm_roofline", "hc_decode_us_per_step"]
    assert {"moe_time_share", "moe_decode_hbm_roofline", "moe_prefill_flops_roofline",
            "mla_attn_time_share", "mla_decode_hbm_roofline", "expert_load_max_over_mean",
            "decode_step_ms.moonlight", "prefill_share.moonlight", "kv_used_peak_share",
            "tokens_per_dispatch.offline", "prefills_per_chunk", "tick_host_ms.offline",
            "idle_named_share.offline"} <= set(reported)
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == cell["name"] and serve["bound"] == 0.08
    with open(os.path.join(BENCH, "traffic", "longdoc-offline.json")) as f:
        mix = json.load(f)
    assert mix["mode"] == "serve-closed-xing" and mix["clients"] == 24
    assert mix["requests"] == {"prompt_lens": [2048, 3072, 4096, 6144, 8192, 10240, 12288, 15360],
                               "max_new_tokens": [128, 256, 512], "temperature": 0.8}
    engine = mix["engine"]
    assert (engine["num_slots"], engine["max_len"], engine["block_size"]) == (16, 16384, 128)
    buckets = engine["prefill_buckets"]
    assert len(buckets) <= 5 and max(buckets) == 16384 and all(b % 2048 == 0 for b in buckets)
    assert (mix["ramp_s"], mix["settle_s"], mix["tail_s"], mix["trace_s"]) == (8, 4, 1.0, 6.0)
    assert set(engine) == {"num_slots", "prefill_buckets", "max_len", "block_size"}
