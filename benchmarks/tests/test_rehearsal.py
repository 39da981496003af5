"""Each mode end to end on the CPU at a tiny size, through the mode's own
functions (the command itself refuses a CPU). Counts and control flow only: a
CPU run says nothing about a time or a rate."""

import importlib.util
import os
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"vocab_size": 211, "n_positions": 64, "n_embd": 32, "n_layer": 2, "n_head": 4,
        "n_inner": 128, "layer_norm_epsilon": 1e-5, "initializer_range": 0.02}


class Ctx:
    def __init__(self, tmp_path, traffic, chips=1, seconds=2.0, trace=False):
        self.seed, self.seconds, self.trace = 2147483659, seconds, trace
        self.cell = {"name": "tiny", "chips": chips}
        self.config, self.traffic = TINY, traffic
        self.tmp, self.setup_s, self.ramp_s = tmp_path, None, None

    def out_path(self, name):
        return str(self.tmp / name)

    def mark(self, name):
        pass

    def mark_setup_done(self, ramp_s):
        self.setup_s, self.ramp_s = time.monotonic(), ramp_s

    def memory_peak(self):
        return 0


def mode(name):
    spec = importlib.util.spec_from_file_location(name.replace("-", "_"),
                                                  os.path.join(BENCH, "modes", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(folder, name):
    spec = importlib.util.spec_from_file_location("r_" + name.replace(".", "_"),
                                                  os.path.join(BENCH, folder, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


ENGINE = {"num_slots": 4, "prefill_buckets": [16, 32], "max_len": 64}


def test_open_loop_measures_the_requests_due_in_the_window(tmp_path):
    traffic = {"mode": "serve-open", "ramp_s": 1.0, "trace_s": 1.0, "engine": ENGINE,
               "arrivals": {"rate_per_s": 6.0, "temperature": 0.8,
                            "prompt_len": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 30},
                            "max_new_tokens": {"median": 6, "sigma": 0.3, "lo": 3, "hi": 10}}}
    run = mode("serve-open").run(Ctx(tmp_path, traffic))
    assert run["attempted"] == 12 and run["failed"] == 0, run["facts"]
    assert all(0 <= r["due"] - run["t0"] < 2.0 for r in run["measured"])
    assert any(not r["measured"] for r in run["records"])       # the ramp was offered
    assert run["correct"], run["facts"]
    assert run["facts"]["max_logit_deficit"] <= 0.1
    run.update(config=TINY, peaks={"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0})
    assert run["facts"]["shed"] == 0 and run["facts"]["measured_requests"] == 12
    assert reader("end_to_end", "ttft_p95_ms")(run) > 0
    assert reader("end_to_end", "tpot_mean_ms")(run) > 0
    assert reader("layer_metrics", "queue_wait_p90_ms")(run) >= 0
    assert reader("layer_metrics", "tokens_per_dispatch.chat")(run) > 0
    assert reader("layer_metrics", "decode_step_ms")(run) is None   # no trace, no number


def test_closed_loop_counts_completions_between_events(tmp_path):
    traffic = {"mode": "serve-closed", "clients": 6, "ramp_s": 0.5, "settle_s": 0.2,
               "tail_s": 0.3, "trace_s": 1.0, "engine": ENGINE,
               "requests": {"prompt_lens": [8, 12, 20, 28], "max_new_tokens": [3, 5, 8],
                            "temperature": 0.8}}
    run = mode("serve-closed").run(Ctx(tmp_path, traffic))
    assert run["attempted"] > 3 and run["failed"] == 0, run["facts"]
    assert run["correct"], run["facts"]
    assert reader("end_to_end", "serve_tok_s")(run) > 0
    assert reader("layer_metrics", "kv_used_peak_share")(run) > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_train_mode_matches_the_reference_loss(tmp_path, chips):
    traffic = {"mode": "train", "seq_len": 32, "per_chip_batch": 2, "data_parallel": chips > 1,
               "learning_rate": 1e-3, "distinct_batches": 2, "warm_up_steps": 2, "trace_s": 1.0}
    run = mode("train").run(Ctx(tmp_path, traffic, chips=chips, seconds=1.5))
    assert run["facts"]["loss_rel_gap"] < 1e-3, run["facts"]
    assert run["facts"]["devices_holding_weights"] == chips
    assert run["correct"], run["facts"]
    assert run["recompiles"] == 0
    assert reader("end_to_end", "train_tok_s")(run) > 0
    assert reader("layer_metrics", "step_ms")(run) > 0
