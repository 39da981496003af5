"""chip_smoke.py at a toy size on the CPU: its phase functions run (the
kernels interpreted), main() refuses anything but a TPU before doing any
work, and the compile cache lands where it should. The real sizes run on
the chip only: `python chip_smoke.py`."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig  # noqa: E402
from paddle_tpu.models.gpt_decode import collect_gpt_params  # noqa: E402
from paddle_tpu.utils.compile_cache import ensure_compile_cache  # noqa: E402


def _toy(layers=2, **kw):
    return GPTConfig(vocab_size=97, hidden=32, layers=layers, heads=4,
                     max_pos=128, dropout=0.0, **kw)


def test_main_exits_nonzero_on_cpu_before_any_work(monkeypatch, capsys):
    def no_work(*args, **kwargs):
        raise AssertionError("a phase ran without a TPU")

    for name in ("phase_kernels", "phase_share_kernels",
                 "phase_block_diffusion", "phase_state_group",
                 "phase_state_space", "phase_gated_delta", "phase_train",
                 "phase_serve"):
        monkeypatch.setattr(chip_smoke, name, no_work)
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""    # no phase line, no result


def test_last_stdout_line_is_the_verdict_and_the_device(monkeypatch, capsys):
    """The driver's check reads the last line of stdout: one JSON object
    with the keys ok and device {platform, kind, count} and no others.
    Everything else the run has to say goes on the lines before it."""
    import json

    from paddle_tpu.models import gpt_decode

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(chip_smoke, "phase_kernels", lambda: {"cases": 0})
    monkeypatch.setattr(chip_smoke, "phase_share_kernels", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_block_diffusion", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_state_group", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_state_space", lambda: {})
    monkeypatch.setattr(chip_smoke, "phase_gated_delta", lambda: {})
    monkeypatch.setattr(
        chip_smoke, "phase_train",
        lambda *a, **kw: {"losses": [2.0, 1.0], "scope": None})
    monkeypatch.setattr(gpt_decode, "collect_gpt_params",
                        lambda *a, **kw: {})
    for passing in (True, False):
        def serve(*a, **kw):
            chip_smoke._require(passing, "serve: stubbed to fail")
            return {"live_arrays": 0}

        monkeypatch.setattr(chip_smoke, "phase_serve", serve)
        assert chip_smoke.main() == (0 if passing else 1)
        lines = capsys.readouterr().out.splitlines()
        last = json.loads(lines[-1])
        assert set(last) == {"ok", "device"} and last["ok"] is passing
        assert set(last["device"]) == {"platform", "kind", "count"}
        assert isinstance(last["device"]["count"], int)
        assert lines[-2].startswith("summary=")
        assert json.loads(lines[-2][len("summary="):])["claim"] is None


def test_kernels_phase_both_families_interpreted():
    # 128 takes the single-pass family, 640 (> 512) the tiled one
    facts = chip_smoke.phase_kernels(seqs=(128, 640), batch=1, heads=1)
    assert facts["cases"] == 18     # 2 families x (4 causal + 5 bias)
    assert facts["worst_rel_err"] <= chip_smoke.KERNEL_REL_TOL


def test_share_kernels_phase_interpreted():
    """The phase at a small size: the grouped paged kernel over a ring,
    the flash band and the expert layer of a share, each against its
    twin (the kernels interpreted, the expert product `ragged_dot`), and
    the combine kernel against XLA's gather."""
    facts = chip_smoke.phase_share_kernels(
        heads=8, kv_heads=2, head_dim=128, window=32, block=8, hidden=256,
        width=256, experts=16, held=4, picks=4, shared=2, rows=256, tokens=64,
        combine_tokens=48, combine_hidden=512)
    assert (facts["paged_group"], facts["paged_ring"]) == (4, 5)
    assert max(facts[k] for k in facts if k.endswith("rel_err")
               or k.startswith("moe_rel_err")) <= chip_smoke.KERNEL_REL_TOL
    assert 0 < facts["moe_held_picks_64"] < 64 * 4
    live = sum(1 for n in range(48) if n % 7 != 3)
    assert facts["combine_rows_fetched_mellum"] == live * 4
    assert 0 < facts["combine_rows_fetched_command_a"] < live * 4


def test_block_diffusion_phase_interpreted():
    """The phase at a small size with the kernel paths forced: the flash
    forward with `block=` and the block-row paged kernel interpreted, two
    blocks of passes, every served token judged at the pass that fixed it."""
    facts = chip_smoke.phase_block_diffusion(
        hidden=128, heads=2, kv_heads=1, width=128, experts=4, vocab=256,
        prompt_len=18, max_new=6, bucket=128, page=8, force_kernels=True)
    assert facts["blocks_committed"] == 2
    assert len(facts["fixed_at"]) == 6 and set(facts["fixed_at"]) <= {0, 1, 2, 3}
    assert facts["max_logit_deficit"] <= chip_smoke.LOGIT_MARGIN
    assert 0 < facts["max_confidence_drift"] <= chip_smoke.LOGIT_MARGIN


def test_state_group_phase_interpreted():
    """The phase at a small size with the kernel paths forced: the flash
    forward and the latent paged kernel interpreted beside the chunked
    scan's kernel and the step's kernel over a state block, two prompts
    shorter than their buckets (the longer one's last chunk passed by), a
    chunk and a part of steps."""
    facts = chip_smoke.phase_state_group(
        hidden=128, heads=2, head_dim=128, rank=128, rope=128, width=128,
        experts=4, vocab=256, prompt_lens=(130, 70), max_news=(10, 3),
        bucket=256, page=128, force_kernels=True)
    assert facts["state"]["recurrence_path"] == "kernel"
    assert facts["state"]["prefill_recurrence_path"] == "kernel"
    assert facts["state"]["prefill_kernel_buckets"] == [128, 256]
    assert facts["state"]["peak_blocks_used"] == 2
    assert facts["kda_state_steps"] == 4 * (9 + 2)
    assert facts["kda_prefill_chunks"] == 4 * (3 + 2)
    assert facts["max_logit_deficit"] <= chip_smoke.LOGIT_MARGIN


def test_state_space_phase_interpreted():
    """The phase at a small size with the kernel paths forced: the flash
    forward and the grouped paged kernel at the published scale interpreted
    beside the step's kernel over a state block, two prompts that end
    mid-bucket (the longer one mid-chunk), a chunk and a part of steps."""
    facts = chip_smoke.phase_state_space(
        hidden=128, heads=2, kv_heads=1, mamba_heads=4, mamba_head_dim=64,
        mamba_state=128, width=128, experts=4, picks=2, vocab=256,
        prompt_lens=(200, 70), max_news=(10, 3), bucket=256, page=128,
        force_kernels=True)
    assert facts["state"]["recurrence_path"] == "kernel"
    assert facts["state"]["peak_blocks_used"] == 2
    assert facts["ssd_state_steps"] == 3 * (9 + 2)
    assert facts["ssd_prefill_rows"] == 3 * 270
    assert facts["max_logit_deficit"] <= chip_smoke.LOGIT_MARGIN / 16


def test_gated_delta_phase_interpreted():
    """The phase at a small size with the kernel paths forced: the flash
    forward and the grouped paged kernel at head size 256 interpreted beside
    the delta rule's two kernels over broadcast operands (a scalar decay, 2
    key heads under 4 value heads), two prompts that end mid-bucket (the
    longer one inside its fourth chunk), a chunk and a part of steps."""
    facts = chip_smoke.phase_gated_delta(
        hidden=128, heads=2, kv_heads=1, head_dim=256, key_heads=2,
        value_heads=4, width=128, experts=4, picks=2, vocab=256,
        prompt_lens=(200, 70), max_news=(10, 3), bucket=256, page=128,
        force_kernels=True)
    assert facts["state"]["recurrence_path"] == "kernel"
    assert facts["state"]["prefill_recurrence_path"] == "kernel"
    assert facts["state"]["prefill_kernel_buckets"] == [128, 256]
    assert facts["state"]["peak_blocks_used"] == 2
    assert facts["gdn_state_steps"] == 3 * (9 + 2)
    assert facts["gdn_prefill_rows"] == 3 * 270
    assert facts["gdn_prefill_chunks"] == 3 * (4 + 2)
    assert facts["max_logit_deficit"] <= chip_smoke.LOGIT_MARGIN


def test_train_then_serve_phases():
    """The train phase's scope feeds the serve phase, as main() chains
    them."""
    cfg = _toy()
    trained = chip_smoke.phase_train(cfg, batch=4, seq=16, reference=True)
    assert trained["mosaic_calls"] == 0     # no Mosaic on the CPU
    # the loss gradient rebuilt from the saved log-sum-exp, lowered once,
    # and the first loss beside the cells' float32 reference
    assert trained["loss_lowerings"] == [1, 0]
    assert trained["loss_rel_gap"] <= chip_smoke.LOSS_REL_TOL
    assert trained["losses"][-1] < trained["losses"][0]
    # no plan: nothing to place, and every run says so
    assert trained["scope_vars_placed"] == [0, 0, 0]
    assert trained["runs_in_place"] == 3
    params = collect_gpt_params(trained["scope"], cfg, dtype=jnp.bfloat16)
    facts = chip_smoke.phase_serve(
        params, cfg, prompt_lens=(6, 7, 3, 12, 14, 16), shared_prefix=4,
        max_new_tokens=8, num_slots=4, prefill_buckets=(8, 16), max_len=32)
    assert facts["compiled_executables"] <= 2 + 3
    assert facts["max_logit_deficit"] <= chip_smoke.LOGIT_MARGIN


def test_data_parallel_step_with_the_kernel_matches_one_device():
    """A Mosaic kernel cannot be partitioned by GSPMD; the fused_attention
    op runs it per shard of the batch under a mesh. Forced here
    (impl="flash", interpreted) because the CPU's auto dispatch never
    takes the kernel, which is how this combination went unmet until the
    chip refused it."""
    cfg = _toy(layers=1, attn_impl="flash")
    one = chip_smoke.phase_train(cfg, batch=8, seq=128)
    # raises SmokeFailure if the losses part ways or parameters do not
    # sit on every device
    four = chip_smoke.phase_train(cfg, batch=8, seq=128, data_parallel=True,
                                  one_chip_losses=one["losses"])
    # the first step after the startup program places the scope, no later one
    assert four["scope_vars_placed"][0] > 10
    assert four["scope_vars_placed"][1:] == [0, 0]
    assert four["runs_in_place"] == 2


def test_train_phase_fails_when_a_later_step_places_scope_variables(monkeypatch):
    """The phase's line carries the executor's counters and the phase fails
    where a step after the first handed scope variables to the plan: here
    the scope is made to forget, at every write-back, who placed what."""
    from paddle_tpu.framework.executor import Scope

    def forgetful(self, values, plan):
        for name, value in values.items():
            self.set_var(name, value)

    monkeypatch.setattr(Scope, "_set_placed", forgetful)
    cfg = _toy(layers=1)
    with pytest.raises(chip_smoke.SmokeFailure, match="placed scope variables"):
        chip_smoke.phase_train(cfg, batch=8, seq=16, data_parallel=True)
    # without a plan there is nothing to forget
    facts = chip_smoke.phase_train(cfg, batch=8, seq=16)
    assert facts["scope_vars_placed"] == [0, 0, 0]


def test_compile_cache_placement(monkeypatch, tmp_path):
    configured = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ensure_compile_cache() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == configured

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = ensure_compile_cache()
    assert first == ensure_compile_cache() \
        == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
