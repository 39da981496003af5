"""GPT-2-small KV-cache generation throughput (VERDICT r4 item 2).

Measures tokens/s for batch 1 (interactive latency) and batch 32
(serving throughput): randomly-initialised GPT-2-small (generation cost
does not depend on the weight values), bf16 weights/cache, prompt 64,
192 new tokens, greedy — the whole prefill+decode loop is ONE jitted
dispatch (models/gpt_decode.py), so host-side timing is honest after
the compile warmup.

Usage: python tools/bench_gpt_decode.py  (GEN, PROMPT, BATCHES env)
Prints one JSON line per batch size.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
    from paddle_tpu.models import gpt_decode as gd

    gen = int(os.environ.get("GEN", 192))
    prompt_len = int(os.environ.get("PROMPT", 64))
    batches = [int(x) for x in
               os.environ.get("BATCHES", "1,32").split(",")]

    cfg = GPTConfig(max_pos=1024, dropout=0.0)
    main_p, startup, _ = gpt_lm_program(cfg, 64, is_test=True)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        params = gd.collect_gpt_params(scope, cfg, dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    for b in batches:
        prompt = rng.randint(0, cfg.vocab_size,
                             (b, prompt_len)).astype(np.int32)
        out = gd.gpt_generate(params, cfg, prompt, gen)  # compile+warm
        assert out.shape == (b, prompt_len + gen)
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = gd.gpt_generate(params, cfg, prompt, gen)
        dt = (time.perf_counter() - t0) / reps
        toks = b * gen
        print(json.dumps({
            "metric": f"gpt2_small_decode_tokens_per_s_b{b}",
            "value": round(toks / dt, 1),
            "unit": "tokens/s (batch=%d, prompt=%d, gen=%d, %.1f ms/tok"
                    "/seq, %.0f ms total)"
                    % (b, prompt_len, gen, dt * 1e3 / gen, dt * 1e3),
            "vs_baseline": None,
        }))


if __name__ == "__main__":
    main()
