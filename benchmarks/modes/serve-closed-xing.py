"""`serve-closed-model`'s loop, server, window and tie-aware verdict for
Xing4.0-29B-A4B. This file loads a copy of that mode of its OWN, as run.py loads
a mode, and sets in the copy (in memory; the file on disk is Moonlight's and is
not touched) the architecture's builder and reference, this model's verdict
constants, and a by-scope reduction of the trace that also knows the residual
mixer's scopes (`hc/coeff`, `hc/pre`, `hc/post`). Everything else is the copy's.

The verdict's constants, measured on the chip (my chip runs, PR 31; PERF.md section
6 has every reading). Xing's logits are y W_head with y of unit RMS over 3584 values
and W_head normal(0, 0.02): standard deviation 1.197 measured, against Moonlight's
0.905; the picks are 4 of 64 in five expert layers, and the gap between the 4th and
the 5th biased score, least over the layers, is a median 0.0025 (Moonlight's 0.011):
6.5% of the positions are 0.01 clear of a tie.
  1. JUDGED positions (gap at least PICK_GAP in every expert layer): every one's
     deficit within LOGIT_MARGIN. Readings: served 0.025-0.035 at the worst of 80-97
     judged a run over five runs (no flip at a gap of 0.005 or more either; at 0.002
     deficits reach 2.5); tokens picked by the reference with its weights rounded to
     float8_e4m3, the precision below the stated bfloat16: median 0.13-0.20, worst
     2.2-2.3 over 36 and 50 judged.
  2. Of ALL checked positions at least MIN_SHARE_WITHIN within LOGIT_MARGIN. Readings:
     served 0.913-0.925 of 1,280-1,408; the float8 reference 0.338 and 0.369 of 512
     and 640. Float8 weights fail both limits.
  3. The mixer's own: H_res's rows sum to one within `hc_eps` (1e-6) by the last
     division of the last Sinkhorn round, whatever the streams, IF the mixer is
     computed in float32; the in-graph counter reads the worst row of every pass
     (`hc_rowsum_dev_ppm` / `hc_passes`). Readings: 1.0 ppm in every float32 run; with
     the mixer computed in bfloat16 4,244 ppm (bfloat16's unit roundoff is 3,900),
     while limits 1 and 2 read 0.010 and 0.925, no worse than float32's: tokens
     cannot see a bfloat16 mixer over bfloat16 streams, the counter can. The limit
     is HC_ROWSUM_PPM_A_PASS, between the two readings with room on both sides."""

import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_xing")
# `lib.` in the name: the copy imports its neighbour trace_reduce relatively
scopes = _own_copy("lib", "scope_reduce", "lib.scope_reduce_with_hc")
scopes.SCOPE = re.compile(r"(?:^|[/\"(])((?:mla|moe|ffn|hc)/[a-z_]+|head)(?=[/\")]|$)")

base.ARCHITECTURES["xing4_0"] = ("xing", "xing_ref")
base.scope_reduce = scopes
base.LOGIT_MARGIN = 0.1
base.PICK_GAP = 0.01
base.MIN_SHARE_WITHIN = 0.65
HC_ROWSUM_PPM_A_PASS = 50.0


def run(ctx):
    run = base.run(ctx)
    counted = run["model1"]
    passes = counted.get("hc_passes")
    ppm = counted["hc_rowsum_dev_ppm"] / passes if passes else None
    run["facts"].update(moe_kernel_passes=counted.get("moe_kernel_passes"), hc_passes=passes,
                        hc_rowsum_dev_ppm_a_pass=ppm, hc_rowsum_ppm_limit=HC_ROWSUM_PPM_A_PASS)
    if ppm is None or ppm > HC_ROWSUM_PPM_A_PASS:
        run["why_incorrect"].append(
            f"a row of the mixer's H_res is {ppm} parts per million from summing to one, a "
            f"pass's worst over {passes} passes (at most {HC_ROWSUM_PPM_A_PASS}: float32 reads 1)")
        run["correct"] = False
    return run
