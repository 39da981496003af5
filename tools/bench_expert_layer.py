"""The expert layer alone: `models/_experts.py::moe` stage by stage at
the four expert cells' widths.

One layer's weights (router, the experts HELD (64 of 64; command-a's 16
of 128, routed over all), the shared experts where the
config has them; bfloat16, made on the chip and passed as ARGUMENTS: as
constants 1 GB of them costs minutes of compile) and `moe` jitted once a
row count, traced once a case:
  * every prompt length a cell's traffic file sends, in the bucket the
    engine would take for it (the rows past the length are not live);
  * the cell's decode step (every slot live: 32 / 16 / 48 tokens).
The device's time is read from a profiler trace by the program's own
scopes (`moe/router`, `/dispatch`, `/experts`, `/shared`, `/combine`;
`benchmarks/lib/stage_times.py`, which the cells' metrics read). Beside
the microseconds of each stage a line gives
  * `flops_routed`, `flops_computed`: the kernel's share of 197 TFLOP/s
    on the rows that were someone's and on the rows it computed
    (`rows_computed` is the layer's own counter where it has one; for a
    checkout without it, one visit of `tile` rows for every (expert, row
    tile) pair of groups laid end to end: the layout before PR 37);
  * `dispatch_hbm`, `combine_hbm`: one copy of the bucket's routed rows
    (tokens x picks x h x 2 B; the held share of them where the layer
    holds a share of the experts) over 819 GB/s, over the stage's time;
  * `combine_ns_row`: `combine_us` over the bucket's tokens x picks, and
    `combine_path`: which carrier the case's combine took (`moe`'s
    counter `combine_kernel_passes`: "row_dma_kernel", else "gather"; a
    checkout without the counter has the gather alone).
A chip is required: on any other backend it exits 1 with nothing
measured.

    chiprun -- python tools/bench_expert_layer.py
    chiprun -- python tools/bench_expert_layer.py --models xing --repo .scratch/parent

`--repo DIR` times the layer of another checkout (the parent's, unpacked
by `git archive`; one older than PR 43 has it at `models/moonlight.py::_moe`,
the one fall-back below); `--tile N` replaces `row_tile_for`'s choice in the prompt
cases for a sweep (nothing but this tool does). Prints one JSON line a
case and a table a model; the same goes to
chiprun_out/bench_expert_layer[.<tag>].json.
"""

import argparse
import json
import os
import sys
import tempfile
import types

PEAK_FLOPS = 197e12              # TPU v5e, bfloat16 (Google Cloud documentation)
HBM_BYTES_PER_S = 819e9
HERE = os.path.dirname(os.path.abspath(__file__))
STAGES = ("router", "dispatch", "experts", "shared", "combine")
# the published widths (benchmarks/configs/*.json) and the cells' traffic;
# `experts` the router's width, `held` how many of them the layer holds
# (the first ones; all where the key is absent)
MODELS = {
    "moonlight": dict(h=2048, F=1408, k=6, shared=2, scoring="sigmoid",
                      factor=2.446, traffic="longctx-offline"),
    "xing": dict(h=3584, F=1024, k=4, shared=1, scoring="sigmoid",
                 factor=2.5, traffic="longdoc-offline"),
    "mellum": dict(h=2304, F=896, k=8, shared=0, scoring="softmax",
                   factor=1.0, traffic="mixedlen-offline"),
    "commanda": dict(h=4096, F=4096, k=8, shared=4, scoring="sigmoid",
                     factor=1.0, traffic="reason-offline", experts=128,
                     held=16, bias=False, combination="average"),
}
REPEATS = 6


def cases_of(traffic):
    """[(name, tokens, live tokens)] of a traffic file: its prompts in
    their buckets, then its decode step."""
    with open(os.path.join(HERE, "..", "benchmarks", "traffic",
                           f"{traffic}.json")) as f:
        spec = json.load(f)
    buckets = sorted(spec["engine"]["prefill_buckets"])
    out = []
    for length in spec["requests"]["prompt_lens"]:
        bucket = next(b for b in buckets if b >= length)
        out.append((f"p{length}", bucket, length))
    slots = spec["engine"]["num_slots"]
    return out + [(f"d{slots}", slots, slots)]


def layer(jax, jnp, model):
    """One expert layer's parameters, on the device."""
    h, F, E = model["h"], model["F"], model.get("experts", 64)
    held = model.get("held", E)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))

    def w(*shape):
        return (0.02 * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(jnp.bfloat16)

    lp = {"router": w(h, E), "w_gate": w(held, h, F), "w_up": w(held, h, F),
          "w_down": w(held, F, h)}
    if model.get("bias", True):
        lp["router_bias"] = jnp.zeros((E,), jnp.float32)
    if model["shared"]:
        Fs = model["shared"] * F
        lp.update(shared_gate=w(h, Fs), shared_up=w(h, Fs),
                  shared_down=w(Fs, h))
    return lp


def old_layout_rows(sizes, tile):
    """Rows computed when the groups lie end to end and a visit is one
    (expert, row tile) pair that shares a row."""
    rows = start = 0
    for n in sizes:
        if n:
            rows += ((start + n - 1) // tile - start // tile + 1) * tile
        start += n
    return rows


def stage_table(trace_dir):
    """{program: {"runs", "stages": {stage: seconds}, "kinds"}} of the
    first chip of a trace."""
    sys.path.insert(0, os.path.join(HERE, "..", "benchmarks"))
    from lib import scope_reduce, stage_times
    from lib import trace_reduce as tr

    path = tr.find_xplane(trace_dir)
    planes = tr.load(path)
    device = sorted(p for p in planes if p.startswith(tr.DEVICE_PLANE)
                    and planes[p].get(tr.OPS_LINE))[0]
    lines = planes[device]
    return stage_times.by_stage(
        lines[tr.OPS_LINE], lines.get(tr.MODULES_LINE, []), 0.0, float("inf"),
        scope_reduce.metadata_ops(path))["modules"]


def config_of(model):
    """What the layer reads of a config (`models/_experts.py`'s list), of
    one of MODELS."""
    experts = model.get("experts", 64)
    held = model.get("held", experts)
    cfg = types.SimpleNamespace(
        experts_per_tok=model["k"], n_routed_experts=experts,
        n_shared_experts=model["shared"], router_scoring=model["scoring"],
        routed_scaling_factor=model["factor"])
    if held < experts:
        # a share of an expert-parallel deployment (a checkout whose layer
        # is not told which experts it holds cannot run this case)
        cfg.experts_held = (0, held)
        cfg.shared_expert_combination = model["combination"]
    return cfg


def checkout_layer():
    """The expert layer of the checkout that is first on `sys.path`."""
    try:
        from paddle_tpu.models._experts import moe
    except ImportError:            # --repo of a checkout before PR 43
        from paddle_tpu.models.moonlight import _moe as moe
    return moe


def layer_program(name, cfg, moe_layer, tokens):
    """`moe_layer` (the checkout's `moe`) of one config, jitted under the
    name its trace is read by."""
    import jax

    def moe(lp, x, live):
        return moe_layer(cfg, lp, x, live)
    moe.__name__ = f"moe_{name}_{tokens}"
    return jax.jit(moe)


def bench(name, model, moe_layer, gs):
    import jax
    import jax.numpy as jnp
    import numpy as np

    experts = model.get("experts", 64)
    held = model.get("held", experts)
    cfg = config_of(model)
    lp = layer(jax, jnp, model)
    h, F, k = model["h"], model["F"], model["k"]
    programs, rows = {}, []
    for case, tokens, length in cases_of(model["traffic"]):
        if tokens not in programs:
            programs[tokens] = layer_program(name, cfg, moe_layer, tokens)
        program = programs[tokens]
        x = jax.random.normal(jax.random.PRNGKey(tokens + length),
                              (tokens, h), jnp.bfloat16)
        live = jnp.arange(tokens) < length
        y, counters = program(lp, x, live)
        if not bool(jnp.isfinite(y.astype(jnp.float32)).all()):
            raise SystemExit(f"{name} {case}: the layer's output is not finite")
        sizes = np.asarray(counters["expert_tokens"])
        if held == experts:
            assert sizes.sum() == length * k, (sizes.sum(), length, k)
        rows_tile = gs.row_tile_for(tokens * k, experts)
        computed = counters.get("rows_computed")
        computed = old_layout_rows(sizes.tolist(), rows_tile) \
            if computed is None else int(computed)
        fact = dict(tokens=tokens, live=length, tile=rows_tile,
                    rows_routed=int(sizes.sum()), rows_computed=computed,
                    combine_path="row_dma_kernel" if int(counters.get(
                        "combine_kernel_passes", 0)) else "gather")
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            for _ in range(REPEATS):
                y, _ = program(lp, x, live)
            y.block_until_ready()
            jax.profiler.stop_trace()
            entry = stage_table(trace_dir)[f"jit_moe_{name}_{tokens}"]
        runs = round(entry["runs"])
        if runs != REPEATS:
            raise SystemExit(f"{name} {case}: {runs} runs in the trace, "
                             f"{REPEATS} made")
        us = {s: 1e6 * entry["stages"].get(f"moe/{s}", 0.0) / runs
              for s in STAGES}
        flops = 6 * h * F
        # one copy of the rows that are HELD (tokens x picks where every
        # expert is)
        copy_us = 1e6 * fact["tokens"] * k * held // experts * h * 2 \
            / HBM_BYTES_PER_S
        row = dict(model=name, case=case, **fact,
                   **{f"{s}_us": round(v, 1) for s, v in us.items()},
                   layer_us=round(1e6 * entry["busy_s"] / runs, 1),
                   flops_routed=round(
                       100 * fact["rows_routed"] * flops / PEAK_FLOPS
                       / (us["experts"] * 1e-6), 1),
                   flops_computed=round(
                       100 * fact["rows_computed"] * flops / PEAK_FLOPS
                       / (us["experts"] * 1e-6), 1),
                   dispatch_hbm=round(100 * copy_us / us["dispatch"], 1),
                   combine_hbm=round(100 * copy_us / us["combine"], 1),
                   combine_ns_row=round(1e3 * us["combine"] / (tokens * k), 1),
                   kinds={s: {kind: round(1e6 * v / runs, 1)
                              for kind, v in sorted(
                                  entry["kinds"].get(f"moe/{s}", {}).items(),
                                  key=lambda kv: -kv[1])[:6]}
                          for s in ("dispatch", "combine")})
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--models", default="moonlight,xing,mellum,commanda")
    ap.add_argument("--repo", default=os.path.join(HERE, ".."))
    ap.add_argument("--tile", type=int, default=None)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    if jax.default_backend() != "tpu":
        print(f"a chip is required; the backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    moe = checkout_layer()
    from paddle_tpu.ops import grouped_swiglu as gs

    if args.tile:
        own = gs.row_tile_for
        # the prompt cases alone: a decode step keeps its packed tile
        gs.row_tile_for = lambda rows, groups: (
            args.tile if own(rows, groups) >= 128 else own(rows, groups))
    results = []
    for name in args.models.split(","):
        jax.clear_caches()
        rows = bench(name, MODELS[name], moe, gs)
        results += rows
        cols = ("case", "tokens", "live", "tile", "rows_computed",
                *(f"{s}_us" for s in STAGES), "layer_us", "flops_routed",
                "flops_computed", "dispatch_hbm", "combine_hbm",
                "combine_ns_row", "combine_path")
        print(f"# {name}" + (f" ({args.tag})" if args.tag else ""))
        print(" | ".join(cols))
        for row in rows:
            print(" | ".join(str(row[c]) for c in cols))
    out = os.path.join(HERE, "..", "chiprun_out")
    os.makedirs(out, exist_ok=True)
    tag = f".{args.tag}" if args.tag else ""
    with open(os.path.join(out, f"bench_expert_layer{tag}.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
