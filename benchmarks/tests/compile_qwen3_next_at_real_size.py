"""A rehearsal, run by hand before a chip call (it is not a test: it loads the TPU's
compiler and takes some minutes; it makes NO weights and runs nothing):

    JAX_PLATFORMS=cpu python3 benchmarks/tests/compile_qwen3_next_at_real_size.py [slots] [kernel|decode|<bucket> ...]

`qwen3-next-longmix-offline`'s served programs (the step's kernel alone at this model's
broadcast operands, the decode step, a prefill of each of the five buckets) lowered from the
program's own config over ABSTRACT weights and pools (`jax.eval_shape`, `ShapeDtypeStruct`)
with `jax.default_backend` answering "tpu", so the verdicts are the chip's, and compiled for a
described `v5e:2x2` chip. The row pool is the traffic file's `kv_blocks`, the state pools a
block a slot. It prints `memory_analysis()` of one program at a time, or what the compiler
refused: the flash forward at d = dv = 256, the grouped paged kernel over (2, 128, 512) pages
and `kda_chunk` over 16,384 rows are first met here, before any chip time."""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
             - m.alias_size_in_bytes)
    print(f"{tag}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB, aliased {m.alias_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, together {total / 1e9:.3f} GB of 16 "
          f"GB; {compiled.as_text().count('tpu_custom_call')} Mosaic calls", flush=True)


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from lib import qwen3_next as builder, model
    from paddle_tpu.ops import kda_step
    from paddle_tpu.serving.model import cache_groups, serving_model

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    sizes = model.load_json("traffic", "longmix-offline.json")["engine"]
    cfg = builder.qwen3_next_config(model.load_json("configs", "qwen3-next-80b-a3b.json"))
    slots = int(argv[0]) if argv else sizes["num_slots"]
    which = argv[1:] or ["kernel", "decode"] + [str(b) for b in sizes["prefill_buckets"]]

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: shape(*a.shape, dtype=a.dtype), tree)

    served = serving_model(cfg)
    layout = cache_groups(served, cfg, sizes["max_len"], sizes["block_size"])
    width = layout[-1].start + layout[-1].pages
    # the primary group's pool is the traffic file's; a state group's a block a slot
    blocks = [sizes.get("kv_blocks") or slots * layout[0].pages + 1] + [slots + 1] * 2
    arena = tuple(shape(*g.spec.arena_shape(n, sizes["block_size"]),
                        dtype=jnp.dtype(g.spec.dtype or "bfloat16"))
                  for g, n in zip(layout, blocks))
    params = described(jax.eval_shape(
        lambda key: builder.program.init_params(cfg, key, jnp.bfloat16), jax.random.PRNGKey(0)))
    print("pools", [round(a.size * a.dtype.itemsize / 1e9, 3) for a in arena], "GB; a page row of",
          width, "columns", flush=True)
    jax.default_backend = lambda: "tpu"         # the chip's verdicts, for this process
    n, dk, dv = cfg.state_shape
    f32 = jnp.float32
    programs = {
        "kernel": lambda: jax.jit(
            lambda a, ids, q, k, v, g, beta: kda_step.kda_step_blocks(
                a, 3, ids, None, q, k, v, g, beta), donate_argnums=(0,)).lower(
                    arena[1], shape(slots), shape(slots, n, dk, dtype=f32),
                    shape(slots, n, dk, dtype=f32), shape(slots, n, dv, dtype=f32),
                    shape(slots, n, dk, dtype=f32), shape(slots, n, dtype=f32)),
        "decode": lambda: jax.jit(
            lambda p, t, a, pt, ts, d: served.decode_step(p, cfg, t, a, pt, ts, d),
            donate_argnums=(2,)).lower(params, shape(slots), arena, shape(slots, width),
                                       shape(slots), shape(slots, dtype=jnp.bool_)),
    }
    for name in which:
        start = time.time()
        lower = programs.get(name) or (lambda bucket=int(name): jax.jit(
            lambda p, t, a, pages, n: served.prefill(p, cfg, t, jnp.int32(0), n, a, pages),
            donate_argnums=(2,)).lower(params, shape(1, bucket), arena, shape(width), shape()))
        try:
            report(f"{name} ({slots} slots)", lower().compile())
        except Exception as e:
            print(f"{name}: REFUSED {type(e).__name__}: {str(e)[:1500]}", flush=True)
        print(f"  ({time.time() - start:.0f} s)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
