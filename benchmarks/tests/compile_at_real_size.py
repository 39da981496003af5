"""A rehearsal, run by hand before a chip call (it is not a test: it loads the
TPU's compiler, takes minutes and tens of GB of host memory):

    JAX_PLATFORMS=cpu python3 benchmarks/tests/compile_at_real_size.py train 8 16 32
    JAX_PLATFORMS=cpu python3 benchmarks/tests/compile_at_real_size.py serve gpt2-xl docs-offline [num_slots=16] [decode]

It builds the program's own objects on the CPU at the cell's real sizes,
catches the jitted step on its way to the device, and compiles it for a
described `v5e:2x2` chip instead. What it prints is memory_analysis() of one
program at a time, not what else the process keeps on the device; nothing runs
on a TPU, so it gives no time and no result."""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def described(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
             - m.alias_size_in_bytes)
    print(f"{tag}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB, aliased {m.alias_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, together {total / 1e9:.3f} GB "
          f"of 16 GB", flush=True)
    return compiled


class Caught(Exception):
    pass


def train(batches):
    import jax
    import numpy as np
    import paddle_tpu as pt
    from lib import model
    from paddle_tpu.models.gpt import gpt_lm_program

    cfg = model.load_json("configs", "gpt2-small.json")
    chip = one_chip()
    # the program asks jax for its backend when it picks the attention path:
    # answer as the chip would, so that the Mosaic kernels are in the step
    jax.default_backend = lambda: "tpu"
    for batch in batches:
        main, startup, fetches = gpt_lm_program(model.gpt_config(cfg), 1024,
                                                learning_rate=1e-4, amp=True)
        exe, scope = pt.Executor(), pt.Scope()
        inner = exe._compile

        def catching(*args, **kwargs):
            step = inner(*args, **kwargs)

            def lower_only(*call_args):
                text = report(f"train step b{batch} s1024",
                              step.lower(*described(call_args, chip)).compile()).as_text()
                print(f"  Mosaic calls in the step: {text.count('tpu_custom_call')}")
                raise Caught()
            return lower_only

        with pt.scope_guard(scope):
            exe.run(startup)
            exe._compile = catching
            try:
                exe.run(main, feed={"tokens": np.zeros((batch, 1024), np.int64)},
                        fetch_list=[fetches["loss"]])
            except Caught:
                pass
            except Exception as e:      # what the chip's compiler would refuse
                print(f"train step b{batch} s1024: REFUSED {type(e).__name__}: {str(e)[:300]}")


def serve(config, traffic, overrides=()):
    import jax.numpy as jnp
    import numpy as np
    from lib import model
    from paddle_tpu.serving import ServingConfig, ServingEngine

    cfg = model.load_json("configs", config + ".json")
    sizes = dict(model.load_json("traffic", traffic + ".json")["engine"])
    decode = "decode" in overrides
    for key, value in (o.split("=") for o in overrides if "=" in o):
        sizes[key] = [int(v) for v in value.split(",")] if "," in value else int(value)
    sizes["prefill_buckets"] = tuple(sizes["prefill_buckets"])
    chip = one_chip()
    params = model.serving_params(cfg, 0, jnp.bfloat16)
    engine = ServingEngine(params, model.gpt_config(cfg), ServingConfig(**sizes))
    sched = engine.scheduler
    inner = sched._jit_call
    seen = set()

    def catching(family, fn, *args):
        if family not in seen:
            seen.add(family)
            try:
                report(f"{config} {family} ({sizes['num_slots']} slots)",
                       fn.lower(*described(args, chip)).compile())
            except Exception as e:
                print(f"{config} {family}: REFUSED {type(e).__name__}: {str(e)[:300]}")
        return inner(family, fn, *args)

    sched._jit_call = catching
    print(f"weights {sum(a.nbytes for a in __import__('jax').tree_util.tree_leaves(params)) / 1e9:.3f} GB, "
          f"arena {engine.kv.pool_bytes / 1e9:.3f} GB", flush=True)
    # one token each first: a request that ends at its admission runs the
    # prefill and never the decode chunk, which the CPU takes minutes over
    for bucket in sizes["prefill_buckets"]:
        n = min(bucket, sizes["max_len"] - 20)
        engine.submit((np.arange(n, dtype=np.int32) * 7 + bucket) % cfg["vocab_size"], max_new_tokens=1)
        engine.run_until_drained()
    if decode:
        engine.submit(np.arange(8, dtype=np.int32), max_new_tokens=3)
        engine.run_until_drained()
    engine.close()


if __name__ == "__main__":
    if sys.argv[1] == "train":
        train([int(b) for b in sys.argv[2:]] or [8, 16])
    else:
        serve(sys.argv[2], sys.argv[3], sys.argv[4:])
