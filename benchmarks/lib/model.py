"""From a configuration file to the program's objects: the GPTConfig, weights
made on the device from the seed, and the weights a training scope holds in
the reference's layout."""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def gpt_config(cfg):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
                     layers=cfg["n_layer"], heads=cfg["n_head"],
                     ffn=cfg.get("n_inner") or 4 * cfg["n_embd"],
                     max_pos=cfg["n_positions"], dropout=0.0,
                     init_range=cfg.get("initializer_range", 0.02))


def fold_seed(seed):
    """--seed may pass 2**31; a PRNG key and a program seed take 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def serving_params(cfg, seed, dtype):
    """The served weights, made on the device in one jitted call, in the type
    they are served in: normal(0, initializer_range) matrices and embeddings,
    zero biases, unit layer norms, as GPT-2 initialises."""
    import jax
    import jax.numpy as jnp

    h, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    inner = cfg.get("n_inner") or 4 * h
    std = cfg.get("initializer_range", 0.02)
    shapes = {"q": (h, h), "k": (h, h), "v": (h, h), "out": (h, h),
              "mlp1": (h, inner), "mlp2": (inner, h)}

    def make(key):
        def normal(k, shape):
            return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

        def ln():
            return {"g": jnp.ones((h,), dtype), "b": jnp.zeros((h,), dtype)}

        k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
        # one generator call per kind of matrix, over all layers at once: a
        # call per matrix makes a program of hundreds of generators, which
        # took the compiler two minutes for the 48 layers of GPT-2-XL
        stacked = {name: jax.vmap(lambda k, shape=shape: normal(k, shape))(
                       jax.random.split(k, layers))
                   for (name, shape), k in zip(shapes.items(),
                                               jax.random.split(k_blocks, len(shapes)))}
        blocks = []
        for i in range(layers):
            blk = {"ln1": ln(), "ln2": ln()}
            for name, shape in shapes.items():
                blk[name] = {"w": stacked[name][i], "b": jnp.zeros((shape[1],), dtype)}
            blocks.append(blk)
        return {"wte": normal(k_wte, (vocab, h)),
                "wpe": normal(k_wpe, (cfg["n_positions"], h)),
                "lnf": ln(), "blocks": blocks}

    return jax.jit(make)(jax.random.PRNGKey(fold_seed(seed)))


def scope_params(scope, cfg, prefix="gpt"):
    """A copy of the weights a training scope holds, by the names
    models/gpt.py gives them, in the reference's layout. A copy, because the
    executor donates its buffers to the next step."""
    import jax.numpy as jnp

    def get(name):
        var = scope.find_var(name)
        if var is None:
            raise KeyError(f"no variable {name!r} in the scope")
        return jnp.array(var, copy=True)

    def ln(name):
        return {"g": get(f"{name}.scale"), "b": get(f"{name}.bias")}

    blocks = []
    for i in range(cfg["n_layer"]):
        pre = f"{prefix}/l{i}"
        blk = {"ln1": ln(f"{pre}/ln1"), "ln2": ln(f"{pre}/ln2")}
        for name in ("q", "k", "v", "out", "mlp1", "mlp2"):
            blk[name] = {"w": get(f"{pre}/{name}.w"), "b": get(f"{pre}/{name}.b")}
        blocks.append(blk)
    return {"wte": get(f"{prefix}/wte"), "wpe": get(f"{prefix}/wpe"),
            "lnf": ln(f"{prefix}/lnf"), "blocks": blocks}
