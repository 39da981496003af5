"""The training cells' `head` and `loss` stages alone (what `loss_time_share`
reads): the product over the tied 50,257-wide table, softmax_with_cross_entropy
and its gradient, and the head's two backward products, for 8 x 1,024
positions of width 768 in bfloat16. Parent beside change.

One jitted program holds what the step holds between the final norm and the
gradient of its output, and nothing else: logits = h wte^T in bfloat16, the
shift, the op's own lowering (`get_op_def(...).lower`), the mean over the
8 x 1,023 positions that predict a token, the op's own `grad_lower` fed the
mean's gradient, and dh = d wte, dwte = d^T h. The loss op cannot be timed
without its neighbours: on the chip XLA folds the row maximum (and a slice
of the logits) into the head's product, and, since PR 51, where the
gradient is an expression of the logits and a scalar a row with no pad
behind it, the gradient into the operands of the two backward products;
alone, each of those is a pass over 0.82 GB that the step does not make.
What XLA folds HERE need not be what it folds in the whole step: the
cells' traces are the record (PERF.md section 5). `Softmax` is returned
by nobody, so XLA may drop it, as in the cells' program. `--shift`:

  loss    the op over the (b, s, V) logits as they lie against the tokens
          rolled left, the per-position LOSS cut to s - 1
          (gpt_lm_program since PR 51; the default)
  logits  the logits cut to (b, s - 1, V) before the op, the gradient
          padded back (gpt_lm_program before: say it with `--repo`)

`--repo DIR` times the lowering of another checkout (the parent's, unpacked
by `git archive`): a grad op that takes the saved `Softmax` is fed it, one
that takes `Logits` and `Lse` those. The time is the host's clock around
CALLS dispatches ending in block_until_ready (a call is ~15 ms of device
time, a dispatch microseconds); `products_ms_at_peak` is the three
products' 1.9 TFLOP at 197 TFLOP/s, the floor under any loss. A chip is
required: on any other backend it exits 1 with nothing measured (`--tiny`
rehearses the program on the CPU and prints no time).

    chiprun -- python tools/bench_loss_op.py
    chiprun -- python tools/bench_loss_op.py --repo .scratch/parent --shift logits --tag parent

Prints one JSON line; the same goes to chiprun_out/bench_loss_op[.tag].json.
Run by no cell.
"""

import argparse
import json
import os
import sys
import time

CALLS = 30
PEAK_FLOPS = 197e12              # TPU v5e, bfloat16
SHAPE = (8, 1024, 768, 50257)    # batch, positions, width, vocabulary
TINY = (2, 8, 16, 61)


def stages(opdef, shift="loss"):
    """(h, wte, tokens) -> (mean loss, dh, dwte): `head` + `loss`."""
    import jax.numpy as jnp
    from paddle_tpu.framework.registry import LowerContext

    def run(h, wte, tokens):
        ctx = LowerContext(abstract=True)   # a bench counts no lowering
        logits = jnp.einsum("bsh,vh->bsv", h, wte)
        s = logits.shape[1]
        if shift == "logits":
            x, label = logits[:, :-1], tokens[:, 1:, None]
        else:
            x, label = logits, jnp.roll(tokens, -1, axis=1)[..., None]
        out = opdef.lower(ctx, {"Logits": [x], "Label": [label]}, {})
        loss = out["Loss"][0][:, :s - 1]
        g = jnp.full(loss.shape, 1.0 / loss.size, jnp.float32)
        g = jnp.pad(g, ((0, 0), (0, x.shape[1] - (s - 1)), (0, 0)))
        ins = {"Label": [label], "Loss@GRAD": [g]}
        if "Lse" in out:
            ins.update(Logits=[x], Lse=out["Lse"])
        else:
            ins["Softmax"] = out["Softmax"]
        d = opdef.grad_lower(ctx, ins, {})["Logits@GRAD"][0]
        d = jnp.pad(d, ((0, 0), (0, s - x.shape[1]), (0, 0)))
        dh = jnp.einsum("bsv,vh->bsh", d, wte)
        dwte = jnp.einsum("bsv,bsh->vh", d, h,
                          preferred_element_type=jnp.float32)
        return jnp.mean(loss), dh, dwte

    return run


def measure(opdef, shape, timed, shift="loss"):
    import jax
    import jax.numpy as jnp

    b, s, width, v = shape
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    h = jax.random.normal(keys[0], (b, s, width), jnp.bfloat16)
    wte = (0.05 * jax.random.normal(keys[1], (v, width))).astype(jnp.bfloat16)
    tokens = jax.random.randint(keys[2], (b, s), 0, v, jnp.int32)
    fn = jax.jit(stages(opdef, shift))
    loss, dh, dwte = jax.block_until_ready(fn(h, wte, tokens))

    def reference(h, wte):
        logits = jnp.einsum("bsh,vh->bsv", h, wte).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1])
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))

    want, (want_dh, _) = jax.jit(jax.value_and_grad(reference, (0, 1)))(
        h, wte)
    dh32, want32 = dh.astype(jnp.float32), want_dh.astype(jnp.float32)
    line = {"shift": shift, "shape": list(shape), "loss": float(loss),
            "loss_rel_gap": float(abs(loss - want) / abs(want)),
            "dh_rel_err": float(jnp.max(jnp.abs(dh32 - want32))
                                / jnp.max(jnp.abs(want32))),
            "dh_last_position_max": float(jnp.max(jnp.abs(dh32[:, -1]))),
            "dwte_abs_sum": float(jnp.sum(jnp.abs(dwte)))}
    if timed:
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(h, wte, tokens)
        jax.block_until_ready(out)
        seconds = (time.perf_counter() - t0) / CALLS
        floor = 3 * 2.0 * b * s * width * v / PEAK_FLOPS
        line.update(ms=1e3 * seconds, products_ms_at_peak=1e3 * floor)
    return line


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--repo", default=None)
    parser.add_argument("--tag", default=None)
    parser.add_argument("--shift", choices=("loss", "logits"),
                        default="loss")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    root = os.path.abspath(args.repo or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    sys.path.insert(0, root)
    import jax
    if jax.default_backend() != "tpu" and not args.tiny:
        print(f"bench_loss_op: jax's default backend is "
              f"{jax.default_backend()!r}, not 'tpu'", file=sys.stderr)
        return 1
    import paddle_tpu  # noqa: F401  (registers the ops)
    from paddle_tpu.framework.registry import get_op_def

    line = measure(get_op_def("softmax_with_cross_entropy"),
                   TINY if args.tiny else SHAPE, timed=not args.tiny,
                   shift=args.shift)
    line.update(repo=root, device=jax.devices()[0].device_kind)
    print(json.dumps(line), flush=True)
    if not args.tiny:
        os.makedirs("chiprun_out", exist_ok=True)
        name = "bench_loss_op" + (f".{args.tag}" if args.tag else "")
        with open(os.path.join("chiprun_out", name + ".json"), "w") as f:
            json.dump(line, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
