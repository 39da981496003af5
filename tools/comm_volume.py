"""Collective-traffic accounting from compiled HLO (VERDICT r3 Missing #4).

The reference accounts all-reduce traffic per gradient inside
AllReduceOpHandle (details/all_reduce_op_handle.cc:83,129).  The XLA analog:
the SPMD partitioner inserts the collectives, so the ground truth is the
optimized HLO.  This tool compiles each dryrun parallelism mode on the
virtual 8-device CPU mesh, parses the collective ops out of the HLO, and
reports per-step op counts + payload bytes per device, plus an analytic
scaling-efficiency projection for a v5e-8 (tune COMM_ICI_GBPS /
COMM_PEAK_TFLOPS when real multi-chip hardware is available).

Run: python tools/comm_volume.py            # all modes, table to stdout
     python tools/comm_volume.py dp dpmp    # subset
"""

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import numpy as np  # noqa: E402

N_DEV = 8
# v5e public ballpark: ~45 GB/s/link one-way ICI, 2D torus -> aggregate
# per-chip; efficiency projection is ANALYTIC until real hardware runs
ICI_GBPS = float(os.environ.get("COMM_ICI_GBPS", "90"))
PEAK_TFLOPS = float(os.environ.get("COMM_PEAK_TFLOPS", "197"))
ASSUMED_MFU = float(os.environ.get("COMM_ASSUMED_MFU", "0.45"))

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_text: str, largest_only: bool = False) -> int:
    """Bytes of an HLO result shape.

    largest_only: for async '-start' ops whose tuple result carries the
    operand alias alongside the output (plus u32 context scalars), summing
    the tuple would double-count — the payload is the largest element."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES[dtype])
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


def parse_collectives(hlo: str):
    """-> {op_kind: {"count": n, "bytes": payload}} from optimized HLO.

    Counts the -start form only once (its -done twin carries no new
    payload); fused async pairs appear as <op>-start/<op>-done."""
    stats = {}
    payloads = []
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}: ]+?)\s+"
                     r"([\w-]+)\(", line)
        if not m:
            continue
        shape_text, opname = m.group(1), m.group(2)
        base = opname[:-6] if opname.endswith("-start") else opname
        if opname.endswith("-done"):
            continue
        if base not in _COLLECTIVES:
            continue
        b = _shape_bytes(shape_text,
                         largest_only=opname.endswith("-start"))
        ent = stats.setdefault(base, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += b
        payloads.append((base, b, line.split(" = ")[0].lstrip("%")))
    payloads.sort(key=lambda t: -t[1])
    return stats, payloads[:5]


def wire_bytes_per_device(stats, k=N_DEV):
    """Ring-algorithm per-device wire traffic from payload sizes:
    all-reduce 2N(k-1)/k, all-gather/reduce-scatter N(k-1)/k,
    collective-permute N, all-to-all N(k-1)/k."""
    total = 0.0
    for kind, ent in stats.items():
        n = ent["bytes"]
        if kind == "all-reduce":
            total += 2 * n * (k - 1) / k
        elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
            total += n * (k - 1) / k
        elif kind == "collective-permute":
            total += n
    return total


# ---------------------------------------------------------------------------
# mode builders (the dryrun_multichip matrix, one step each)
# ---------------------------------------------------------------------------

def _bert_feed(cfg, batch, seq, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "src_ids": rng.randint(0, cfg.vocab_size,
                               (batch, seq)).astype(np.int64),
        "sent_ids": rng.randint(0, 2, (batch, seq)).astype(np.int64),
        "input_mask": np.ones((batch, seq), np.float32),
        "mlm_labels": rng.randint(0, cfg.vocab_size,
                                  (batch, seq)).astype(np.int64),
    }


def _capture(build_fn, compile_fn=None):
    """Build + run one step with HLO capture; returns the optimized HLO."""
    import paddle_tpu as pt
    with pt.unique_name_guard():
        main, startup, loss, feed = build_fn()
    target = compile_fn(main) if compile_fn else main
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.capture_hlo = True
        exe.run(target, feed=feed, fetch_list=[loss])
    if exe.last_hlo is None:
        raise RuntimeError(getattr(exe, "last_hlo_error", "no HLO"))
    return exe.last_hlo


def _bert_builder(cfg, seq, batch):
    import paddle_tpu as pt
    from paddle_tpu.models.bert import bert_pretrain_program

    def build():
        main, startup, fetches = bert_pretrain_program(
            cfg, seq, learning_rate=1e-3)
        return main, startup, fetches["loss"], _bert_feed(cfg, batch, seq)
    return build


def mode_dp():
    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig
    cfg = BertConfig(vocab_size=1024, hidden=128, layers=2, heads=4,
                     ffn=512, max_pos=128, dropout=0.1)
    return _capture(
        _bert_builder(cfg, 32, N_DEV * 2),
        lambda m: __import__("paddle_tpu").CompiledProgram(m)
        .with_sharding({}, mesh_shape=(N_DEV,), axis_names=("dp",)))


def mode_dpmp():
    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig, tp_shardings
    cfg = BertConfig(vocab_size=1024, hidden=128, layers=2, heads=4,
                     ffn=512, max_pos=128, dropout=0.1)
    return _capture(
        _bert_builder(cfg, 32, (N_DEV // 2) * 2),
        lambda m: pt.CompiledProgram(m).with_sharding(
            tp_shardings(cfg), mesh_shape=(N_DEV // 2, 2),
            axis_names=("dp", "mp")))


def mode_ep():
    # EP_SCALE=1 measures at bench scale (h=768, ffn=3072, b=32 s=128 —
    # the BASELINE MoE row's shapes) instead of the tiny dryrun config
    import paddle_tpu as pt
    E = N_DEV
    big = os.environ.get("EP_SCALE", "0") == "1"
    seq, h, f = (128, 768, 3072) if big else (8, 16, 32)
    b = 32 if big else E
    rng = np.random.RandomState(1)
    xv = rng.randn(b, seq, h).astype(np.float32)
    feed = {"x": xv, "y": np.tanh(xv)}

    def build():
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [seq, h], dtype="float32")
            y = pt.layers.data("y", [seq, h], dtype="float32")
            out, aux = pt.nets.switch_moe_ffn(x, E, h, f)
            loss = pt.layers.mean(pt.layers.square(out - y)) + \
                pt.layers.scale(aux, scale=0.01)
            pt.optimizer.SGD(0.05).minimize(loss)
        return main, startup, loss, feed

    def shard(main):
        expert_params = {p.name: ("ep", None, None)
                         for p in main.all_parameters()
                         if len(p.shape) == 3 and p.shape[0] == E}
        return pt.CompiledProgram(main).with_sharding(
            expert_params, mesh_shape=(E,), axis_names=("ep",))

    return _capture(build, shard)


def mode_pp():
    import paddle_tpu as pt
    rng = np.random.RandomState(2)
    feed = {"x": rng.randn(8, 16).astype(np.float32),
            "label": rng.randint(0, 4, (8, 1)).astype(np.int64)}

    def build():
        main, startup = pt.Program(), pt.Program()
        cuts = []
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [16])
            label = pt.layers.data("label", [1], dtype="int64")
            h = pt.layers.fc(x, 32, act="tanh")
            cuts.append(h.name)
            for _ in range(4):
                h = pt.layers.fc(h, 32, act="tanh")
                cuts.append(h.name)
            logits = pt.layers.fc(h, 4)
            loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(
                label=label, logits=logits))
            opt = pt.optimizer.PipelineOptimizer(
                pt.optimizer.Adam(1e-2), cut_list=cuts, num_microbatches=2)
            opt.minimize(loss)
        return main, startup, loss, feed

    return _capture(build)


def mode_cp():
    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig, bert_pretrain_program
    cfg = BertConfig(vocab_size=512, hidden=64, layers=2, heads=8,
                     ffn=128, max_pos=64, dropout=0.0)
    cfg.attn_impl = "fused"
    cfg.cp_axis = "cp"
    feed = _bert_feed(cfg, 4, 64, seed=4)

    def build():
        m, st, f = bert_pretrain_program(cfg, 64, learning_rate=1e-3)
        return m, st, f["loss"], feed

    return _capture(
        build,
        lambda m: pt.CompiledProgram(m).with_sharding(
            {}, mesh_shape=(1, N_DEV), axis_names=("dp", "cp"),
            feed_shardings={k: (None, "cp") for k in feed}))


MODES = {"dp": mode_dp, "dpmp": mode_dpmp, "ep": mode_ep, "pp": mode_pp,
         "cp": mode_cp}


def main():
    wanted = sys.argv[1:] or list(MODES)
    print(f"{'mode':<6} {'collective':<20} {'count':>5} {'payload MiB':>12} "
          f"{'wire MiB/dev':>13} {'proj eff v5e-8':>15}")
    for name in wanted:
        hlo = MODES[name]()
        stats, top = parse_collectives(hlo)
        wire = wire_bytes_per_device(stats)
        # analytic projection: t_comm = wire/ICI, t_comp from the HLO's
        # FLOP-dominant ops is unknown here — report the comm time per step
        # and efficiency for a step of the same compute:comm ratio measured
        # at bench scale (BASELINE.md carries the narrative)
        t_comm_ms = wire / (ICI_GBPS * 1e9) * 1e3
        first = True
        if not stats:
            print(f"{name:<6} {'(none)':<20} {0:>5} {0.0:>12.2f} "
                  f"{0.0:>13.2f} {'1.000':>15}")
        for kind, ent in sorted(stats.items()):
            eff = ""
            if first:
                eff = f"comm {t_comm_ms:.3f} ms/step"
                first = False
            print(f"{name:<6} {kind:<20} {ent['count']:>5} "
                  f"{ent['bytes'] / 2**20:>12.2f} "
                  f"{wire_bytes_per_device({kind: ent}) / 2**20:>13.2f} "
                  f"{eff:>15}")
        for kind, b, nm in top[:3]:
            print(f"{'':<6}   top: {kind} {b / 2**20:.2f} MiB  {nm[:60]}")
    print(f"\nconstants: ICI {ICI_GBPS} GB/s/chip, peak {PEAK_TFLOPS} "
          f"TFLOP/s, assumed MFU {ASSUMED_MFU} (env-tunable)")


if __name__ == "__main__":
    main()
