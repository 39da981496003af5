"""DeepFM sparse-PS throughput — the last unmeasured BASELINE target row
("DeepFM / wide&deep CTR: throughput w/ sparse PS path").

Criteo-like shape: 26 sparse fields over a 1e5-slot vocabulary, embedding
16, batch 512. The sparse tables live on a local pskv C++ server; every
step pulls the touched rows, runs the jitted dense step on the device, and
pushes sparse grads back — the full async-PS data path (transpiler ->
PSPlan -> native/pskv).

Run: python tools/bench_deepfm_ps.py  (JAX_PLATFORMS=cpu times the
host-side PS path alone)
"""

import os
import socket
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

BATCH = int(os.environ.get("BENCH_BATCH", "512"))
FIELDS = 26
VOCAB = int(os.environ.get("BENCH_VOCAB", "100000"))
EMB = 16
STEPS = int(os.environ.get("BENCH_STEPS", "100"))
SERVERS = int(os.environ.get("BENCH_SERVERS", "1"))


def main():
    import paddle_tpu as pt
    from paddle_tpu.models.deepfm import deepfm
    from paddle_tpu.transpiler import DistributeTranspiler, start_pserver

    endpoints = []
    for _ in range(SERVERS):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        endpoints.append(f"127.0.0.1:{s.getsockname()[1]}")
        s.close()

    main_p, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main_p, startup):
        spec = deepfm(num_fields=FIELDS, sparse_feature_dim=VOCAB,
                      embedding_size=EMB, dense_dim=0,
                      layer_sizes=(400, 400))
        pt.optimizer.Adam(learning_rate=1e-3).minimize(spec["loss"])

    t = DistributeTranspiler()
    t.transpile(0, program=main_p, pservers=",".join(endpoints),
                trainers=1, sync_mode=True, startup_program=startup)
    srvs = [start_pserver(t.get_pserver_program(ep)) for ep in endpoints]
    n_sparse = sum(1 for sp in main_p._ps_plan.specs if sp.sparse)

    exe = pt.Executor()
    rng = np.random.RandomState(0)

    def batch():
        ids = rng.randint(0, VOCAB, (BATCH, FIELDS)).astype(np.int64)
        label = (ids.sum(axis=1) % 2).astype(np.float32)[:, None]
        return {"feat_ids": ids, "label": label}

    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        exe.run(main_p, feed=batch(), fetch_list=[spec["loss"]])  # warm
        t0 = time.perf_counter()
        last = None
        for _ in range(STEPS):
            last = exe.run(main_p, feed=batch(),
                           fetch_list=[spec["loss"]])[0]
        lv = float(np.ravel(np.asarray(last))[0])
        dt = (time.perf_counter() - t0) / STEPS
    main_p._ps_plan.shutdown()
    for srv in srvs:
        srv.stop()

    import json
    print(json.dumps({
        "metric": f"deepfm_sparse_ps_samples_per_s_{SERVERS}srv",
        "value": round(BATCH / dt, 1),
        "unit": (f"samples/s (batch={BATCH} fields={FIELDS} vocab={VOCAB} "
                 f"emb={EMB}, {dt * 1e3:.1f} ms/step, {n_sparse} sparse "
                 f"tables sharded over {SERVERS} pskv server(s), "
                 f"loss={lv:.3f})"),
    }))


if __name__ == "__main__":
    main()
