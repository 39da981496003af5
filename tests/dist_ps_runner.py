"""Subprocess entry for the multi-PROCESS parameter-server tests (the
reference's dist_mnist.py / dist_ctr.py analogs, driven by
paddle_tpu.distributed.launch --server_num/--worker_num). Role comes from
TRAINING_ROLE env; each worker writes its per-step losses to
$DIST_PS_OUT/worker.<id>.json.

DIST_PS_MODE selects the scenario (reference test_dist_base.py matrix):
  dense  (default) — dense fc model, sync PS
  sparse           — is_sparse embedding + remote sparse table, sync PS
  async            — dense model, sync_mode=False + background Communicator
"""

import json
import os
import sys

# CPU: three processes must not fight over one TPU, and rbg PRNG values
# differ per backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.incubate.fleet.base.role_maker import PaddleCloudRoleMaker
from paddle_tpu.incubate.fleet.parameter_server import (
    PSFleet, DistributeTranspilerConfig)

MODE = os.environ.get("DIST_PS_MODE", "dense")
STEPS = 6


def build_model(sparse):
    """The shared test model — ALSO imported by test_dist_ps.py's local
    baseline, so runner and baseline can never diverge."""
    if sparse:
        ids = pt.layers.data("ids", [1], dtype="int64")
        x = pt.layers.embedding(ids, size=[50, 8], is_sparse=True)
    else:
        x = pt.layers.data("x", [8], dtype="float32")
    label = pt.layers.data("label", [1], dtype="float32")
    h = pt.layers.fc(x, size=16, act="relu")
    pred = pt.layers.fc(h, size=1)
    return pt.layers.mean(pt.layers.square(pred - label))


def make_feed(rng, sparse):
    if sparse:
        ids = rng.randint(0, 50, (16, 1)).astype(np.int64)
        return {"ids": ids, "label": ids.astype(np.float32) / 50.0}
    x = rng.randn(16, 8).astype(np.float32)
    return {"x": x, "label": x.sum(1, keepdims=True).astype(np.float32)}


def build(f):
    strategy = None
    if MODE == "async":
        strategy = DistributeTranspilerConfig()
        strategy.sync_mode = False
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name_guard(), pt.program_guard(main, startup):
        loss = build_model(MODE == "sparse")
        opt = f.distributed_optimizer(
            pt.optimizer.SGD(learning_rate=0.05), strategy=strategy)
        opt.minimize(loss, startup_program=startup)
    main.random_seed = startup.random_seed = 9
    return main, startup, loss


def main():
    fleet = PSFleet()
    fleet.init(PaddleCloudRoleMaker())
    _, startup, loss = build(fleet)

    if fleet.is_server():
        fleet.run_server()  # blocks until a trainer sends shutdown
        return

    exe = pt.Executor()
    scope = pt.Scope()
    rng = np.random.RandomState(0)  # same data on every worker: lockstep
    losses = []
    plan = fleet.main_program._ps_plan
    comm = None
    with pt.scope_guard(scope):
        exe.run(startup)
        if MODE == "async":
            comm = plan.start_communicator(scope, recv_interval_ms=5)
        for _ in range(STEPS):
            feed = make_feed(rng, MODE == "sparse")
            (lv,) = exe.run(fleet.main_program, feed=feed,
                            fetch_list=[loss])
            losses.append(float(np.ravel(lv)[0]))
        if comm is not None:
            # flush queued pushes, then record one DETERMINISTIC final
            # loss on fully-synced params: the in-loop async losses race
            # the 5ms recv thread (on a fast box no refresh may land
            # between steps), so the test's convergence check uses this
            # last entry
            comm.stop()
            plan._communicator = None
            (lv,) = exe.run(fleet.main_program,
                            feed=make_feed(np.random.RandomState(0),
                                           MODE == "sparse"),
                            fetch_list=[loss])
            losses.append(float(np.ravel(lv)[0]))
    out_dir = os.environ["DIST_PS_OUT"]
    wid = fleet.worker_index()
    with open(os.path.join(out_dir, f"worker.{wid}.json"), "w") as f:
        json.dump(losses, f)
    # worker 0 shuts the servers down once everyone is done (barrier keeps
    # it from killing servers mid-round)
    for ep in plan.endpoints:
        plan._client(ep).barrier()
    plan.shutdown(stop_servers=(wid == 0))


if __name__ == "__main__":
    main()
    sys.exit(0)
