"""The executor's steady path under a sharding plan: a step finds the scope as
the last step under the same plan left it, sends a host feed to its shards in
one transfer, and lets the donated inputs go before it waits for the device.

What decides "as the last step left it" is the scope's own note
(`Scope._placed_for`): the executor's write-back sets it, every other write
forgets it. So each case below changes the scope the way a user would and
says what the next run must then do. CPU, four of the suite's eight virtual
devices.
"""

import gc
import weakref

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.parallel.plan import ShardingPlan

PLACED = "executor_scope_vars_placed_total"
IN_PLACE = "executor_scope_in_place_runs_total"
CHIPS = 4


def _build(seed=5):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [8])
        y = pt.layers.data("y", [1], dtype="int64")
        h = pt.layers.fc(x, 16, act="relu")
        logits = pt.layers.fc(h, 4)
        loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, y))
        pt.optimizer.Adam(1e-2).minimize(loss)
    return main, startup, loss


def _feed(step):
    rng = np.random.RandomState(100 + step)
    return {"x": rng.randn(32, 8).astype("f"),
            "y": rng.randint(0, 4, (32, 1)).astype(np.int64)}


def _dp(main, loss):
    return pt.CompiledProgram(main).with_data_parallel(loss_name=loss.name,
                                                       places=CHIPS)


def _counts():
    reg = get_registry()
    return (reg.counter(PLACED).value, reg.counter(IN_PLACE).value)


class _Run:
    """A program, its data-parallel form, an executor and a scope after the
    startup program; `step(k)` returns the loss and what the counters gained."""

    def __init__(self):
        self.main, self.startup, self.loss = _build()
        self.target = _dp(self.main, self.loss)
        self.exe, self.scope = pt.Executor(), pt.Scope()
        self.exe.run(self.startup, scope=self.scope)

    def step(self, k, target=None, scope=None):
        before = _counts()
        out, = self.exe.run(target or self.target, feed=_feed(k),
                            fetch_list=[self.loss], scope=scope or self.scope)
        after = _counts()
        return float(out.reshape(-1)[0]), (after[0] - before[0], after[1] - before[1])


def test_scope_is_placed_by_the_first_run_only_and_losses_are_the_forced_walks():
    run = _Run()
    steps = [run.step(k) for k in range(3)]
    placed = [gained[0] for _, gained in steps]
    assert placed[0] > 10 and placed[1:] == [0, 0], placed
    assert [gained[1] for _, gained in steps] == [0, 1, 1]

    # the same three steps with every variable handed to the plan each step:
    # a plain write of a value forgets who placed it
    forced = _Run()
    losses = []
    for k in range(3):
        for n in forced.scope.var_names():
            forced.scope.set_var(n, forced.scope.find_var(n))
        loss, gained = forced.step(k)
        assert gained[0] == placed[0] and gained[1] == 0
        losses.append(loss)
    assert losses == [loss for loss, _ in steps]


def test_a_variable_replaced_between_steps_is_placed_again_and_used():
    run = _Run()
    run.step(0)
    name = run.main.all_parameters()[0].name
    zeros = np.zeros_like(np.asarray(run.scope.find_var(name)))
    run.scope.set_var(name, zeros)
    loss, gained = run.step(1)
    assert gained == (1, 0), gained

    # a second run whose weights were replaced the same way, every variable
    # forced through the plan: the loss is that of the replaced weights
    other = _Run()
    other.step(0)
    other.scope.set_var(other.main.all_parameters()[0].name, zeros.copy())
    for n in other.scope.var_names():
        other.scope.set_var(n, other.scope.find_var(n))
    assert loss == other.step(1)[0]
    untouched = _Run()
    untouched.step(0)
    assert loss != untouched.step(1)[0]
    # and the step after finds everything in place again
    assert run.step(2)[1] == (0, 1)


def test_two_scopes_alternating_keep_their_own_state():
    run = _Run()
    second = pt.Scope()
    run.exe.run(run.startup, scope=second)
    turns = {"a": [], "b": []}
    for ka, kb in ((0, 0), (1, 3), (2, 4)):           # other batches for the second scope
        turns["a"].append(run.step(ka))
        turns["b"].append(run.step(kb, scope=second))
    for name in turns:
        gained = [g for _, g in turns[name]]
        assert gained[0][0] > 10 and gained[0][1] == 0   # placed by its own first run
        assert gained[1:] == [(0, 1), (0, 1)]
    assert turns["a"][0][0] == turns["b"][0][0]      # same seed, same batch
    # alone, each scope gives what it gave in turns
    for name, batches in (("a", (0, 1, 2)), ("b", (0, 3, 4))):
        solo = _Run()
        assert [solo.step(k)[0] for k in batches] == [loss for loss, _ in turns[name]]


def test_another_program_or_executor_writing_the_scope_is_seen():
    run = _Run()
    run.step(0)
    assert run.step(1)[1] == (0, 1)
    # the same program on the same scope without a plan: its write-back is a
    # plain write, so the plan places what it wrote
    run.step(2, target=run.main)
    _, gained = run.step(3)
    assert gained[0] > 10 and gained[1] == 0
    # a second executor under a plan of its own
    other = pt.Executor()
    twin = _dp(run.main, run.loss)
    before = _counts()
    other.run(twin, feed=_feed(4), fetch_list=[run.loss], scope=run.scope)
    assert _counts()[0] - before[0] > 10             # not this plan's: handed to it
    _, gained = run.step(5)
    assert gained[0] > 10
    assert run.step(6)[1] == (0, 1)


def test_a_read_only_persistable_is_transferred_once():
    import jax
    run = _Run()
    lr = [n for n in run.scope.var_names() if "learning_rate" in n]
    assert len(lr) == 1, run.scope.var_names()
    assert len(run.scope.find_var(lr[0]).sharding.device_set) == 1
    moved = []
    real = ShardingPlan._put

    def put(self, v, sharding):
        out = real(self, v, sharding)
        if out is not v:
            moved.append(tuple(getattr(v, "shape", ())))
        return out

    ShardingPlan._put = put
    try:
        run.step(0)
        first, moved[:] = list(moved), []
        run.step(1)
        run.step(2)
    finally:
        ShardingPlan._put = real
    assert len(first) > 10
    assert moved == [(32, 8), (32, 1)] * 2            # the two feeds, nothing of the scope
    placed = run.scope.find_var(lr[0])
    assert isinstance(placed, jax.Array)
    assert len(placed.sharding.device_set) == CHIPS
    # a new rate is a plain write: placed again, once, and used
    run.scope.set_var(lr[0], np.asarray([0.5], "float32"))
    assert run.step(3)[1] == (1, 0)
    assert run.step(4)[1] == (0, 1)


def test_the_executor_holds_nothing_of_a_dropped_scope():
    run = _Run()
    run.step(0)
    run.step(1)
    name = run.main.all_parameters()[0].name
    ref = weakref.ref(run.scope.find_var(name))
    assert ref() is not None
    exe = run.exe
    scope = run.scope
    del run, scope
    gc.collect()
    assert ref() is None
    assert exe.run_count == 3


def test_donated_inputs_are_gone_when_the_fetch_opens(monkeypatch):
    run = _Run()
    run.step(0)
    name = run.main.all_parameters()[0].name
    ref = weakref.ref(run.scope.find_var(name))
    alive_at = {}
    real = executor_mod.trace_span

    def span(name_, cat="", args=None):
        if name_ in ("executor/dispatch", "executor/fetch", "executor/release"):
            alive_at[name_] = ref() is not None
        return real(name_, cat, args)

    monkeypatch.setattr(executor_mod, "trace_span", span)
    run.step(1)
    assert alive_at == {"executor/dispatch": True, "executor/fetch": False,
                        "executor/release": False}


def test_without_donation_the_callers_aliases_stay_readable():
    main, startup, loss = _build()
    exe = pt.Executor(donate=False)
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    name = main.all_parameters()[0].name
    alias = scope.find_var(name)
    before = np.asarray(alias).copy()
    for k in range(2):
        exe.run(main, feed=_feed(k), fetch_list=[loss], scope=scope)
    np.testing.assert_array_equal(np.asarray(alias), before)
    assert not np.array_equal(np.asarray(scope.find_var(name)), before)


def test_a_host_feed_goes_to_its_shards_in_one_transfer_and_a_placed_one_passes(monkeypatch):
    import jax
    run = _Run()
    run.step(0)
    plan = run.target._plan()
    puts, handed, seen = [], {}, {}
    real_put, real_shard = jax.device_put, plan.shard_feed

    def device_put(x, *a, **kw):
        puts.append(type(x).__name__)
        return real_put(x, *a, **kw)

    def shard_feed(feed):                             # its result is the step's argument
        handed.update(feed)
        seen.update(real_shard(feed))
        return dict(seen)

    monkeypatch.setattr(plan, "shard_feed", shard_feed)
    monkeypatch.setattr(jax, "device_put", device_put)
    run.step(1)
    # one transfer a feed, of the HOST array, onto the plan's sharding
    assert puts == ["ndarray", "ndarray"], puts
    assert all(isinstance(v, np.ndarray) for v in handed.values())
    for k, v in seen.items():
        assert v.sharding == plan.feed_sharding(tuple(v.shape), name=k)
        assert len(v.sharding.device_set) == CHIPS
    assert str(seen["y"].dtype) == "int32"            # as jnp.asarray would have made it

    # a device feed already under the plan's sharding is the step's argument
    given = {k: real_put(v, plan.feed_sharding(tuple(v.shape), name=k))
             for k, v in _feed(2).items()}
    assert str(given["y"].dtype) == "int32"           # the variable's dtype while x64 is off
    puts.clear()
    seen.clear()
    run.exe.run(run.target, feed=given, fetch_list=[run.loss], scope=run.scope)
    assert puts == []
    assert sorted(seen) == sorted(given) and all(seen[k] is given[k] for k in given)


def test_without_a_plan_nothing_is_placed_and_the_feed_is_made_as_before():
    import jax
    main, startup, loss = _build()
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    before = _counts()
    for k in range(2):
        exe.run(main, feed=_feed(k), fetch_list=[loss], scope=scope)
    after = _counts()
    assert after[0] - before[0] == 0 and after[1] - before[1] == 2
    assert scope._placed_for == {}
    made = executor_mod._as_feed_array(_feed(0)["y"], main.global_block.vars["y"])
    assert isinstance(made, jax.Array) and str(made.dtype) == "int32"


@pytest.mark.parametrize("spec", [(), ("dp",), (None, "dp")])
def test_the_plan_builds_a_sharding_once(spec):
    plan = ShardingPlan(places=CHIPS)
    assert plan._nsh(plan._spec(*spec)) is plan._nsh(plan._spec(*spec))
    assert plan.scope_sharding("any") is plan.scope_sharding("other")


def _plan_kind(kind):
    """(target, startup, loss, feed(k)) under each kind of plan the executor
    meets: GSPMD data parallel, GSPMD with a sharded parameter, and the
    explicit shard_map of a collective-transpiled program (the last two over
    all eight devices)."""
    if kind == "collective":
        from paddle_tpu.incubate.fleet.base.role_maker import Role, UserDefinedRoleMaker
        from paddle_tpu.incubate.fleet.collective import CollectiveOptimizer, fleet
        fleet.init(UserDefinedRoleMaker(current_id=0, role=Role.WORKER, worker_num=8))
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [8])
            y = pt.layers.data("y", [1], dtype="float32")
            pred = pt.layers.fc(pt.layers.fc(x, 16, act="tanh"), 1)
            loss = pt.layers.reduce_mean(pt.layers.square(pred - y))
            CollectiveOptimizer(pt.optimizer.SGD(0.1)).minimize(loss)

        def feed(k):
            return {"x": _feed(k)["x"], "y": _feed(k)["y"].astype("f")}
        return pt.CompiledProgram(main).with_collective(nranks=8), startup, loss, feed
    main, startup, loss = _build()
    if kind == "dp":
        return _dp(main, loss), startup, loss, _feed
    weight = main.all_parameters()[0].name
    return pt.CompiledProgram(main).with_sharding(
        {weight: (None, "mp")}, mesh_shape=(2, 4), axis_names=("dp", "mp")), startup, loss, _feed


@pytest.mark.parametrize("kind", ["dp", "sharded_parameter", "collective"])
def test_every_plan_returns_the_scope_under_its_own_shardings(kind):
    """What the scope's note rests on: a step compiled under a plan returns
    every name under `plan.scope_sharding(name)`, so a value it left needs no
    second look, and handing it back compiles nothing anew."""
    target, startup, loss, feed = _plan_kind(kind)
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    placed = []
    for k in range(3):
        before = _counts()[0]
        exe.run(target, feed=feed(k), fetch_list=[loss], scope=scope)
        placed.append(_counts()[0] - before)
    assert placed[0] > 0 and placed[1:] == [0, 0]
    plan = target._plan()
    for name in scope.var_names():
        assert scope.find_var(name).sharding == plan.scope_sharding(name), name
        assert scope._placed_for[name] is plan
    step = exe._cache[next(reversed(exe._cache))]
    assert step._cache_size() == 1                   # one trace served all three steps
