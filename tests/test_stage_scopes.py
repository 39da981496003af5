"""The stages a device trace reads: `jax.named_scope`s in the GPT serving
programs and in the engine's decode loop, `pt.name_scope`'s stamp
(`op_namescope`) on Program ops, and `executor/release`.

(a) the scopes change no instruction: optimized HLO less its metadata is
    byte-identical with the scopes and without, and so are greedy tokens and
    the first losses;
(b) every stage appears in the `op_name` metadata of the program it belongs
    to, and `loop/sample`, `loop/finish` in the chunk of every model family
    the engine serves;
(c) `op_namescope`: nesting, inheritance by `_grad` ops, `optimizer`, absence
    without `name_scope`, survival through clone, serialisation, the fusion
    and recompute passes; persistable names as the parent's;
(d) `executor/release` reaches a profiler trace after `executor/fetch`.
The CPU gives names and identity, never a time."""

import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.framework import core
from paddle_tpu.framework.core import NAMESCOPE_ATTR
from paddle_tpu.models import gpt_decode as gd
from paddle_tpu.models.gpt import GPTConfig, gpt_lm_program
from paddle_tpu.serving import ServingConfig, ServingEngine

CFG = GPTConfig(vocab_size=97, hidden=32, layers=2, heads=4, max_pos=64,
                dropout=0.0, attn_impl="xla")
SEQ = 16
GPT_STAGES = {"embed", "norm", "attn/project", "attn/write", "attn/attend",
              "ffn/dense", "head"}
LOOP_STAGES = {"loop/sample", "loop/finish"}
FAMILIES = ("attn", "ffn", "loop", "moe", "mla", "hc")
BARE = ("embed", "norm", "head", "loss", "optimizer")


@contextlib.contextmanager
def no_scopes():
    """The parent's programs: `jax.named_scope` names nothing and
    `pt.name_scope` neither prefixes nor stamps."""
    named, enter, leave = jax.named_scope, core.name_scope.__enter__, \
        core.name_scope.__exit__
    jax.named_scope = lambda name: contextlib.nullcontext()
    core.name_scope.__enter__ = lambda self: self
    core.name_scope.__exit__ = lambda self, *exc: False
    try:
        yield
    finally:
        jax.named_scope = named
        core.name_scope.__enter__, core.name_scope.__exit__ = enter, leave


def instructions(hlo):
    """Optimized HLO text less what names an instruction's origin: each
    instruction's `metadata={...}`, the module's tables of files, functions
    and stack frames, and the NUMBER in an instruction's name, which counts
    the instructions made before it (jax shares the lowering of an inner jit
    such as `_where` only under one name stack, so the module holds more
    copies before XLA inlines them): names are renumbered in order of first
    appearance. Opcodes, shapes, layouts, operands and order all stay."""
    head, _, rest = hlo.partition("\n")
    body = rest[re.search(r"^(%|ENTRY )", rest, re.M).start():]
    body = re.sub(r",? ?metadata=\{[^}]*\}", "", body)
    seen = {}
    return head + "\n" + re.sub(
        r"%([\w\-]+?)\.(\d+)\b",
        lambda m: seen.setdefault(m.group(0), f"%{m.group(1)}#{len(seen)}"), body)


def stages_in(hlo):
    """The stages named in the `op_name`s of an HLO text, innermost a name."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        parts = op_name.rstrip(":").split("/")
        for i in range(len(parts) - 1, -1, -1):
            if parts[i] in BARE:
                found.add(parts[i])
                break
            if i and parts[i - 1] in FAMILIES:
                found.add(f"{parts[i - 1]}/{parts[i]}")
                break
    return found


@pytest.fixture(scope="module")
def gpt_params():
    main, startup, _ = gpt_lm_program(CFG, 8, is_test=True)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        return gd.collect_gpt_params(scope, CFG)


def engine_of(params, cfg, **kw):
    kw.setdefault("prefill_buckets", (8, 16))
    return ServingEngine(params, cfg, ServingConfig(
        num_slots=3, max_len=48, block_size=4, decode_chunk=4, **kw))


def serving_hlo(engine, program):
    """The compiled text of one of the scheduler's own jitted programs."""
    s = engine.scheduler
    s._ensure_jits()
    if program == "prefill":
        lowered = s._prefill_jit.lower(
            s.params, s.kv.arena, s._pt, s._state, np.zeros((1, 8), np.int32),
            np.int32(0), np.int32(5),
            np.zeros((s.kv.table_width,), np.int32), np.int32(0))
    elif program == "admit":
        lowered = s._admit_jit.lower(
            s._keys, s._state, np.int32(0), np.int32(1),
            jnp.zeros((s.cfg.vocab_size,), jnp.float32), np.float32(0.0),
            np.int32(5), np.int32(4), np.int32(-1), np.int32(0))
    else:
        lowered = s._chunk_jit.lower(s.params, s.kv.arena, s._pt, s._keys,
                                     s._state)
    return lowered.compile().as_text()


def train_case():
    """(first two losses, the step's optimized HLO) of a tiny GPT under AMP."""
    with pt.unique_name.guard():                   # a build's names are its own
        main, startup, fetches = gpt_lm_program(CFG, SEQ, learning_rate=1e-3,
                                                amp=True)
    main.random_seed = startup.random_seed = 11
    batch = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, SEQ))
    exe, scope = pt.Executor(), pt.Scope()
    exe.capture_hlo = True
    with pt.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(main, feed={"tokens": batch},
                                fetch_list=[fetches["loss"]])[0].reshape(-1)[0])
                  for _ in range(2)]
    return losses, exe.last_hlo, main


# -- (a) metadata only -----------------------------------------------------------

@pytest.mark.parametrize("program", ["chunk", "prefill", "admit"])
def test_scopes_change_no_instruction_of_a_serving_program(gpt_params, program):
    named = serving_hlo(engine_of(gpt_params, CFG), program)
    with no_scopes():
        bare = serving_hlo(engine_of(gpt_params, CFG), program)
    assert stages_in(named) and not stages_in(bare)
    assert instructions(named) == instructions(bare)


def test_scopes_change_no_greedy_token(gpt_params):
    prompts = [np.arange(3 + i, 9 + 2 * i, dtype=np.int32) % CFG.vocab_size
               for i in range(4)]
    named = engine_of(gpt_params, CFG).generate(prompts, max_new_tokens=6)
    with no_scopes():
        bare = engine_of(gpt_params, CFG).generate(prompts, max_new_tokens=6)
    assert [list(a) for a in named] == [list(b) for b in bare]


def test_the_stamp_changes_no_instruction_of_the_training_step():
    losses, named, main = train_case()
    with no_scopes():
        bare_losses, bare, bare_main = train_case()
    assert any(NAMESCOPE_ATTR in op.attrs for op in main.global_block.ops)
    # without the scope the optimizer's stamp is all there is
    assert {op.attrs.get(NAMESCOPE_ATTR) for op in bare_main.global_block.ops} \
        == {None, "optimizer"}
    assert [op.type for op in main.global_block.ops] \
        == [op.type for op in bare_main.global_block.ops]
    assert losses == bare_losses                   # to the bit
    assert instructions(named) == instructions(bare)
    # a checkpoint of the parent loads: every persistable keeps its name
    names = lambda prog: sorted(v.name for v in prog.list_vars() if v.persistable)
    assert names(main) == names(bare_main)
    assert any(n.startswith("adamoptimizer/gpt/l1/q.w/") for n in names(main))


# -- (b) every stage is in the program it belongs to -----------------------------

@pytest.mark.parametrize("program", ["prefill", "decode", "verify", "train"])
def test_every_stage_is_named_in_its_program(gpt_params, program):
    if program == "train":
        found = stages_in(train_case()[1])
        assert {"embed", "norm", "head", "loss", "optimizer"} <= found, found
        for op in ("attn/mul", "attn/mul_grad", "attn/fused_attention",
                   "attn/fused_attention_grad", "ffn/mul", "ffn/gelu_grad"):
            assert op in found, (op, found)
        hlo = train_case()[1]
        for op in ("head/matmul", "head/matmul_grad", "head/layer_norm",
                   "loss/softmax_with_cross_entropy", "optimizer/adam",
                   "norm/layer_norm_grad", "embed/lookup_table"):
            assert f"/{op}" in hlo, op
        return
    if program == "prefill":
        found = stages_in(serving_hlo(engine_of(gpt_params, CFG), "prefill"))
        assert found == GPT_STAGES, found
        return
    kw = {"speculate_k": 2} if program == "verify" else {}
    found = stages_in(serving_hlo(engine_of(gpt_params, CFG, **kw), "chunk"))
    want = GPT_STAGES | LOOP_STAGES | ({"loop/draft"} if kw else set())
    assert found == want, found


def test_the_unpaged_pair_names_its_stages(gpt_params):
    tokens = jnp.zeros((1, 6), jnp.int32)
    text = jax.jit(lambda p, t: gd.gpt_prefill(p, CFG, t, 12)).lower(
        gpt_params, tokens).compile().as_text()
    assert stages_in(text) == GPT_STAGES
    cache = jnp.zeros((CFG.layers, 2, 1, CFG.heads, 12, 8), jnp.float32)
    text = jax.jit(lambda p, t, c: gd.gpt_decode_step(p, CFG, t, c, 6)).lower(
        gpt_params, tokens[:, 0], cache).compile().as_text()
    assert stages_in(text) == GPT_STAGES


def _moonlight(**more):
    from paddle_tpu.models.moonlight import MoonlightConfig, init_params
    cfg = MoonlightConfig(
        vocab_size=211, hidden=64, layers=3, heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate=96, moe_intermediate=32, n_routed_experts=8,
        n_shared_experts=1, experts_per_tok=2, max_pos=64, **more)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32), {}


def _mellum():
    from paddle_tpu.models.mellum import MellumConfig, init_params
    cfg = MellumConfig(
        vocab_size=211, hidden=64, layers=8, heads=4, kv_heads=1, head_dim=16,
        moe_intermediate=32, n_routed_experts=8, experts_per_tok=2,
        sliding_window=8, max_pos=64, rope_scaling={
            "type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1})
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32), \
        {"prefill_buckets": (8, 16, 32)}


def _command_a():
    from paddle_tpu.models.command_a import CommandAConfig, init_params
    cfg = CommandAConfig(
        vocab_size=96, hidden=64, layers=4, heads=8, kv_heads=2, head_dim=16,
        moe_intermediate=32, n_routed_experts=16, n_shared_experts=2,
        experts_per_tok=4, experts_held=(4, 4), vocab_slice=(0, 96, 768),
        sliding_window=8, max_pos=64)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32), \
        {"prefill_buckets": (8, 16, 32)}


def _xing():
    return _moonlight(q_lora_rank=16, hc_mult=4, hc_sinkhorn_iters=6,
                      name="Xing4.0-29B-A4B")


def _sdar():
    from paddle_tpu.models.sdar import SdarConfig, init_params
    cfg = SdarConfig(
        vocab_size=211, hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16,
        moe_intermediate=32, n_routed_experts=8, experts_per_tok=2,
        max_pos=64, mask_token_id=210, init_range=0.08)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32), {}


_MOE = {"moe/router", "moe/dispatch", "moe/experts", "moe/combine"}
_MLA = {"mla/project", "mla/attend", "ffn/dense", "head",
        "moe/shared"} | _MOE
# block: (its builder, the stages of its prefill, those its decode chunk
# has beside them, the families it must not name)
BLOCK_STAGES = {
    "moonlight": (_moonlight, _MLA, {"mla/absorb"}, ("attn/", "hc/")),
    "xing": (_xing, _MLA | {"hc/coeff", "hc/pre", "hc/post"}, {"mla/absorb"},
             ("attn/",)),
    "mellum": (_mellum, {"attn/project", "attn/window", "attn/full",
                         "head"} | _MOE, set(), ("mla/", "ffn/", "hc/")),
    "command_a": (_command_a, {"embed", "norm", "attn/project", "attn/window",
                               "attn/full", "moe/shared", "head"} | _MOE,
                  set(), ("mla/", "ffn/", "hc/")),
    # the prefill returns no logits: neither the final norm nor the head runs
    "sdar": (_sdar, {"embed", "norm", "attn/project", "attn/full"} | _MOE,
             {"head"}, ("mla/", "ffn/", "hc/", "attn/window")),
}


@pytest.mark.parametrize("program", ["chunk", "prefill"])
@pytest.mark.parametrize("block", sorted(BLOCK_STAGES))
def test_every_block_names_every_stage_in_both_programs(block, program):
    """Every stage of every served block, read from the compiled programs'
    `op_name`s as benchmarks/lib/stage_times.py reads a trace: a jaxpr does
    not show a lost `named_scope`. command-a's expert layer through the
    `lax.cond` of a share's second buffer size where a program has it;
    SDAR's `head` in the block step alone."""
    build, both, decode_only, never = BLOCK_STAGES[block]
    cfg, params, kw = build()
    found = stages_in(serving_hlo(engine_of(params, cfg, **kw), program))
    want = both | (decode_only if program == "chunk" else set())
    assert want <= found, (sorted(want - found), found)
    assert not {s for s in found if s.startswith(never)}, found
    if block == "sdar" and program == "prefill":
        assert "head" not in found


@pytest.mark.parametrize("family", ["gpt", "moonlight", "mellum", "command_a"])
def test_the_loop_names_its_work_for_every_model_family(gpt_params, family):
    cfg, params, kw = (CFG, gpt_params, {}) if family == "gpt" else \
        {"moonlight": _moonlight, "mellum": _mellum,
         "command_a": _command_a}[family]()
    engine = engine_of(params, cfg, **kw)
    found = stages_in(serving_hlo(engine, "chunk"))
    assert LOOP_STAGES <= found, found
    # the model's step between them keeps the model's own scopes
    own = {"gpt": "attn/project", "moonlight": "mla/attend",
           "mellum": "attn/window", "command_a": "moe/shared"}[family]
    assert own in found and "head" in found
    assert LOOP_STAGES <= stages_in(serving_hlo(engine, "admit"))


# -- (c) op_namescope ---------------------------------------------------------------

def _small_program():
    main, startup = pt.Program(), pt.Program()
    with pt.unique_name.guard(), pt.program_guard(main, startup):
        x = pt.layers.data("x", [4])
        y = pt.layers.data("y", [1])
        with pt.name_scope("body"):
            h = pt.layers.fc(x, 8, act="relu")
            with pt.name_scope("inner"):
                h = pt.layers.fc(h, 8)
        out = pt.layers.fc(h, 1)
        with pt.name_scope("loss"):
            loss = pt.layers.mean(pt.layers.square(out - y))
        pt.optimizer.Adam(1e-2, grad_clip=pt.clip.GradientClipByGlobalNorm(1.0)) \
            .minimize(loss)
    return main, startup, loss


def test_op_namescope_nests_is_inherited_and_is_absent_without_a_scope():
    main, startup, loss = _small_program()
    ops = main.global_block.ops
    stamp = lambda op: op.attrs.get(NAMESCOPE_ATTR)
    forward = [op for op in ops if op.attrs.get("op_role") is None]
    assert {stamp(op) for op in forward} == {None, "body", "body/inner", "loss"}
    assert any(stamp(op) == "body/inner" and op.type == "mul" for op in forward)
    # an op built under no scope carries no such attribute at all
    assert any(NAMESCOPE_ATTR not in op.attrs for op in forward)
    backward = [op for op in ops if op.attrs.get("op_role") == "backward"]
    for scope in ("body", "body/inner", "loss"):
        assert any(stamp(op) == scope and op.type.endswith("_grad")
                   for op in backward), scope
    assert any(stamp(op) is None and op.type.endswith("_grad") for op in backward)
    optimize = [op for op in ops if op.attrs.get("op_role") == "optimize"]
    assert {stamp(op) for op in optimize} == {"optimizer"}
    assert "adam" in {op.type for op in optimize}
    assert len({op.type for op in optimize}) > 1     # the clipping too
    # the stamp is no prefix: every persistable is named as without it
    names = lambda prog: sorted(v.name for v in prog.list_vars() if v.persistable)
    stamping, core._stamp_namescope = core._stamp_namescope, lambda attrs: attrs
    try:
        unstamped = _small_program()[0]
    finally:
        core._stamp_namescope = stamping
    assert names(unstamped) == names(main)
    assert all(NAMESCOPE_ATTR not in op.attrs or op.attrs[NAMESCOPE_ATTR] == "optimizer"
               for op in unstamped.global_block.ops)
    assert not any(n.startswith("optimizer/") for n in names(main))


def test_op_namescope_survives_clone_and_serialisation():
    main, _, _ = _small_program()
    stamps = lambda prog: [(op.type, op.attrs.get(NAMESCOPE_ATTR))
                           for op in prog.global_block.ops]
    assert stamps(main.clone()) == stamps(main)
    with pt.name_scope("elsewhere"):               # a clone keeps its own
        assert stamps(main.clone()) == stamps(main)
    test = stamps(main.clone(for_test=True))
    assert test == stamps(main)[:len(test)] and ("mul", "body/inner") in test
    assert stamps(pt.Program.parse_from_string(main.serialize_to_string())) == stamps(main)


def test_op_namescope_passes_through_the_fusion_and_recompute_passes():
    from paddle_tpu.framework.passes import apply_pass

    def build(scoped):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [8])
            with (pt.name_scope("ffn") if scoped else contextlib.nullcontext()):
                h = pt.layers.fc(x, 8)
                pt.layers.relu(pt.layers.elementwise_add(h, x))
        apply_pass("fuse_elewise_add_act", main)
        return main

    scoped, bare = build(True), build(False)
    types = lambda prog: [op.type for op in prog.global_block.ops]
    assert types(scoped) == types(bare) and "fused_elemwise_activation" in types(bare)
    fused = next(op for op in scoped.global_block.ops
                 if op.type == "fused_elemwise_activation")
    assert fused.attrs[NAMESCOPE_ATTR] == "ffn"
    assert all(NAMESCOPE_ATTR not in op.attrs for op in bare.global_block.ops)
    strip = lambda op: {k: v for k, v in op.attrs.items() if k != NAMESCOPE_ATTR}
    assert [strip(a) for a in scoped.global_block.ops] \
        == [strip(b) for b in bare.global_block.ops]

    main, _, _ = gpt_lm_program(CFG, SEQ, recompute=True)
    with no_scopes():
        parent, _, _ = gpt_lm_program(CFG, SEQ, recompute=True)
    assert types(main) == types(parent)
    remat = [op for op in main.global_block.ops
             if op.attrs.get("op_role") == "backward"
             and not op.type.endswith("_grad") and NAMESCOPE_ATTR in op.attrs]
    assert {op.attrs[NAMESCOPE_ATTR] for op in remat} >= {"norm", "attn", "ffn"}


# -- (d) the time after the fetch has a name --------------------------------------

def test_release_follows_the_fetch_inside_the_run():
    """In the program's own ring; the profiler's trace is PR 24's
    test_phase_spans_reach_the_profiler_trace[executor], which lists it."""
    tracer = pt.observability.get_tracer()
    tracer.enable()
    try:
        tracer.clear()
        train_case()
        spans = [(s.name, s.ts_us, s.ts_us + s.dur_us) for s in tracer.snapshot()
                 if s.name.startswith("executor/")]
    finally:
        tracer.disable()
        tracer.clear()
    runs = [s for s in spans if s[0] == "executor/run"]
    assert len(runs) == 3                          # the startup and two steps
    for _, lo, hi in runs:
        kids = sorted((s for s in spans if s[0] != "executor/run"
                       and lo <= s[1] and s[2] <= hi), key=lambda s: s[1])
        assert [k[0] for k in kids][-2:] == ["executor/fetch", "executor/release"]
        assert kids[-2][2] <= kids[-1][1]          # after it, not around it
