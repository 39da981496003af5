"""The layering of the served blocks, read from the source by `ast`:

  serving/{engine, scheduler, kv_cache, decode_loop}       know no model
  serving/model.py, serving/pages.py                       the interface
  models/_decoder.py, _experts.py, _grouped.py, _latent.py,
         _recurrent.py, _delta.py                          shared pieces
  models/{gpt_decode, moonlight, mellum, command_a, sdar,
          kimi_linear, longcat_flash, granite_hybrid,
          qwen3_next}                                      leaves

R1: no leaf imports another leaf. R2: a module under models/ imports from
serving/ only `model` and `pages`. R3: a leaf takes the shared pieces as
MODULES, so no moved name has a second address to patch. R4: the shared
modules import no jax at module level and `models/__init__.py` imports none
of them. And `jax.default_backend` is asked in two places for the served
blocks: serving/pages.py (attention) and models/_experts.py (the expert
product).

And the callers outside the package (tools/, chip_smoke.py) reach the
moved names at their new homes: every name they take of a module under
models/ or serving/ exists there, and the expert layer's own instrument
runs its program."""

import ast
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "paddle_tpu", "models")
LEAVES = ("gpt_decode", "moonlight", "mellum", "command_a", "sdar",
          "kimi_linear", "longcat_flash", "granite_hybrid", "qwen3_next")
SHARED = ("_decoder", "_experts", "_grouped", "_latent", "_recurrent",
          "_delta")
SERVED = LEAVES + SHARED


def _tree(path):
    with open(path) as f:
        return ast.parse(f.read())


def _imports(tree, top_level_only=False):
    """[(level, module, names)] of every import of a module's source."""
    nodes = tree.body if top_level_only else ast.walk(tree)
    out = []
    for node in nodes:
        if isinstance(node, ast.ImportFrom):
            out.append((node.level, node.module or "",
                        [a.name for a in node.names]))
        elif isinstance(node, ast.Import):
            out += [(0, a.name, []) for a in node.names]
    return out


def _model_files():
    return sorted(f[:-3] for f in os.listdir(MODELS) if f.endswith(".py"))


def _siblings(name):
    """The models/ modules `name` imports, however it names them."""
    found = set()
    for level, module, names in _imports(_tree(os.path.join(MODELS,
                                                            name + ".py"))):
        if level == 1:
            found |= {module.split(".")[0]} if module else set(names)
        elif module.startswith("paddle_tpu.models"):
            rest = module[len("paddle_tpu.models"):].lstrip(".")
            found |= {rest.split(".")[0]} if rest else set(names)
    return found


@pytest.mark.parametrize("leaf", LEAVES)
def test_no_served_model_imports_another(leaf):
    assert not _siblings(leaf) & set(LEAVES), (leaf, _siblings(leaf))


@pytest.mark.parametrize("shared", SHARED)
def test_a_shared_module_imports_no_model(shared):
    assert _siblings(shared) <= set(SHARED), (shared, _siblings(shared))


@pytest.mark.parametrize("name", _model_files())
def test_models_import_only_the_interface_of_serving(name):
    reached = set()
    for level, module, names in _imports(_tree(os.path.join(MODELS,
                                                            name + ".py"))):
        if level == 2 and module.split(".")[0] == "serving":
            rest = module.split(".")[1:]
            reached |= {rest[0]} if rest else set(names)
        elif module.startswith("paddle_tpu.serving"):
            rest = module.split(".")[2:]
            reached |= {rest[0]} if rest else set(names)
    assert reached <= {"model", "pages"}, (name, reached)


@pytest.mark.parametrize("leaf", [m for m in LEAVES if m != "gpt_decode"])
def test_a_leaf_takes_the_shared_pieces_as_modules(leaf):
    """`from . import _experts`, never `from ._experts import moe`: a
    patch of a moved name has ONE address, the module that reads it."""
    for level, module, names in _imports(_tree(os.path.join(MODELS,
                                                            leaf + ".py"))):
        if level == 1:
            assert not module, (leaf, module, names)
            assert set(names) <= set(SHARED), (leaf, names)
        if level == 2 and module == "serving.pages":
            pytest.fail(f"{leaf} imports names from serving/pages")


def test_the_backend_is_asked_in_two_places():
    asked = []
    for path in [os.path.join(MODELS, m + ".py") for m in SERVED] + [
            os.path.join(ROOT, "paddle_tpu", "serving", "pages.py")]:
        for node in ast.walk(_tree(path)):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "default_backend":
                asked.append(os.path.basename(path))
    assert sorted(asked) == ["_experts.py", "pages.py"], asked


def test_importing_the_package_loads_no_shared_module():
    init = _tree(os.path.join(MODELS, "__init__.py"))
    loaded = {n for _, module, names in _imports(init)
              for n in names + module.split(".")}
    assert not loaded & set(SERVED), loaded & set(SERVED)
    for name in SHARED + ("../serving/pages",):
        for _, module, _ in _imports(_tree(os.path.join(
                MODELS, name + ".py")), top_level_only=True):
            assert module.split(".")[0] != "jax", (name, module)


# -- the callers outside the package ------------------------------------------------

def _outside_callers():
    tools = os.path.join(ROOT, "tools")
    return ["chip_smoke.py"] + sorted(
        os.path.join("tools", f) for f in os.listdir(tools)
        if f.endswith(".py"))


def _names_taken(tree):
    """[(module, name)] a source takes of paddle_tpu.models / .serving:
    `from <module> import name`, and `alias.name` of a module imported
    under an alias. An `except ImportError` fall-back (an older checkout's
    address) is not read."""
    fallbacks = {id(n) for h in ast.walk(tree)
                 if isinstance(h, ast.ExceptHandler) for n in ast.walk(h)}
    aliases, taken = {}, []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom) or id(node) in fallbacks \
                or not (node.module or "").startswith(
                    ("paddle_tpu.models", "paddle_tpu.serving")):
            continue
        for a in node.names:
            try:
                importlib.import_module(f"{node.module}.{a.name}")
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
            except ImportError:
                taken.append((node.module, a.name))
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases:
            taken.append((aliases[node.value.id], node.attr))
    return taken


@pytest.mark.parametrize("path", _outside_callers())
def test_an_outside_caller_names_what_exists(path):
    for module, name in _names_taken(_tree(os.path.join(ROOT, path))):
        assert hasattr(importlib.import_module(module), name), \
            f"{path} takes {name} of {module}, which has none"


def test_the_expert_layer_tool_runs_its_program():
    """tools/bench_expert_layer.py's program (the checkout's layer under
    the tool's config and name) at a toy width: what its chip run jits."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import bench_expert_layer as tool
    finally:
        sys.path.pop(0)
    from paddle_tpu.models import _experts
    assert tool.checkout_layer() is _experts.moe
    for model in (dict(h=16, F=8, k=2, shared=1, scoring="sigmoid",
                       factor=2.5, experts=8),
                  dict(h=16, F=8, k=2, shared=2, scoring="sigmoid",
                       factor=1.0, experts=8, held=4, bias=False,
                       combination="average")):
        program = tool.layer_program("toy", tool.config_of(model),
                                     tool.checkout_layer(), 8)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16), jnp.bfloat16)
        y, c = program(tool.layer(jax, jnp, model), x, jnp.arange(8) < 5)
        assert y.shape == x.shape and bool(jnp.isfinite(
            y.astype(jnp.float32)).all())
        held = model.get("held", 8)
        assert c["expert_tokens"].shape == (held,)
        if held == 8:
            assert int(c["expert_tokens"].sum()) == 5 * 2
        assert program.__wrapped__.__name__ == "moe_toy_8"
