"""What a model supplies to the serving engine.

The engine, the scheduler and the cache manager know no architecture. A
model is served through one `ServingModel`, and what a new model writes
is four things: a description of the cache state a token leaves behind
in a layer (`cache_spec` -> `CacheSpec`: how many "heads" the arena has
and how wide a row is), its `prefill` of one prompt suffix into its
pages, ONE `decode_step` over the whole pool and, if it implements
speculation, one multi-position `verify` pass; plus the engine features
it declares. A model that generates by DIFFUSION OVER BLOCKS (the
feature "block_diffusion") writes one `block_step`, a pass over a block
of B positions a slot, in place of the decode step, and names its
block length, denoising steps, threshold and mask token in
`diffusion(cfg)`. Everything else is the engine's and is the same for
every model: slots, blocks, page tables, prefix hashing, swap and
migration payloads on the block axis, the HTTP service, and the whole
fused decode loop around the step or the pass (`decode_loop`: the scan,
the sampler and its key cadence, the frozen-slot rule, the EOS/budget
finish rule, the n-gram drafter and its acceptance, the unmasking rule
and the commit of a denoised block, the named decode carry). A model
has no loop of its own: whether a scan iteration yields one token a
slot, several or, until a block commits, none is the loop's body, which
the engine picks from what the model declares.

A config object names its model by a `serving_model()` method
(`models.gpt.GPTConfig`, `models.moonlight.MoonlightConfig`); a config
without one is served as a GPT, which is what the engine did before it
had this interface. The model's module is imported when the first engine
over such a config is built, never by `import paddle_tpu`.

CACHE GROUPS. `cache_spec` may return a tuple of `CacheSpec`s: each is a
group of the model's layers with an arena, a block pool and page rows of
its own (layers that attend over everything beside layers that attend
over a window). The first group is the PRIMARY: its pages are columns
`[0, max_pages)` of the one page table and it alone is what the
single-group engine always had (`kv_blocks`, `blocks_used`, the gauges).
Every further group's page row follows in the SAME table, at the columns
`cache_groups` gives (`GroupLayout.columns`), and its block ids index ITS
arena; the arena the programs are handed is then the tuple of the
groups' arenas, in order. A group with a `window` is a RING: a slot holds
at most `ring_pages(window, block_size) = ceil(window / block_size) + 1`
blocks of it whatever its length, the row is fixed at admission (the
device table changes at admission alone, nothing is freed mid-request)
and position p lives in row entry `(p // block_size) % ring_pages`, row
`p % block_size`; a block is overwritten once every position in it has
left every later position's window. A model with more than one group
takes no prefix hits (a hit is valid for a window group only with the
window's rows before it), and host swap (`preempt`) and migration are
refused for it at construction and at the call: both carry one group.

STATE GROUPS. A group may hold, instead of rows a token, a FIXED-SIZE
STATE A SLOT (`CacheSpec.state`): what a recurrent layer carries from
position to position, the same size whatever the sequence's length, in a
type of its own (`dtype`: a delta-rule state is float32 beside a bfloat16
latent arena). Nothing new manages it: a slot's state of one layer IS ONE
BLOCK of the group's arena `(layers, 1, num_blocks) + state_shape`, the
group's page row is ONE column of the one page table, and the group's
`_GroupPool` hands the slot that block at admission and takes it back when
the slot retires, with its pages. The block is WRITTEN WHOLE by the
prefill (never read: an admitted slot starts from its prompt's state, so
admission resets nothing), read-modified-written once a step by a live
slot, and a frozen slot's write goes to scratch block 0 like a frozen
row's. A state group comes after the primary group. What several groups
already mean holds for it (no prefix hits, no swap, no migration, one
chip, full-precision arenas), and `require_features` says for each
option what a state group lacks: no snapshot of a slot's state is taken,
so nothing can park it, hand it on, roll it back or resume it mid-prompt.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

__all__ = ["FEATURES", "BLOCK_DIFFUSION", "DIFFUSION_COUNTERS", "CacheSpec",
           "GroupLayout", "ServingModel", "state_groups",
           "serving_model", "require_features", "cache_groups",
           "group_columns", "ring_pages"]

# engine features a model may implement, each switched on by a
# ServingConfig field (see `require_features`)
FEATURES = ("int8_weights", "int8_kv", "adapters", "speculation", "mesh",
            "prefill_chunk")
# what a model declares of HOW it generates, switched on by no option: its
# tokens come from `block_step` passes over blocks (`diffusion(cfg)`), the
# prefill's logits pick nothing, and host swap, migration and prefix hits
# are refused or off (the decode carry holds a block; a hit would need a
# block-causal warm prefill)
BLOCK_DIFFUSION = "block_diffusion"
# The diffusion body's in-graph counters (serving/decode_loop.py counts
# them), under the names such a model lists in its `counter_names`: live
# slot-passes, blocks committed, and the positions fixed because their
# confidence cleared the threshold or because they ranked first where too
# few did.
DIFFUSION_COUNTERS = ("block_passes", "blocks_committed",
                      "tokens_fixed_by_threshold", "tokens_fixed_by_rank")


class CacheSpec(NamedTuple):
    """The per-layer cache state of one token, as the arena stores it:
    the block arena is `(layers, 1, num_blocks, heads, block_size,
    row_width)`, blocks on axis 2 (swap payloads and migration tickets
    index it) and heads on axis 3 (a mesh plan shards it). One spec is
    one GROUP of layers; `window` (None: every position is kept) makes
    the group a ring of `ring_pages` blocks a slot; `name` is how
    `engine.stats()` calls the group. `state=True`: the group holds a
    fixed-size state A SLOT and nothing a token (the module's STATE
    GROUPS): a block is `state_shape` values (None: the shape of a block
    of rows, `(heads, block_size, row_width)`) of `dtype` (None: the
    arena's type), one block a slot a layer, one column of the page
    table."""
    layers: int
    heads: int
    row_width: int
    window: Optional[int] = None
    name: str = "kv"
    state: bool = False
    dtype: Optional[str] = None
    state_shape: Optional[Tuple[int, ...]] = None

    def block_shape(self, block_size: int):
        """One block of one layer: rows a token, or a slot's state."""
        if self.state and self.state_shape is not None:
            return tuple(self.state_shape)
        return (self.heads, block_size, self.row_width)

    def arena_shape(self, num_blocks: int, block_size: int):
        return (self.layers, 1, num_blocks) + self.block_shape(block_size)


def ring_pages(window: int, block_size: int) -> int:
    """Blocks a slot holds of a window group: the window's own and the
    one being written."""
    return -(-int(window) // int(block_size)) + 1


class GroupLayout(NamedTuple):
    """Where a cache group's page row lies in the one page table:
    columns [start, start + pages)."""
    spec: CacheSpec
    start: int
    pages: int

    @property
    def columns(self) -> slice:
        return slice(self.start, self.start + self.pages)


def _layout(specs, max_pages: int, block_size: int):
    out, start = [], 0
    for spec in specs:
        if spec.state:
            pages = 1               # the slot's one block of state
        elif spec.window is None:
            pages = max_pages
        else:
            pages = min(max_pages, ring_pages(spec.window, block_size))
        out.append(GroupLayout(spec, start, pages))
        start += pages
    return tuple(out)


def _specs(model, cfg) -> Tuple[CacheSpec, ...]:
    """`model.cache_spec(cfg)` as a tuple: one spec is one group."""
    specs = model.cache_spec(cfg)
    return (specs,) if isinstance(specs, CacheSpec) else tuple(specs)


def cache_groups(model, cfg, max_len: int, block_size: int
                 ) -> Tuple[GroupLayout, ...]:
    """The model's cache groups laid out in one page table for slots of
    `max_len` positions: the primary group's `max_pages` columns first,
    then each further group's (a window group's `ring_pages`, never more
    than `max_pages`)."""
    specs = _specs(model, cfg)
    if specs[0].window is not None or specs[0].state:
        raise ValueError("the first cache group is the primary one and "
                         "keeps every position: list a window group or a "
                         "state group after it")
    return _layout(specs, -(-int(max_len) // int(block_size)), block_size)


def group_columns(specs, width: int, block_size: int
                  ) -> Tuple[slice, ...]:
    """Each group's columns in a page table of `width` columns, as
    `cache_groups` laid them out: what a model's programs, which are
    handed the table and not `max_len`, split it by."""
    for max_pages in range(1, width + 1):
        layout = _layout(specs, max_pages, block_size)
        if layout[-1].start + layout[-1].pages == width:
            return tuple(g.columns for g in layout)
    raise ValueError(f"no page table of these groups is {width} columns wide")


class ServingModel:
    """The interface. A model subclasses it and hands ONE instance to
    the engine through its config's `serving_model()`.

    Array contracts (S slots, P pages a slot, B a prefill bucket, V
    the vocabulary):
      prefill(params, cfg, tokens (1, B), pfx_len, real_len, arena,
              pages (P,), adapters=None, adapter_id=None
              [, arena_constraint=<the mesh plan's pin>: feature "mesh"])
          -> (logits (1, V) f32 of position pfx_len + real_len - 1,
              arena, counters)
          the real_len real tokens of a right-padded suffix at positions
          pfx_len.., whose first pfx_len positions are already cached.
          pfx_len is a multiple of the block size (a prefix hit; 0 for
          a cold prompt) unless the model declares the feature
          "prefill_chunk", which means "the start need not be
          page-aligned": the engine then runs a long suffix as several
          such calls, each starting where the last one stopped.
      decode_step(params, cfg, tokens (S,), arena, pt (S, P), ts (S,),
                  done (S,), *, adapters=None, adapter_ids=None,
                  arena_constraint=None)
          -> (logits (S, V) f32, arena, counters)
          every slot one position on: writes each live slot's cache row
          at position ts and attends over 0..ts. A slot whose `done` is
          set is frozen: its write must reach no block but scratch
          block 0 (its blocks may be another sequence's by now) and its
          logits are discarded. `arena_constraint` is the mesh plan's
          layout pin (feature "mesh"), which the loop has already
          applied; the step is handed it only to read off which
          attention it may run.
      verify(params, cfg, toks (S, k+1), arena, pt, ts, done, *,
             adapters, adapter_ids) -> (logits (S, k+1, V), arena)
          feature "speculation": the positions ts..ts+k of every slot
          in one pass, row j attending over 0..ts+j; writes past a
          slot's page row and a frozen slot's go to scratch.
      block_step(params, cfg, toks (S, Bk), arena, pt, ts, done)
          -> (logits (S, Bk, V) f32, arena, counters)
          feature "block_diffusion", Bk the block length: a PASS over
          every slot's current block. Writes the Bk rows' cache state
          at ts .. ts + Bk - 1 (ts a multiple of Bk, Bk divides the
          page) and EVERY row attends 0 .. ts + Bk - 1: `verify`'s
          contract with the causal mask taken out of the block. Row j's
          logits score the token AT ts + j. Every pass writes; the
          commit pass is simply the pass whose block holds no mask, so
          a row's last write is the final one. The model's `prefill`
          then writes the prompt's WHOLE blocks alone, block-causally,
          and returns None for its logits (they would pick nothing).
    With several cache groups (`cache_spec` a tuple) `arena` is the
    tuple of the groups' arenas, the primary first, and `pages` / `pt`
    hold every group's page row side by side at `cache_groups`' columns
    (a window group's a ring, read modulo its width); `pfx_len` is then
    always 0 (no prefix hits). A STATE group's column holds the block of
    its arena that is the slot's state: `prefill` writes that block whole
    in every layer of the group (the state at `real_len`, not at the
    bucket's end), `decode_step` reads and writes a live slot's once a
    layer and sends a frozen slot's write to scratch block 0.
    `counters` is None or a dict of small int32 arrays the program
    accumulated in-graph (a routed model's tokens per expert), with the
    names and shapes `counter_names` gives, from both programs alike;
    the loop sums a chunk's, the scheduler fetches them WITH the chunk's
    token block or the first token, adds them up on the host and
    `engine.stats()` reports them.
    """

    name = "model"
    features = frozenset()

    def max_positions(self, cfg) -> int:
        raise NotImplementedError

    def cache_spec(self, cfg):
        """One CacheSpec, or a tuple of them (cache groups, the primary
        first: the module's docstring)."""
        raise NotImplementedError

    def activation_dtype(self, params):
        """bfloat16 for a bfloat16 parameter tree, else float32: the
        full-precision arena's type and the type the kernels run in."""
        raise NotImplementedError

    def decode_attention_path(self, arena, arena_constraint=None) -> str:
        """Which attention `decode_step` runs on this arena, for
        `engine.stats()["decode_attention"]`."""
        raise NotImplementedError

    def prefill_attention_path(self, arena, bucket, arena_constraint=None):
        """Which attention `prefill` runs for a COLD prompt in a bucket
        of `bucket` rows ("flash" or "gather"), for
        `engine.stats()["prefill_attention"]`; None: the model does not
        say."""
        return None

    def counter_names(self, cfg):
        """{name: shape} of the counters the programs return."""
        return {}

    def diffusion(self, cfg):
        """None, or for a model that generates by diffusion over blocks
        (feature "block_diffusion") the loop's parameters as the MODEL
        has them: {"block_length", "denoising_steps",
        "confidence_threshold", "remasking", "mask_token_id"}."""
        return None

    def describe(self, cfg):
        """What `engine.stats()` says of the served config beside the
        model's name: a dict of plain values (the experts this chip
        holds of how many, its slice of the vocabulary)."""
        return {}

    def prefill(self, params, cfg, tokens, pfx_len, real_len, arena,
                pages, adapters=None, adapter_id=None):
        raise NotImplementedError

    def decode_step(self, params, cfg, tokens, arena, pt, ts, done, *,
                    adapters=None, adapter_ids=None, arena_constraint=None):
        raise NotImplementedError

    def verify(self, params, cfg, toks, arena, pt, ts, done, *,
               adapters=None, adapter_ids=None):
        """The speculative verify pass (feature "speculation")."""
        raise NotImplementedError

    def block_step(self, params, cfg, toks, arena, pt, ts, done):
        """The block pass (feature "block_diffusion")."""
        raise NotImplementedError

    def quantize_params(self, params, cfg):
        """Weight-only int8 (feature "int8_weights")."""
        raise NotImplementedError


def serving_model(cfg) -> ServingModel:
    """The model a config is served by."""
    named = getattr(cfg, "serving_model", None)
    if named is not None:
        return named()
    from ..models.gpt_decode import GPT_SERVING_MODEL
    return GPT_SERVING_MODEL


# What each option would need of a model with a STATE group, which no
# model has written: the state group is built, its snapshots are not.
_STATE_LACKS = {
    "int8_weights": "the recurrence's projections have no int8 path",
    "int8_kv": "a state block is float32 values, not rows with a scale "
               "plane",
    "adapters": "the recurrent mixer's projections take no LoRA pair",
    "speculation": "a rejected draft needs the slot's state as it was "
                   "before the verify pass, and no snapshot is kept",
    "mesh": "a state block's heads are not sharded and the recurrence is "
            "one chip's program",
    "prefill_chunk": "a later chunk needs the state and the convolution's "
                     "history carried in from the chunk before; the "
                     "prefill writes them and never reads them",
    "preempt": "a swap payload carries the primary group's blocks; no "
               "snapshot of a slot's state is taken",
}


def state_groups(model: ServingModel, cfg) -> Tuple[CacheSpec, ...]:
    """The model's STATE groups (none for most models)."""
    return tuple(spec for spec in _specs(model, cfg) if spec.state)


def require_features(model: ServingModel, serving, cfg=None) -> None:
    """Refuse, at engine construction, every ServingConfig option that
    asks for a feature `model` does not declare: an engine must never
    serve base-model tokens, read quantized rows as values or run one
    chip's program on a mesh because a model lacked the path. With `cfg`,
    a model whose `cache_spec` has a STATE group is refused each such
    option, and host swap (`preempt`), with what the state group lacks
    for it, whatever the model declares."""
    asked = {
        "int8_weights": (serving.weight_dtype == "int8",
                         "weight_dtype='int8'"),
        "int8_kv": (serving.kv_dtype == "int8", "kv_dtype='int8'"),
        "adapters": (serving.max_adapters is not None, "max_adapters"),
        "speculation": (serving.speculate_k > 0,
                        "speculate_k > 0 (the verify pass)"),
        "mesh": (serving.mesh_shape is not None, "mesh_shape"),
        "prefill_chunk": (serving.prefill_chunk is not None,
                          "prefill_chunk"),
    }
    missing = [f"{option} needs {feature!r}"
               for feature, (on, option) in asked.items()
               if on and feature not in model.features]
    held = state_groups(model, cfg) if cfg is not None else ()
    if held:
        asked["preempt"] = (serving.preempt, "preempt=True")
        missing = [f"{option} with the state group {held[0].name!r}: "
                   f"{_STATE_LACKS[feature]}"
                   for feature, (on, option) in asked.items() if on]
    if BLOCK_DIFFUSION in model.features and serving.preempt:
        missing.append("preempt=True parks a slot's carry rows, not its "
                       "block: a block-diffusion model is not swapped")
    if missing:
        raise ValueError(
            f"the serving model {model.name!r} does not implement: "
            + "; ".join(missing)
            + f" (it declares {sorted(model.features)}) — refusing at "
            "construction rather than serving through a path that "
            "ignores the option")
