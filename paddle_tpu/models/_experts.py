"""The routed + shared expert layer of every served block that has one,
its in-graph counters, and the half of a serving class that every such
block repeats.

NINE CELLS run this file (`moe_time_share` 45-69% of their busy time in
the first five: PERF.md, section 5): moonlight-longctx-offline,
xing-longdoc-offline (models/moonlight.py), mellum-mixedlen-offline
(models/mellum.py), command-a-reason-offline (models/command_a.py),
sdar-blockgen-offline (models/sdar.py), kimi-linear-longgen-offline
(models/kimi_linear.py), longcat-flash-agentgen-offline
(models/longcat_flash.py), granite-h-shortchat-offline
(models/granite_hybrid.py) and qwen3-next-longmix-offline
(models/qwen3_next.py). A change here is a change to all nine.

THE LAYER (`moe`). Every token goes to `experts_per_tok` of the router's
outputs (`route`, by one of THREE rules): `n_routed_experts` SwiGLU
experts and, where the config has them, `zero_expert_num` IDENTITY
experts behind them (a pick of one costs nothing: no row is laid out, no
tile computed, its weight times the layer's own input is added in
`moe/identity`), plus the shared experts, if any. The product is
GROUPED over the rows that were routed (no capacity,
none dropped, never every expert on every token), and those rows are LAID
OUT ONCE, by counting (ops/grouped_swiglu.routed_positions; no sort):
`moe/dispatch` gathers them there, `moe/experts` computes whole tiles of
ONE expert (`grouped_experts`: one kernel a layer on a TPU, ragged
products elsewhere), `moe/combine` reads the products back by the same
positions, sums them in float32 in pick order, adds the shared experts'
term and rounds once: ONE sum with two carriers (`combine_path`).
`expert_product_path` is the ONE place the layer asks for the backend.

WHAT THE LAYER READS, all of it; the defaults are here and nowhere else.
Of a config: `n_routed_experts`, `experts_per_tok`, `n_shared_experts` (0:
no shared expert, none traced), required; `router_scoring`, "sigmoid" (the
default) or "softmax"; `router_renormalize`, True (the picks' weights
over their sum); `routed_scaling_factor`, 1.0; `zero_expert_num`, 0 (the
router's outputs past `n_routed_experts`: identity experts, held by
every chip alike); `shared_expert_combination`, "sum" (the default),
"average" (the
shared experts, stored as ONE SwiGLU n times as wide, over their count) or
"token_gate" (the shared term weighed by the TOKEN, `sigmoid(x .
shared_token_gate)` in float32, the layer's vector (h,));
`experts_held`, None (all) or (first, count), the routed experts this chip
holds (a layer that holds a SHARE of the router's outputs reads its
products back pick by pick and adds a pick of an expert held elsewhere,
or of an identity expert, as 0 by a select: `_weighted_sum`);
`rms_eps`, `ffn`'s norm alone. Of a layer's parameters `lp`:
`router` (h, E) (its presence makes the layer a routed one), `router_bias`
(E,) float32 where the picks are ranked with a correction bias, `w_gate`,
`w_up` (held, h, F), `w_down` (held, F, h), with shared experts
`shared_gate`, `shared_up` (h, Fs), `shared_down` (Fs, h), under
"token_gate" `shared_token_gate` (h,); `ffn` also
`norm2` and a dense layer's `gate`, `up`, `down`.

Scopes: `moe/router`, `moe/dispatch`, `moe/experts`, `moe/shared`,
`moe/identity`, `moe/combine`, `ffn/dense`. Counters: `zero_counters`
under the layer's
names, `counter_names` / `counters` under the engine's.

Imports no model and, at module level, no jax.
"""

from __future__ import annotations

from ..serving.model import ServingModel
from . import _decoder

__all__ = ["route", "held_experts", "checked_share", "router_width",
           "expert_product_path", "grouped_experts",
           "combine_path", "moe", "experts", "ffn", "swiglu", "swiglu_hidden",
           "zero_counters", "counter_names", "counters", "ExpertBlockModel",
           "COMBINE_KERNEL_FROM", "COMBINE_KERNEL_PICKS",
           "COMBINE_KERNEL_WIDE", "HELD_SLACK", "HELD_SPLIT_FROM"]

_LANES = 128


def swiglu_hidden(x, gate, up):
    import jax
    g = x @ gate
    return jax.nn.silu(g) * (x @ up)


def swiglu(x, gate, up, down):
    return swiglu_hidden(x, gate, up) @ down


def route(cfg, lp, x):
    """The router. x (T, h) -> (picks (T, k) int32, weights (T, k)
    float32), by the config's keys; the scores are float32. THREE rules
    are served, and a key each, not a model's name, tells them apart:
      * "sigmoid" scoring (the default): scores sigmoid(x W_g); the k
        largest of score + correction bias are picked (one group, so no
        group stage; a layer without `router_bias` has no bias: the
        scores themselves are ranked); the weights are the scores
        WITHOUT the bias at the picks, over their sum + 1e-20, times
        `routed_scaling_factor`;
      * "softmax" scoring: scores softmax(x W_g) over the router's
        outputs, the k largest picked and their scores divided by their
        sum (no `router_bias` in the layer, no factor in the config);
      * "softmax" with `router_bias` in the layer, `router_renormalize`
        False and a factor: the bias RANKS (the k largest of score +
        bias) and does not weigh (the weights are the scores at the
        picks), nothing is renormalised, and the weights are times
        `routed_scaling_factor`.
    The router is as wide as the MODEL has outputs (`router_width`),
    whichever of its experts this chip holds (`held_experts`)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    softmax = getattr(cfg, "router_scoring", "sigmoid") == "softmax"
    scores = jax.nn.softmax(logits, -1) if softmax else jax.nn.sigmoid(logits)
    if "router_bias" in lp:
        _, picks = jax.lax.top_k(
            scores + lp["router_bias"].astype(jnp.float32),
            cfg.experts_per_tok)
        w = jnp.take_along_axis(scores, picks, -1)
    else:
        w, picks = jax.lax.top_k(scores, cfg.experts_per_tok)
    if getattr(cfg, "router_renormalize", True):
        total = w.sum(-1, keepdims=True)
        w = w / (total if softmax else total + 1e-20)
    factor = getattr(cfg, "routed_scaling_factor", 1.0)
    if factor != 1.0:
        w = w * factor
    return picks.astype(jnp.int32), w


def held_experts(cfg):
    """(first, count): the routed experts this chip holds, ids first ..
    first + count - 1 of `cfg.n_routed_experts`. The chip's share
    of an expert-parallel deployment: the router scores every output of
    the model, the layer lays out and computes the picks that fall on
    its own, and what the experts held elsewhere would add is left out
    (no code stands in for the other chips or their exchange). An
    IDENTITY pick (an output from `n_routed_experts` on, of
    `zero_expert_num`) is nobody's share: it holds no weight, is applied
    where the token lives and is added by every chip alike."""
    held = getattr(cfg, "experts_held", None)
    return (0, cfg.n_routed_experts) if held is None else tuple(held)


def checked_share(experts_held, n_routed_experts, vocab_slice, vocab_size):
    """A config's two keys that name a chip's SHARE, checked and in their
    stored form: `experts_held` None (all) or (first, count) among
    `n_routed_experts`; `vocab_slice` None (the whole vocabulary) or
    (first, rows, of) with `rows` the `vocab_size` held."""
    if experts_held is not None:
        first, count = experts_held
        if not (0 <= first and 0 < count
                and first + count <= n_routed_experts):
            raise ValueError(f"experts_held {experts_held!r} are not "
                             f"experts of {n_routed_experts}")
        experts_held = (int(first), int(count))
    if vocab_slice is None:
        vocab_slice = (0, vocab_size, vocab_size)
    if vocab_slice[1] != vocab_size or sum(vocab_slice[:2]) > vocab_slice[2]:
        raise ValueError(f"vocab_slice {vocab_slice!r} (first, rows, of) "
                         f"does not name {vocab_size} rows of a "
                         "vocabulary")
    return experts_held, tuple(int(n) for n in vocab_slice)


def router_width(cfg):
    """Outputs the router scores: the model's routed experts and, behind
    them, its identity experts (`zero_expert_num`, 0 where a config has
    none)."""
    return cfg.n_routed_experts + getattr(cfg, "zero_expert_num", 0)


def expert_product_path(lp):
    """ "grouped_swiglu_kernel" on a TPU for lane-aligned widths;
    "ragged_dot" elsewhere (the CPU)."""
    import jax
    _, h, F = lp["w_gate"].shape
    if h % _LANES == 0 and F % _LANES == 0 \
            and jax.default_backend() == "tpu":
        return "grouped_swiglu_kernel"
    return "ragged_dot"


def grouped_experts(lp, xs, group_sizes, tile, packed=False):
    """The grouped SwiGLU over the routed rows alone: xs (R, h) in the
    layout of ops/grouped_swiglu.routed_positions (sorted by expert,
    every group from a whole tile of `tile` rows on), group_sizes (E,)
    how many rows each expert has. A row between a group's end and its
    tile's is computed for nobody; the tiles past the last group's are
    not to be read. On a TPU one kernel (ops/grouped_swiglu; `packed`,
    the kernel's alone: its rows as `combine_path`'s kernel reads them);
    elsewhere three ragged products over the groups rounded up to the
    tile."""
    import jax
    if expert_product_path(lp) == "grouped_swiglu_kernel":
        from ..ops.grouped_swiglu import grouped_swiglu
        return grouped_swiglu(xs, lp["w_gate"], lp["w_up"], lp["w_down"],
                              group_sizes, tile, packed)
    whole = -(-group_sizes // tile) * tile
    g = jax.lax.ragged_dot(xs, lp["w_gate"], whole)
    u = jax.lax.ragged_dot(xs, lp["w_up"], whole)
    return jax.lax.ragged_dot(jax.nn.silu(g) * u, lp["w_down"], whole)


# From this many bytes of routed products on, a prompt's combine is the
# kernel ops/routed_combine (a row a DMA: 10-20 ns a (token, pick)
# whatever the row's width); below it XLA's gather reads a row in 8-15 ns
# (Mellum's 512 and 1,024 buckets: 36 and 72 MiB of products) or the whole
# sum costs less than a Pallas call (a decode step: 4-8 MiB, 1-8 us), and
# from it on 34-80 ns (command-a's 4,096 bucket, 96 MiB, is the smallest
# that is slow: PERF.md, PR 39).
COMBINE_KERNEL_FROM = 84 << 20
# WHERE THE KERNEL HAS BEEN HELD ON THE CHIP: at most this many picks a token
# at any served width (4 of 3,584, 6 and 8 of 2,048, 8 of 2,304 and of
# 4,096), and more picks from this width on (granite's 10 of 4,096).
# OUTSIDE it, at 10 picks of 2,048 (PR 56: a prompt of 8,192 tokens, a held
# buffer of 36,864 rows, the picks' sum in float32), a prefill through the
# kernel left the chip in a state in which the decode chunk behind it
# halted the core or hung; the same prefills through XLA's gather serve
# every bucket (`tools/`-free probes, PERF.md section 6). NOT UNDERSTOOD:
# the kernel runs without Mosaic's range checks, its arithmetic is generic
# in k and in the row's width, and interpreted it is right. Until it is,
# the shape is not sent to it (ROADMAP S18 a).
COMBINE_KERNEL_PICKS = 8
COMBINE_KERNEL_WIDE = 4096


def combine_path(lp, x, rows, picks):
    """ "row_dma_kernel" where the expert product is the kernel, the rows
    are bfloat16 of whole 256 lanes, `rows` of them (static) make
    COMBINE_KERNEL_FROM bytes and `picks` a token (static) at this width
    lie where the kernel has been held on the chip; "gather" elsewhere
    (the CPU, a decode step, a short prompt, more picks of a narrow
    row)."""
    import jax.numpy as jnp
    h = x.shape[1]
    if expert_product_path(lp) == "grouped_swiglu_kernel" \
            and x.dtype == jnp.bfloat16 and h % (2 * _LANES) == 0 \
            and rows * h * x.dtype.itemsize >= COMBINE_KERNEL_FROM \
            and (picks <= COMBINE_KERNEL_PICKS or h >= COMBINE_KERNEL_WIDE):
        return "row_dma_kernel"
    return "gather"


# A layer that holds `count` of the router's E outputs (`router_width`:
# the identity experts count among them and are never held) gets T * k *
# count / E picks on average and T * k at the worst. Its routed buffer is
# sized for
# HELD_SLACK times the average (a SECOND STATIC SIZE beside the worst
# case's), so that dispatch's gather, the kernel's grid and the buffer
# combine reads out of follow the picks that are held (XLA's combine
# still makes T * k row reads, a clipped one for a pick held elsewhere;
# the kernel's fetches the held ones alone); a pass
# whose held picks do not fit there (their groups, each rounded up to
# the tile) takes the other branch of a `lax.cond`, the same code over
# the tokens in E / (count * HELD_SLACK) parts (the power of two at or
# below it: a bucket's rows divide by it), each of which fits whatever
# its routing: no pick is ever dropped. Below HELD_SPLIT_FROM
# picks (a decode step) the worst case is a few hundred rows and the one
# buffer holds it.
HELD_SLACK = 2
HELD_SPLIT_FROM = 4096


def _lay_out(lp, x, picks, live, groups, tile, slots, average, packed):
    """Dispatch and the experts' product over a buffer for `slots` picks
    (static): picks (T, k) as `routed_positions` takes them, `live` (T,)
    by token or (T, k) by pick, at most `slots` of them live; `average`
    (static) how many are expected (T * k where every expert is held),
    which says whether dispatch places or gathers. Returns (ys, the
    buffer's rows through their experts, `packed` (static) for the
    combine kernel; pos (T, k); group_sizes (groups,))."""
    import jax
    import jax.numpy as jnp
    from ..ops.grouped_swiglu import padded_rows, routed_positions
    T, k = picks.shape
    with jax.named_scope("moe/dispatch"):
        # a pick that is not live has no position: in no group, never
        # moved, never computed
        pos, group_sizes = routed_positions(picks, live, groups, tile)
        at = pos.reshape(-1)
        token = jnp.arange(T * k, dtype=jnp.int32) // k
        rows = padded_rows(slots, groups, tile)
        if 4 * average <= rows:
            # a step's few rows in a buffer that is mostly the experts'
            # round-ups: the rows are PLACED (a gather fetches every row
            # of the buffer, ~15 ns a row whoever's it is: PERF.md, PR 37)
            xs = jnp.zeros((rows, x.shape[1]), x.dtype).at[at].set(
                x[token], mode="drop", unique_indices=True)
        else:
            # whose row each row of the buffer is (nobody's: token 0's,
            # for nobody): the scatter moves T * k integers, the gather
            # the rows
            source = jnp.zeros((rows,), jnp.int32).at[at].set(
                token, mode="drop", unique_indices=True)
            xs = x[source]
    with jax.named_scope("moe/experts"):
        ys = grouped_experts(lp, xs, group_sizes, tile, packed)
    return ys, pos, group_sizes


def _weighted_sum(ys, pos, w, live, share=False):
    """XLA's sum of `_combine`: pick by pick, (k, T, h) in
    the weights' type (token-major it would be re-laid for k = 4 and 6),
    then ONE multiply-and-sum over the picks in float32, in pick order;
    a dead token's sum is zeroed here. `share` (static, `moe`'s: the
    layer holds a share of the experts) says what a dead pick of a live
    token is. Without it there is none, and the products come back in ONE
    gather of k T rows. With it a pick of an expert held elsewhere has a
    `pos` past the buffer, which the clipped gather reads from the
    buffer's last row: a row of a tile no expert owns, never written,
    that held NaN on the chip where float32 state had lain (PR 45), and
    0 x NaN is NaN; so such a pick adds 0 by a SELECT, and the products
    come back in k gathers of T rows (the TPU's compiler refused the one
    gather of 1,024 x 8 and of 4,096 x 8 rows of 2,304 values behind a
    held layer's buffer, 70-106 MB of scoped VMEM: PR 45; the sum is the
    same to the bit). Returns (T, h) float32."""
    import jax.numpy as jnp
    T, k = pos.shape
    if share:
        back = [ys.at[pos[:, j]].get(mode="clip") for j in range(k)]
    else:
        back = ys.at[pos.T.reshape(-1)].get(mode="clip").reshape(k, T, -1)

    def term(j):
        t = back[j].astype(jnp.float32) * w[:, j, None]
        return jnp.where(pos[:, j, None] < ys.shape[0], t, 0) if share else t

    y = term(0)
    for j in range(1, k):
        y = y + term(j)
    return jnp.where(live[:, None], y, 0)


def _combine(ys, pos, w, live, shared, scale, dtype, by_dma, share=False):
    """`moe/combine`'s whole sum: every token's routed products times
    their weights, summed in float32 in pick order, a dead token's sum
    0; plus `shared` (T, h) in float32 (None: no shared expert), times
    `scale` where that is not None; ONE rounding to `dtype`. `by_dma`
    (static, `combine_path`'s) says how `_lay_out` left `ys` and who
    reads it: the buffer's rows (R, h), gathered by XLA
    (`_weighted_sum`, told whether the layer holds a `share`), or the
    kernel's packed rows, fetched by the kernel ops/routed_combine, a DMA
    a row that is someone's (a dead token has no position), the shared
    term and the rounding inside it."""
    if by_dma:
        from ..ops.routed_combine import routed_combine
        return routed_combine(ys, pos, w, shared,
                              1.0 if scale is None else scale, dtype)
    return _sum_end(_weighted_sum(ys, pos, w, live, share), shared, scale,
                    dtype)


def _sum_end(y, shared, scale, dtype):
    """The end of XLA's sum: y (T, h) float32 plus `shared` in float32
    (None: none), times `scale` where that is not None, ONE rounding."""
    import jax.numpy as jnp
    if shared is not None:
        shared = shared.astype(jnp.float32)
        if scale is not None:
            shared = shared * scale
        y = y + shared
    return y.astype(dtype)


def moe(cfg, lp, x, live):
    """The expert layer's feed-forward on tokens x (T, h), for every
    config and layer the module's docstring describes. `live` (T,)
    bool: rows that are real (a prefill's padding and a frozen slot's
    ride-along are not: they get no expert and do not count).
    Returns (y (T, h), counters: one pass's, `zero_counters`' names)."""
    import jax
    import jax.numpy as jnp
    from ..ops.grouped_swiglu import padded_rows, row_tile_for
    T, k, E = x.shape[0], cfg.experts_per_tok, router_width(cfg)
    first, held = held_experts(cfg)
    tile = row_tile_for(T * k, E)
    with jax.named_scope("moe/router"):
        picks, w = route(cfg, lp, x)
    identity = None
    if E > cfg.n_routed_experts:
        with jax.named_scope("moe/identity"):
            # an identity expert returns its input: the picks' weights
            # times the layer's own input, in float32, added at the sum's end
            free = picks >= cfg.n_routed_experts
            identity = jnp.sum(jnp.where(free, w, 0), -1, keepdims=True) \
                * x.astype(jnp.float32)
    mine, slots, average, parts = live, T * k, T * k, 1
    if held < E:
        picks = picks - first
        mine = live[:, None] & (picks >= 0) & (picks < held)
        # a pick of an expert held elsewhere: its weight divided the sum
        # and multiplies nothing here
        w = jnp.where(mine, w, 0)
        average = -(-T * k * held // E)
        parts = max(1, E // (held * HELD_SLACK))
        parts = 1 << (parts.bit_length() - 1)
        if T * k < HELD_SPLIT_FROM or T % parts:
            parts = 1
    if parts > 1:
        slots = T * k // parts
    by_dma = combine_path(
        lp, x, padded_rows(slots, held, tile), k) == "row_dma_kernel"
    if parts == 1:
        ys, pos, group_sizes = _lay_out(lp, x, picks, mine, held, tile,
                                        slots, average, by_dma)
    else:
        def routed(x, picks, mine, w, live):
            ys, pos, sizes = _lay_out(lp, x, picks, mine, held, tile, slots,
                                      average, by_dma)
            # the picks' sum alone, in float32: the shared term and the
            # rounding come behind the `lax.cond`, where XLA's sum has them
            with jax.named_scope("moe/combine"):
                return _combine(ys, pos, w, live, None, None, jnp.float32,
                                by_dma, held < E), sizes

        def in_parts(*whole):
            # the barrier keeps a part's sum out of the fusion that stacks
            # the parts: fused, XLA wants the whole stack in the combine
            # kernel's scoped VMEM (34 MB of it at 4,096 tokens)
            def one(part):
                done = routed(*part)
                return jax.lax.optimization_barrier(done) if by_dma else done

            ys, sizes = jax.lax.map(one, tuple(
                a.reshape(parts, T // parts, *a.shape[1:]) for a in whole))
            return ys.reshape(T, -1), jnp.sum(sizes, 0)

        sizes = jnp.sum(mine[:, :, None] & (
            picks[:, :, None] == jnp.arange(held, dtype=jnp.int32)),
            (0, 1), dtype=jnp.int32)
        fits = jnp.sum(-(-sizes // tile)) * tile <= \
            padded_rows(slots, held, tile)
        y, group_sizes = jax.lax.cond(fits, routed, in_parts,
                                      x, picks, mine, w, live)
    shared, scale = None, None
    if cfg.n_shared_experts:
        with jax.named_scope("moe/shared"):
            hidden = swiglu_hidden(x, lp["shared_gate"], lp["shared_up"])
            if by_dma and parts == 1:
                # the shared experts' last product BEHIND the routed
                # kernel, where XLA's own order has it when its fusion
                # reads it: free of the kernel's output the scheduler
                # finished them first and kept their (T, h) beside the
                # routed rows and their products, the layer's peak (80
                # MiB more at Xing's 16k bucket)
                hidden, ys = jax.lax.optimization_barrier((hidden, ys))
            shared = hidden @ lp["shared_down"]
            combination = getattr(cfg, "shared_expert_combination", "sum")
            if combination == "token_gate":
                # a weight a token, in float32, on the term the sum's end
                # adds in float32
                gate = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32),
                    lp["shared_token_gate"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
                shared = shared.astype(jnp.float32) * gate[:, None]
        if combination == "average":
            scale = 1.0 / cfg.n_shared_experts
    if identity is not None:
        # the identity picks' term rides where the shared experts' does
        # (float32, added once at the sum's end)
        if shared is not None:
            shared = shared.astype(jnp.float32)
            identity = identity + (shared if scale is None else shared * scale)
        shared, scale = identity, None
    with jax.named_scope("moe/combine"):
        if parts == 1:
            y = _combine(ys, pos, w, live, shared, scale, x.dtype, by_dma,
                         held < E)
        else:
            y = _sum_end(y, shared, scale, x.dtype)
    passes = jnp.any(live).astype(jnp.int32)
    zero = jnp.zeros_like(passes)
    kernel = expert_product_path(lp) == "grouped_swiglu_kernel"
    counters = {"expert_tokens": group_sizes,
                "router_tokens": jnp.sum(live).astype(jnp.int32),
                "experts_touched": jnp.sum(group_sizes > 0).astype(jnp.int32),
                "moe_passes": passes,
                "kernel_passes": passes if kernel else zero,
                # rows the kernel computed: its visits' whole tiles
                "rows_computed": jnp.sum(-(-group_sizes // tile)) * tile
                if kernel else zero,
                "combine_kernel_passes": passes if by_dma else zero}
    if identity is not None:
        # of the live tokens' k picks: identity experts', real experts'
        # (held here or not) and the held ones'; and the tokens by how many
        # of their picks were real experts (0..k: the products a token
        # costs the deployment)
        real = jnp.where(live, k - jnp.sum(free, -1, dtype=jnp.int32), -1)
        n_real = jnp.sum(jnp.maximum(real, 0))
        counters.update(
            moe_identity_picks=counters["router_tokens"] * k - n_real,
            moe_expert_picks=n_real,
            moe_held_picks=jnp.sum(group_sizes).astype(jnp.int32),
            moe_real_picks_hist=jnp.sum(
                real[:, None] == jnp.arange(k + 1, dtype=jnp.int32), 0,
                dtype=jnp.int32))
    return y, counters


def experts(cfg, lp, x, live, counters):
    """`moe` on tokens x that are normed already, its counters added to
    `counters`. Returns (y, counters)."""
    y, c = moe(cfg, lp, x, live)
    return y, dict(counters, **{name: counters[name] + c[name]
                                for name in c})


def ffn(cfg, lp, x, live, counters):
    """norm2 + the layer's feed-forward (dense or routed), with the
    counters of a routed layer added to `counters`: the second sublayer
    of a pre-norm block. Returns (y, counters, None)."""
    import jax
    h = _decoder.rms(x, lp["norm2"], cfg.rms_eps)
    if "router" not in lp:
        with jax.named_scope("ffn/dense"):
            return swiglu(h, lp["gate"], lp["up"], lp["down"]), counters, None
    return experts(cfg, lp, h, live, counters) + (None,)


# -- the counters, and the serving class's half that is every block's -----------

# The layer's counter -> the engine's name for it: by both programs since
# start, and the three the engine reports of the decode step alone.
# expert_tokens[e]: rows routed to held expert e; router_tokens: tokens
# routed (each layer counts); decode_*: tokens routed, experts that had a
# row, passes of an expert layer with a live slot (what a step's expert
# bytes are counted from); moe_kernel_passes: passes whose product was the
# grouped kernel (0 off the TPU), moe_rows_computed: the rows those computed
# (sum(expert_tokens) over it is the share that were someone's),
# moe_combine_kernel_passes: those whose combine was ops/routed_combine.
_BOTH = {"expert_tokens": "expert_tokens", "router_tokens": "router_tokens",
         "kernel_passes": "moe_kernel_passes",
         "rows_computed": "moe_rows_computed",
         "combine_kernel_passes": "moe_combine_kernel_passes"}
_DECODE = {"router_tokens": "decode_router_tokens",
           "experts_touched": "decode_experts_touched",
           "moe_passes": "decode_moe_passes"}
# A layer with identity experts (`zero_expert_num`) also counts, under the
# engine's own names, by both programs since start: moe_identity_picks,
# moe_expert_picks (picks of real experts, held here or not),
# moe_held_picks (those that fell on an expert held here) and
# moe_real_picks_hist[n]: live tokens n of whose k picks were real experts
# (each layer counts; it sums to router_tokens).
_IDENTITY = ("moe_identity_picks", "moe_expert_picks", "moe_held_picks")


def _identity_counters(cfg):
    """{name: shape} of the counters a layer with identity experts adds."""
    if router_width(cfg) == cfg.n_routed_experts:
        return {}
    return dict({name: () for name in _IDENTITY},
                moe_real_picks_hist=(cfg.experts_per_tok + 1,))


def zero_counters(cfg):
    """One pass's counters at zero, under the layer's own names."""
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    return dict({name: zero for name in (*_BOTH, *_DECODE)},
                expert_tokens=jnp.zeros((held_experts(cfg)[1],), jnp.int32),
                **{name: jnp.zeros(shape, jnp.int32)
                   for name, shape in _identity_counters(cfg).items()})


def counter_names(cfg):
    """{name: shape} of the layer's counters as the engine reports them."""
    return dict({name: () for name in (*_BOTH.values(), *_DECODE.values())},
                expert_tokens=(held_experts(cfg)[1],),
                **_identity_counters(cfg))


def counters(c, decode):
    """A program's counters `c` under the engine's names: the layer's
    renamed, whatever else the block counted (`hc_*`, `decode_rows_*`) as
    it is."""
    import jax.numpy as jnp
    zero = jnp.zeros((), jnp.int32)
    out = {name: c[name] for name in c
           if name not in _BOTH and name not in _DECODE}
    out.update({new: c[old] for old, new in _BOTH.items()})
    out.update({new: c[old] if decode else zero
                for old, new in _DECODE.items()})
    return out


class ExpertBlockModel(ServingModel):
    """What the serving class of every block with this layer repeats: an
    instance a served name; the two programs' wrappers around the block's
    `prefill_pages` and `decode_step_pages` (the subclass's static
    methods); the counters under the engine's names, every one of
    `counter_names` read from the program's own (one it lost is a KeyError
    when the program is traced) but a `decode_*` one from a prefill: those
    are the decode step's alone, zero there. `own_counters`: the scalars a
    block adds to them, at zero where its `_counters` supplies none (the
    engine's loop fills SDAR's)."""
    own_counters = ()

    def __init__(self, name):
        self.name = name

    def max_positions(self, cfg):
        return cfg.max_pos

    def activation_dtype(self, params):
        return _decoder.act_dtype(params)

    def counter_names(self, cfg):
        return dict(counter_names(cfg),
                    **{name: () for name in self.own_counters})

    def _counters(self, cfg, c, decode):
        import jax.numpy as jnp
        zero = jnp.zeros((), jnp.int32)
        out = dict(dict.fromkeys(self.own_counters, zero),
                   **counters(c, decode))
        return {name: out[name] if decode or not name.startswith("decode_")
                else zero for name in self.counter_names(cfg)}

    def prefill(self, params, cfg, tokens, pfx_len, real_len, arena, pages,
                adapters=None, adapter_id=None):
        logits, arena, c = self.prefill_pages(
            params, cfg, tokens, pfx_len, real_len, arena, pages)
        return logits, arena, self._counters(cfg, c, decode=False)

    def decode_step(self, params, cfg, tokens, arena, pt, ts, done, *,
                    adapters=None, adapter_ids=None, arena_constraint=None):
        logits, arena, c = self.decode_step_pages(
            params, cfg, tokens, arena, pt, ts, done,
            attention=self.decode_attention_path(arena, arena_constraint))
        return logits, arena, self._counters(cfg, c, decode=True)
