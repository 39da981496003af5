"""SDAR-30B-A3B-Chat as its published config.json describes it (`model_type: sdar_moe`,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat) and as its family's
`block_diffusion_generate` generates with it. Plain jax.numpy in float32 at the highest
matmul precision: no cache, no kernel, the experts in a Python loop. It shares no code
with paddle_tpu and imports nothing from it; only the parameter tree's layout is the
served one, so that the same weights can be given to both (`x @ W`, W is (in, out)):

  {"wte": (V, h), "head": (h, V), "norm_f": (h,),
   "layers": [{"norm1", "norm2": (h,), "q_norm", "k_norm": (d,), "wq": (h, n*d), "wk",
               "wv": (h, n_kv*d), "wo": (n*d, h), "router": (h, E), "w_gate", "w_up":
               (E, h, F), "w_down": (E, F, h)}]}

The layer, with x^ = RMSNorm(x) (eps `rms_norm_eps`, no bias anywhere). [config]: settled
by a key of the config; [assumed]: not, and listed under `assumed` in
benchmarks/configs/sdar-30b-a3b-chat.json.
  q = x^ W_q: `num_attention_heads` heads of `head_dim`; k = x^ W_k, v = x^ W_v:
  `num_key_value_heads` heads; query head i reads KV head i // (n / n_kv) [config].
  q and k each RMS-normed over the `head_dim` values of a head, a learned scale of
  `head_dim` values [assumed: Qwen3's rule]; then rotary on all `head_dim` values,
  rotate_half with pairs in halves, theta `rope_theta`, plain [config: rope_scaling null].
  Scores q . k / sqrt(head_dim) under the BLOCK-CAUSAL mask M(i, j) = [j // B <= i // B],
  B = `generation.block_length` [assumed]; softmax, o = sum p v, x = x + concat(o) W_o.
  Every layer is sparse [config: decoder_sparse_step 1, mlp_only_layers []; the dense
  intermediate_size is used by no layer]: p = softmax(x^ W_r) over `num_experts` in
  float32, the `num_experts_per_tok` largest, their probabilities over their sum [config:
  norm_topk_prob]; x = x + sum_k w_k Expert_k(x^), an expert a SwiGLU of width
  `moe_intermediate_size`. No shared expert [config: no key].
  Final RMSNorm, logits = y W_head (untied) [config]. Row i scores the token AT i: the
  logits are not shifted [assumed].

Generation [assumed: the family's published loop as the -Chat checkpoints use it]. The
sequence is cut in blocks of B from position 0; the prompt's whole blocks are context,
its last p mod B tokens open the first generated block, fixed. A block starts as those
and the mask token elsewhere. Pass s = 0, 1, ..: if the block holds no mask it is
committed; otherwise, from the pass's logits at each masked position j, with the mask
token's own logit at -inf [assumed]: x0_j the arg-max (greedy), c_j its softmax
probability; with n = B / denoising_steps: if at least n masked positions have c_j >
threshold, all of those are fixed, else the n of largest c_j.

Departures, none of which changes a value: each expert is applied to EVERY token and
weighted by its routing weight, zero where it was not picked (the same sum); a sequence
is held in blocks of BLOCK rows and computed a layer, a block, a head and an expert at a
time. `replay` runs every pass of every served block as EXTRA ROWS of one forward pass
beside the final sequence, each pass's B rows under a mask that shows them the final
sequence before their block and themselves: under the block-causal mask the rows before
a block do not depend on it, so the logits are those of running that pass on its own
(`generate`, which does, is checked against it in the tests).

WRONG references (`wrong=`), for showing that the cell's verdict tells them from the
true one; each is another program's answer along the SAME served blocks:
  "float8": every matrix rounded to float8_e4m3 (the precision below bfloat16);
  "causal": a block attended causally (j <= i), as every other served model's;
  "no_commit": the commit pass left out: a block's last-fixed positions are cached as the
      mask token's rows (the last denoising pass's), so every later block attends those;
  "left_to_right": the positions of a block fixed left to right, not by confidence;
  "block8": a block length of 8: a row attends its block of 8 whole."""

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 2048
WRONG = ("float8", "causal", "no_commit", "left_to_right", "block8")
MASKED, PROMPT = -2, -1


def generation(cfg):
    """The generation parameters of the configuration file (all assumed)."""
    return cfg["assumed"]["generation"]


def _wide(w, float8):
    """A weight in float32, through float8_e4m3 first for the wrong reference."""
    w = jnp.asarray(w)
    if float8:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (T, heads, d) at integer positions pos (T,), plain frequencies."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = pos.astype(F32)[:, None, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _qkv(x, pos, lp, c, float8):
    """One block of rows' normed, rotated queries (R, n, d) and keys, and values."""
    R, d, eps = x.shape[0], c["head_dim"], c["rms_norm_eps"]
    xh = _rms_norm(x, jnp.asarray(lp["norm1"], F32), eps)
    q, k, v = ((xh @ _wide(lp[name], float8)).reshape(R, -1, d) for name in ("wq", "wk", "wv"))
    q = _rms_norm(q, jnp.asarray(lp["q_norm"], F32), eps)
    k = _rms_norm(k, jnp.asarray(lp["k_norm"], F32), eps)
    return _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"]), v


def _head_block(y, q, visible, k, v, w_o, float8):
    """y (R, h) + one query head of one block of rows against its KV head's keys and
    values over all rows (T, d), under `visible` (R, T), through its rows of W_o."""
    scores = (q @ k.T) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return y + (probs @ v) @ _wide(w_o, float8)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _route(x, lp, c, float8):
    """x (R, h) -> (normed x, dense (R, E) of the picks' weights at their experts, zeros
    (R, h), and (R,) how far the last expert picked is ahead of the first left out in the
    router's LOGIT: a system in a lower precision picks another expert where that is
    within its rounding, and its logits there are another function's)."""
    xh = _rms_norm(x, jnp.asarray(lp["norm2"], F32), c["rms_norm_eps"])
    logits = xh @ _wide(lp["router"], float8)
    k = c["num_experts_per_tok"]
    weights, picks = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    dense = jnp.zeros_like(logits).at[jnp.arange(x.shape[0])[:, None], picks].set(weights)
    best, _ = jax.lax.top_k(logits, k + 1)
    return xh, dense, jnp.zeros_like(x), best[:, k - 1] - best[:, k]


def _expert(acc, xh, w_col, gate, up, down, float8):
    return acc + w_col[:, None] * _swiglu(xh, _wide(gate, float8), _wide(up, float8),
                                          _wide(down, float8))


def _logits(x, norm_f, head, eps, float8):
    return _rms_norm(x, jnp.asarray(norm_f, F32), eps) @ _wide(head, float8)


def _read(logits, tokens, mask_id):
    """Of rows' logits (R, V), the mask token's own at -inf: (the largest, its token, the
    logit of `tokens` (R,), the log of the largest's softmax probability)."""
    logits = jnp.where(jnp.arange(logits.shape[-1])[None, :] == mask_id, -jnp.inf, logits)
    best = logits.max(-1)
    return (best, logits.argmax(-1), jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0],
            best - jax.scipy.special.logsumexp(logits, axis=-1))


def _visible(pos_i, grp_i, upto_i, pos_j, grp_j, t_real, causal):
    """Which key rows j a block of query rows i sees. A row is of the final sequence
    (group 0) or of one replayed pass (its own group > 0; padding: -1). A key of the
    final sequence is seen below the row's bound `upto` (a sequence row: the end of its
    own block; a pass's row: the start of its block) and below the real length; a key of
    a pass by that pass's rows alone."""
    seen = jnp.where(grp_j[None, :] == 0,
                     (pos_j[None, :] < upto_i[:, None]) & (pos_j[None, :] < t_real),
                     (grp_j[None, :] == grp_i[:, None]))
    if causal:
        seen = seen & (pos_j[None, :] <= pos_i[:, None])
    # a row sees itself whatever else (padding rows: no empty softmax)
    return seen | ((pos_j[None, :] == pos_i[:, None]) & (grp_j[None, :] == grp_i[:, None]))


_ATTN = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm")
_PIECES = {}


def _pieces(cfg):
    key = tuple((k, cfg[k]) for k in ("head_dim", "rms_norm_eps", "num_experts_per_tok",
                                      "norm_topk_prob", "rope_theta"))
    if key not in _PIECES:
        c = dict(key)
        _PIECES[key] = {
            "qkv": jax.jit(lambda x, pos, lp, float8: _qkv(x, pos, lp, c, float8),
                           static_argnums=(3,)),
            "visible": jax.jit(_visible, static_argnums=(6,)),
            "head_block": jax.jit(_head_block, static_argnums=(6,), donate_argnums=(0,)),
            "route": jax.jit(lambda x, lp, float8: _route(x, lp, c, float8),
                             static_argnums=(2,)),
            "expert": jax.jit(_expert, static_argnums=(6,), donate_argnums=(0,)),
            "logits": jax.jit(lambda x, g, w, float8: _logits(x, g, w, c["rms_norm_eps"],
                                                              float8), static_argnums=(3,)),
            "read": jax.jit(lambda x, g, w, float8, tokens, mask_id: _read(_logits(
                x, g, w, c["rms_norm_eps"], float8), tokens, mask_id), static_argnums=(3,)),
        }
    return _PIECES[key]


def _forward(params, cfg, tokens, pos, grp, upto, t_real, rows, wrong):
    """Final hidden states (len(rows), h) and least pick gaps (len(rows),) of the rows
    `rows` of one forward pass over rows `tokens` at positions `pos`, each seeing what `_visible`
    says. The residual is held in blocks of BLOCK rows (one block where the count is no
    multiple of it)."""
    fn = _pieces(cfg)
    float8, causal = wrong == "float8", wrong == "causal"
    tokens = jnp.asarray(tokens, jnp.int32)
    pos, grp, upto = (jnp.asarray(a, jnp.int32) for a in (pos, grp, upto))
    R = tokens.shape[0]
    size = BLOCK if R % BLOCK == 0 else R
    cuts = [slice(s, s + size) for s in range(0, R, size)]
    d = cfg["head_dim"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    with jax.default_matmul_precision("highest"):
        X = [_wide(params["wte"][tokens[c]], float8) for c in cuts]
        seen = [fn["visible"](pos[c], grp[c], upto[c], pos, grp, jnp.int32(t_real), causal)
                for c in cuts]
        least = [jnp.full((size,), jnp.inf, F32) for _ in cuts]
        for lp in params["layers"]:
            sub = {k: lp[k] for k in _ATTN}
            q, k, v = zip(*(fn["qkv"](x, pos[c], sub, float8) for x, c in zip(X, cuts)))
            k, v = jnp.concatenate(k), jnp.concatenate(v)
            for h in range(cfg["num_attention_heads"]):
                kh, vh = k[:, h // group], v[:, h // group]
                X = [fn["head_block"](x, qb[:, h], m, kh, vh, lp["wo"][h * d:(h + 1) * d], float8)
                     for x, qb, m in zip(X, q, seen)]
            del q, k, v
            for b, x in enumerate(X):
                xh, dense, acc, gap = fn["route"](
                    x, {k: lp[k] for k in ("norm2", "router")}, float8)
                least[b] = jnp.minimum(least[b], gap)
                for e in range(lp["w_gate"].shape[0]):
                    acc = fn["expert"](acc, xh, dense[:, e], lp["w_gate"][e], lp["w_up"][e],
                                       lp["w_down"][e], float8)
                X[b] = x + acc
        x, least = jnp.concatenate(X), jnp.concatenate(least)
        if rows is not None:
            x, least = x[jnp.asarray(rows)], least[jnp.asarray(rows)]
        return x, least


def _check(wrong):
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"wrong is None or one of {WRONG}, not {wrong!r}")


def sequence_logits(params, cfg, tokens, rows=None, gaps=False, wrong=None, real=None):
    """tokens (T,) -> logits (len(rows), V) float32 of one sequence under the block-causal
    mask at the positions `rows` (all when None, in order): row i scores the token AT i.
    `cfg` is the configuration file's dict. `real`: the rows that are no padding (None:
    T). With `gaps`, also each position's smallest pick gap over the layers."""
    _check(wrong)
    T = len(tokens)
    B = 8 if wrong == "block8" else generation(cfg)["block_length"]
    pos = np.arange(T)
    x, least = _forward(params, cfg, tokens, pos, np.zeros(T, np.int32),
                        (pos // B + 1) * B, T if real is None else real, rows, wrong)
    with jax.default_matmul_precision("highest"):
        logits = _pieces(cfg)["logits"](x, params["norm_f"], params["head"],
                                        wrong == "float8")
    return (logits, least) if gaps else logits


def _passes(gen, prompt, served, fixed_at):
    """The served blocks that are whole, as (first position, block tokens (B,), fixed_at
    (B,) with PROMPT for the prompt's), in order. A last block that eos or the budget
    trimmed is left out: what its dropped positions held is not in the stream."""
    B = gen["block_length"]
    whole = len(prompt) // B * B
    toks = list(prompt[whole:]) + list(served)
    fixed = [PROMPT] * (len(prompt) - whole) + list(fixed_at)
    return [(whole + at, np.asarray(toks[at:at + B]), np.asarray(fixed[at:at + B]))
            for at in range(0, len(toks) - B + 1, B)]


# rows of logits read at a time (a row is the vocabulary in float32)
READ_ROWS = 512


def _read_rows(params, cfg, x, tokens, float8):
    """`_read` of hidden rows x (R, h) against `tokens` (R,), READ_ROWS rows at a time:
    four (R,) numpy arrays."""
    fn = _pieces(cfg)
    R = x.shape[0]
    step = READ_ROWS if R % READ_ROWS == 0 else R
    mask_id = generation(cfg)["mask_token_id"]
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        read = [fn["read"](x[at:at + step], params["norm_f"], params["head"], float8,
                           jnp.asarray(tokens[at:at + step]), mask_id)
                for at in range(0, R, step)]
    return tuple(np.concatenate([np.asarray(r[i]) for r in read]) for i in range(4))


def logits_of(params, cfg, blocks, items):
    """The logits of OTHER tokens at `replay`'s rows: `items` a list of (block index,
    pass, positions (n,), tokens (n,)); returns a list of (n,) arrays, an item each."""
    rows = [blocks[b]["hidden"][s][np.asarray(at)] for b, s, at, _ in items]
    tokens = np.concatenate([np.asarray(t) for *_, t in items]) if items else np.zeros(0)
    if not tokens.size:
        return [np.zeros(0) for _ in items]
    x = jnp.concatenate(rows)
    extra = -x.shape[0] % READ_ROWS
    x = jnp.concatenate([x, jnp.zeros((extra, x.shape[1]), x.dtype)])
    got = _read_rows(params, cfg, x, np.concatenate([tokens, np.zeros(extra)]), False)[2]
    cuts = np.cumsum([len(t) for *_, t in items])[:-1]
    return np.split(got[:tokens.size], cuts)


def replay(params, cfg, prompt, served, fixed_at, wrong=None, pad_to=None, logits=False):
    """The passes the engine ran to serve `served` (tokens) with `fixed_at` (the pass of
    its block at which each was fixed) behind `prompt`: for each whole served block and
    each pass s = 0 .. its last, the block with the tokens of fixed_at < s in place and the
    mask token elsewhere, behind the prompt and the committed blocks. Returns a list, a
    block each, of dicts: `start`, `tokens` (B,), `fixed_at` (B,), and per pass and
    position, from that pass's logits with the mask token's own at -inf: `best` (passes,
    B) the largest, `x0` its token, `served` the logit of the token the stream holds
    there, `conf` the log of the largest's softmax probability, `gaps` the rows' least
    pick gaps, `hidden` (passes, B, h) the rows before the head (`logits_of` reads other
    tokens' logits from them); with `logits`, also `logits` (passes, B, V) (the tests' sizes). `pad_to`:
    rows are padded to a multiple of it (one compiled reference for every request)."""
    _check(wrong)
    gen = generation(cfg)
    B, mask_id = gen["block_length"], gen["mask_token_id"]
    blocks = _passes(gen, prompt, served, fixed_at)
    seq = np.asarray(list(prompt) + list(served), np.int64)
    t_real = len(seq)
    if wrong == "no_commit":
        # the cache holds the LAST denoising pass's rows: its positions still the mask's
        for start, _, fixed in blocks:
            if fixed.max() >= 0:
                seq[start:start + B][fixed == fixed.max()] = mask_id
    Bw = 8 if wrong == "block8" else B
    pad = (lambda n: -(-n // pad_to) * pad_to) if pad_to else (lambda n: n)
    T = pad(t_real)
    tokens = [np.concatenate([seq, np.zeros(T - t_real, np.int64)])]
    pos, grp = [np.arange(T)], [np.zeros(T, np.int64)]
    upto = [(np.arange(T) // Bw + 1) * Bw]
    final = []                     # the stream's token at each replayed row
    for start, toks, fixed in blocks:
        for s in range(int(fixed.max()) + 1):     # the denoising passes
            tokens.append(np.where(fixed < s, toks, mask_id))
            pos.append(start + np.arange(B))
            grp.append(np.full(B, len(final) + 1))
            upto.append(np.full(B, start))
            final.append(toks)
    n_rows = B * len(final)
    extra = pad(n_rows) - n_rows
    tokens.append(np.zeros(extra, np.int64))
    pos.append(np.arange(extra))
    grp.append(np.full(extra, -1))
    upto.append(np.zeros(extra, np.int64))
    x, least = _forward(params, cfg, np.concatenate(tokens), np.concatenate(pos),
                        np.concatenate(grp), np.concatenate(upto), t_real,
                        T + np.arange(pad(n_rows)), wrong)
    fn, float8 = _pieces(cfg), wrong == "float8"
    final = np.concatenate(final + [np.zeros(extra, np.int64)])
    best, x0, served_logit, conf = (a[:n_rows].reshape(-1, B)
                                    for a in _read_rows(params, cfg, x, final, float8))
    if logits:
        with jax.default_matmul_precision("highest"):
            whole = np.array(fn["logits"](x[:n_rows], params["norm_f"], params["head"],
                                          float8)).reshape(-1, B, params["head"].shape[1])
        whole[..., mask_id] = -np.inf
    hidden = x[:n_rows].reshape(-1, B, x.shape[-1])
    least = np.asarray(least)[:n_rows].reshape(-1, B)
    out, at = [], 0
    for start, toks, fixed in blocks:
        n = int(fixed.max()) + 1
        cut = slice(at, at + n)
        out.append({"start": start, "tokens": toks, "fixed_at": fixed, "best": best[cut],
                    "x0": x0[cut], "served": served_logit[cut], "conf": conf[cut],
                    "gaps": least[cut], "hidden": hidden[cut]})
        if logits:
            out[-1]["logits"] = whole[cut]
        at += n
    return out


def picks(block, gen, wrong=None):
    """What the published rule fixes at each replayed pass of one of `replay`'s blocks,
    from that pass's confidences: a list, a pass each, of (positions fixed (sorted),
    their tokens). `wrong` "left_to_right": the leftmost masked positions instead."""
    n = gen["block_length"] // gen["denoising_steps"]
    out = []
    for s, (x0, conf) in enumerate(zip(block["x0"], block["conf"])):
        masked = np.flatnonzero(block["fixed_at"] >= s)
        if wrong == "left_to_right":
            fix = masked[:n]
        else:
            c = np.exp(conf[masked])
            high = masked[c > gen["confidence_threshold"]]
            fix = high if len(high) >= n \
                else masked[np.argsort(-c, kind="stable")[:n]]
        fix = np.sort(fix)
        out.append((fix, x0[fix]))
    return out


def generate(params, cfg, prompt, max_new):
    """The published loop, greedy, one pass a forward over the prompt and the blocks so
    far (the CPU tests' size). Returns (tokens, fixed_at): max_new generated tokens and the
    pass of its block at which each was fixed."""
    gen = generation(cfg)
    B, mask_id = gen["block_length"], gen["mask_token_id"]
    whole = len(prompt) // B * B
    seq, tail = list(prompt[:whole]), list(prompt[whole:])
    out, fixed_out = [], []
    while len(out) < max_new:
        toks = np.asarray(tail + [mask_id] * (B - len(tail)))
        fixed = np.asarray([PROMPT] * len(tail) + [MASKED] * (B - len(tail)))
        s = 0
        while (fixed == MASKED).any():
            logits = np.array(sequence_logits(params, cfg, seq + list(toks),
                                              len(seq) + np.arange(B)))
            logits[:, mask_id] = -np.inf
            conf = logits.max(-1) - np.asarray(jax.scipy.special.logsumexp(logits, -1))
            # one pass of a block whose masked positions are all "fixed at >= 0"
            fix, x0 = picks({"fixed_at": np.where(fixed == MASKED, 0, PROMPT),
                             "x0": logits.argmax(-1)[None], "conf": conf[None]}, gen)[0]
            toks[fix], fixed[fix] = x0, s
            s += 1
        out += list(toks[len(tail):])
        fixed_out += list(fixed[len(tail):])
        seq, tail = seq + list(toks), []
    return [int(t) for t in out[:max_new]], [int(f) for f in fixed_out[:max_new]]
