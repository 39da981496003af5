"""The one traffic generator. A mix is a data file of parameters under
benchmarks/traffic/; nothing here knows a mix by name.

Stratified, not sampled: lengths and gaps sit at the quantile midpoints of
their distributions, so every seed offers a window the same requests, the same
tokens in and out and the same gaps. The seed pairs them, orders them, rotates
the arrivals and draws the token ids."""

import math
from statistics import NormalDist

import numpy as np


def lognormal_midpoints(n, median, sigma, lo, hi):
    inv = NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * math.exp(sigma * inv((i + 0.5) / n))))))
            for i in range(n)]


def exponential_gaps(n, span):
    """n gaps at the quantile midpoints of an exponential, scaled to fill
    `span` seconds exactly (the midpoints cut the far tail, so the raw sum
    falls a little short of n over the rate)."""
    raw = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return raw * (span / raw.sum())


def open_block(params, rate, seconds, seed, block):
    """One window's worth of open-loop arrivals: round(rate * seconds) requests
    at offsets in [0, seconds). `block` tells the ramp (-1), the window (0) and
    the tail (+1) apart: each gets its own pairing, order and rotation."""
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng([int(seed), block + 1000])
    prompts = lognormal_midpoints(n, **params["prompt_len"])
    outputs = lognormal_midpoints(n, **params["max_new_tokens"])
    gaps = exponential_gaps(n, seconds)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    rng.shuffle(gaps)
    offsets = np.sort((np.cumsum(gaps) + rng.uniform(0.0, seconds)) % seconds)
    return [{"offset": float(offsets[i]), "prompt_len": prompts[i],
             "max_new_tokens": outputs[i]} for i in range(n)]


def open_schedule(params, seconds, seed, ramp_s, tail_s):
    """Requests with `due` relative to the window's start: the last ramp_s
    seconds of a ramp block, the window, and tail_s seconds after it. Only the
    requests due in [0, seconds), the window's block, are `measured`; the
    others keep the load up."""
    rate = params["rate_per_s"]
    out = []
    for block, shift in ((-1, -seconds), (0, 0.0), (1, seconds)):
        for r in open_block(params, rate, seconds, seed, block):
            due = r["offset"] + shift
            if -ramp_s <= due < seconds + tail_s:
                out.append({"due": due, "measured": block == 0, "prompt_len": r["prompt_len"],
                            "max_new_tokens": r["max_new_tokens"]})
    out.sort(key=lambda r: r["due"])
    for k, r in enumerate(out):
        r["index"] = k
        finish_sampling(r, params, seed)
    return out


def closed_request(params, seed, k):
    """Request k of a closed loop. Prompt lengths cycle through one list and
    output lengths through another of coprime length, so any run of
    len(prompts) * len(outputs) consecutive requests holds every pair once.
    The seed sets where the cycles start and their order."""
    rng = np.random.default_rng([int(seed), 77])
    prompts = list(params["prompt_lens"])
    outputs = list(params["max_new_tokens"])
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    rot_p, rot_o = rng.integers(0, len(prompts)), rng.integers(0, len(outputs))
    r = {"index": k,
         "prompt_len": prompts[(k + rot_p) % len(prompts)],
         "max_new_tokens": outputs[(k + rot_o) % len(outputs)]}
    finish_sampling(r, params, seed)
    return r


def finish_sampling(r, params, seed):
    """Even requests greedy, odd ones sampled at the mix's temperature with a
    seed of their own."""
    if r["index"] % 2 and params.get("temperature"):
        r["temperature"] = params["temperature"]
        r["seed"] = (int(seed) * 1000003 + r["index"]) % (2 ** 31 - 1)
    else:
        r["temperature"] = 0.0
        r["seed"] = 0


def prompt_tokens(seed, index, length, vocab):
    """Token ids of request `index`: drawn from the seed, shared with no other
    request."""
    rng = np.random.default_rng([int(seed), 5, int(index)])
    return rng.integers(0, vocab, size=length).tolist()


def train_batches(seed, count, batch, seq, vocab):
    rng = np.random.default_rng([int(seed), 9])
    return [rng.integers(0, vocab, size=(batch, seq)).astype(np.int64)
            for _ in range(count)]
