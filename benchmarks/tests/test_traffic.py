"""The generators: the same totals on every seed, another order."""

from lib import traffic

CHAT = {"rate_per_s": 8.0, "temperature": 0.8,
        "prompt_len": {"median": 96, "sigma": 0.9, "lo": 16, "hi": 512},
        "max_new_tokens": {"median": 64, "sigma": 0.7, "lo": 16, "hi": 256}}
SEEDS = (1, 7, 2147483659)


def window(seed, seconds=40.0):
    sched = traffic.open_schedule(CHAT, seconds, seed, ramp_s=10.0, tail_s=5.0)
    inside = [r for r in sched if 0.0 <= r["due"] < seconds]
    assert inside == [r for r in sched if r["measured"]]
    return sched, inside


def test_open_loop_offers_every_seed_the_same_window():
    windows = [window(s)[1] for s in SEEDS]
    assert {len(w) for w in windows} == {320}
    for key in ("prompt_len", "max_new_tokens"):
        assert len({tuple(sorted(r[key] for r in w)) for w in windows}) == 1
    gaps = []
    for w in windows:
        dues = [r["due"] for r in w]
        cyc = sorted(b - a for a, b in zip(dues, dues[1:] + [dues[0] + 40.0]))
        gaps.append([round(g, 9) for g in cyc])
    assert gaps[0] == gaps[1] == gaps[2]
    # and another order
    orders = {tuple(r["prompt_len"] for r in w) for w in windows}
    assert len(orders) == len(SEEDS)


def test_open_loop_lengths_are_clipped_and_centred():
    _, w = window(3)
    prompts = sorted(r["prompt_len"] for r in w)
    assert prompts[0] == 16 and prompts[-1] == 512
    assert 90 <= prompts[len(prompts) // 2] <= 100
    outputs = sorted(r["max_new_tokens"] for r in w)
    assert outputs[0] >= 16 and outputs[-1] == 256


def test_ramp_and_tail_are_offered_outside_the_window():
    sched, w = window(5)
    assert min(r["due"] for r in sched) < -9.0 and max(r["due"] for r in sched) > 44.0
    assert [r["due"] for r in sched] == sorted(r["due"] for r in sched)
    assert len({r["index"] for r in sched}) == len(sched)
    odd = [r for r in sched if r["index"] % 2]
    assert all(r["temperature"] == 0.8 and r["seed"] > 0 for r in odd)
    assert all(r["temperature"] == 0.0 for r in sched if r["index"] % 2 == 0)


def test_same_seed_same_traffic():
    assert window(11)[0] == window(11)[0]
    assert traffic.prompt_tokens(11, 3, 20, 50257) == traffic.prompt_tokens(11, 3, 20, 50257)
    assert traffic.prompt_tokens(11, 3, 20, 50257) != traffic.prompt_tokens(11, 4, 20, 50257)
    assert max(traffic.prompt_tokens(2 ** 31 + 11, 0, 500, 50257)) < 50257


DOCS = {"prompt_lens": [384, 448, 512, 608, 704, 800, 896, 960],
        "max_new_tokens": [16, 32, 64], "temperature": 0.8}


def test_closed_loop_holds_every_pair_in_any_24_requests():
    firsts = set()
    for seed in SEEDS:
        for start in (0, 5, 17):
            reqs = [traffic.closed_request(DOCS, seed, k) for k in range(start, start + 24)]
            pairs = {(r["prompt_len"], r["max_new_tokens"]) for r in reqs}
            assert len(pairs) == 24
            assert sum(r["prompt_len"] for r in reqs) == 3 * sum(DOCS["prompt_lens"])
        firsts.add(tuple(traffic.closed_request(DOCS, seed, k)["prompt_len"] for k in range(8)))
    assert len(firsts) == len(SEEDS)


def test_train_batches_are_distinct_and_seeded():
    a = traffic.train_batches(3, 4, 2, 16, 211)
    b = traffic.train_batches(3, 4, 2, 16, 211)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == a[1]).all() and a[0].shape == (2, 16) and a[0].max() < 211
