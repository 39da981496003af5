"""The end-to-end arithmetic on hand-made records, with a request or a step
across each edge of the window."""

import pytest

from lib import metrics, spread


def req(due, first, last, end, tokens, prompt_len=100, ok=True):
    return {"due": due, "sent": due, "first": first, "last": last, "end": end,
            "tokens": tokens, "prompt_len": prompt_len, "ok": ok, "measured": True}


def test_open_loop_set_is_defined_by_due_time_and_nothing_is_censored():
    t0, seconds = 100.0, 10.0
    records = [
        req(99.9, 100.2, 101.0, 101.0, 5),     # due before the window, runs into it: not measured
        req(100.0, 100.1, 100.5, 100.5, 5),    # due at t0: measured
        req(105.0, 105.3, 106.3, 106.3, 11),
        req(109.9, 110.4, 114.4, 114.4, 21),   # due inside, finishes after the window: measured whole
        req(110.0, 110.1, 110.2, 110.2, 3),    # due at the end: not measured
    ]
    measured = metrics.due_in_window(records, t0, seconds)
    assert [r["due"] for r in measured] == [100.0, 105.0, 109.9]
    # waits 0.1, 0.3, 0.5 s: the nearest-rank 95th percentile of three is the largest
    assert metrics.ttft_p95_ms(measured) == pytest.approx(500.0)
    # (0.4 + 1.0 + 4.0) s over (4 + 10 + 20) tokens after the first
    assert metrics.tpot_mean_ms(measured) == pytest.approx(1e3 * 5.4 / 34)


def test_ttft_p95_of_forty_is_the_thirty_eighth():
    measured = [req(float(i), i + 0.001 * (i + 1), i + 1.0, i + 1.0, 2) for i in range(40)]
    assert metrics.ttft_p95_ms(measured) == pytest.approx(38.0)


def test_single_token_requests_do_not_enter_tpot():
    measured = [req(0.0, 0.5, 0.5, 0.5, 1), req(0.0, 1.0, 3.0, 3.0, 5)]
    assert metrics.tpot_mean_ms(measured) == pytest.approx(500.0)
    assert metrics.tpot_mean_ms([measured[0]]) is None


def test_serve_rate_runs_between_completion_events():
    t0, seconds = 50.0, 10.0
    records = [
        req(40.0, 41.0, 49.9, 49.9, 10, prompt_len=500),    # ends before the window: not counted
        req(45.0, 46.0, 52.0, 52.0, 16, prompt_len=384),    # straddles t0, ends inside: opens the interval
        req(50.0, 51.0, 55.0, 55.0, 32, prompt_len=512),
        req(51.0, 52.0, 58.0, 58.0, 64, prompt_len=960),
        req(57.0, 58.0, 61.0, 61.0, 64, prompt_len=960),    # straddles the end: not counted
        req(53.0, 54.0, 57.0, 57.0, 9, prompt_len=100, ok=False),   # failed: no tokens credited
    ]
    # completions inside at 52, 55, 58: (544 + 1024) tokens over 6 s
    assert metrics.serve_tok_s(records, t0, seconds) == pytest.approx(1568 / 6.0)
    assert metrics.serve_tok_s(records[:2], t0, seconds) is None


def test_train_rate_counts_steps_completing_in_the_window():
    steps = [{"done": 9.95, "tokens": 8192, "loss": 1.0}]          # before
    steps += [{"done": 10.0 + 0.5 * i, "tokens": 8192, "loss": 1.0} for i in range(5)]   # 10.0 .. 12.0
    steps += [{"done": 12.6, "tokens": 8192, "loss": 1.0}]         # after
    # inside [10, 12.5): five completions, four intervals of 0.5 s
    assert metrics.train_tok_s(steps, 10.0, 2.5) == pytest.approx(4 * 8192 / 2.0)


def test_spread_is_the_interquartile_share_of_the_median():
    # statistics.quantiles(n=4) of 1..6 gives 1.75 and 5.25; the median is 3.5
    assert metrics.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
    assert metrics.spread([10.0] * 6) == 0.0


def test_spread_script_reads_sub_windows(tmp_path):
    import json

    paths = []
    for seed, step in ((1, 0.100), (2, 0.101), (3, 0.099)):
        p = tmp_path / f"cell.seed{seed}.trace0.jsonl"
        rows = [{"header": True, "cell": "cell", "mode": "train", "t0": 5.0,
                 "seconds": 51.0, "seed": seed, "setup_s": 30.0 + seed}]
        rows += [{"done": 5.0 + step * i, "tokens": 100, "loss": 1.0} for i in range(600)]
        p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        paths.append(str(p))
    out = spread.table(paths)
    median, spr, n = out["train_tok_s"][20.0]
    assert n == 3 and median == pytest.approx(1000.0, rel=1e-3) and 0.005 < spr < 0.03
    assert set(out["train_tok_s"]) == {20.0, 30.0, 40.0, 51.0}
    assert out["setup_s"][0.0][0] == 32.0
