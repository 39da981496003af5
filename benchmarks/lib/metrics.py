"""The end-to-end arithmetic, over per-request and per-step records. Times are
seconds on the host's monotonic clock; a window is [t0, t0 + seconds).

A request record: due, sent, first (first token received), last (last token
received), end (stream closed), tokens (generated), prompt_len, ok.
A step record: done (when the loss reached the host), loss, tokens."""

import math


def quantile(values, q):
    """Nearest-rank quantile: the smallest value with at least q of the sample
    at or below it. No interpolation, so a hand count gives the same number."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def due_in_window(records, t0, seconds):
    """The measured set of an open-loop cell: every request DUE in the window,
    whenever it finished. Nothing is censored at the window's edge."""
    return [r for r in records if t0 <= r["due"] < t0 + seconds]


def ttft_p95_ms(measured):
    waits = [r["first"] - r["due"] for r in measured if r.get("first") is not None]
    value = quantile(waits, 0.95)
    return None if value is None else 1e3 * value


def tpot_mean_ms(measured):
    """Token-weighted: all the time between first and last tokens over all the
    tokens after a first, so it does not matter which requests are the long ones."""
    span = sum(r["last"] - r["first"] for r in measured
               if r.get("first") is not None and r["tokens"] > 1)
    steps = sum(r["tokens"] - 1 for r in measured
                if r.get("first") is not None and r["tokens"] > 1)
    return 1e3 * span / steps if steps else None


def rate_between_events(events, t0, seconds):
    """events: (completion time, work). Work of the completions at c1..cm over
    (cm - c0), for the completions c0 < ... < cm inside the window: the clock
    starts and stops on an event, so no request is cut at an edge and the rate
    is not quantised by whole requests."""
    inside = sorted((t, w) for t, w in events if t0 <= t < t0 + seconds)
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return None
    return sum(w for _, w in inside[1:]) / (inside[-1][0] - inside[0][0])


def serve_tok_s(records, t0, seconds):
    return rate_between_events(
        [(r["end"], r["prompt_len"] + r["tokens"]) for r in records if r["ok"]],
        t0, seconds)


def train_tok_s(steps, t0, seconds):
    return rate_between_events([(s["done"], s["tokens"]) for s in steps],
                               t0, seconds)


def spread(values):
    """Interquartile distance as a share of the median, with the quartiles of
    statistics.quantiles(n=4): the driver's measure of how far runs repeat."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
