"""Open loop: requests are sent when they are due, whatever the server does.
The measured set is every request DUE in [t0, t0 + seconds); the same traffic
is offered for a ramp before t0 and goes on after the window until every
measured request has finished or DRAIN_LIMIT_S have passed. Requests of the
ramp and the tail are offered and not measured."""

import threading
import time

from lib import serving, traffic as traffic_lib

DRAIN_LIMIT_S = 20.0


def run(ctx):
    mix = ctx.traffic
    served = serving.Served(ctx)
    try:
        executables = served.warm_up()
        schedule = traffic_lib.open_schedule(mix["arrivals"], ctx.seconds, ctx.seed,
                                             mix["ramp_s"], DRAIN_LIMIT_S + 5.0)
        t0 = time.monotonic() + mix["ramp_s"] + 0.2
        stop_offering = threading.Event()
        threads = []

        def offer():
            for spec in schedule:
                due = t0 + spec["due"]
                if stop_offering.wait(max(0.0, due - time.monotonic())):
                    return
                t = threading.Thread(target=served.request, args=(spec, due, spec["measured"]),
                                     name=f"bench-client-{spec['index']}", daemon=True)
                t.start()
                threads.append((t, spec["measured"]))

        offerer = threading.Thread(target=offer, name="bench-offer", daemon=True)
        offerer.start()
        time.sleep(max(0.0, t0 - time.monotonic()))
        ctx.mark_setup_done(ramp_s=mix["ramp_s"])
        observed = serving.observe_window(served, t0)
        n_measured = sum(s["measured"] for s in schedule)
        limit = t0 + ctx.seconds + DRAIN_LIMIT_S
        while time.monotonic() < limit:
            started = [t for t, m in list(threads) if m]
            if len(started) == n_measured and not any(t.is_alive() for t in started):
                break
            time.sleep(0.05)
        stop_offering.set()
        offerer.join(5.0)
        peak = ctx.memory_peak()
        served.shutdown()
        for t, _ in threads:
            t.join(10.0)
    except BaseException:
        served.shutdown()
        raise
    served.records.sort(key=lambda r: r["due"])
    measured = [r for r in served.records if r["measured"]]
    return serving.finish(served, "serve-open", t0, observed, measured, n_measured,
                          executables, peak)
