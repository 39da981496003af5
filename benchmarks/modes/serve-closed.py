"""Closed loop: `clients` threads, each sending its next request when its last
one has finished, so the queue is never empty and never past the limit. The
clients run through the ramp, the window and past its end without a break;
the window only selects which completions are counted. The ramp starts the
clients one at a time over `ramp_s`, about the life of one request, so that
they do not move in waves."""

import itertools
import threading
import time

from lib import serving, traffic as traffic_lib


def run(ctx):
    mix = ctx.traffic
    served = serving.Served(ctx)
    try:
        executables = served.warm_up()
        clients = mix["clients"]
        counter = itertools.count()
        take = threading.Lock()
        stop = threading.Event()

        def client(delay):
            if stop.wait(delay):
                return
            while not stop.is_set():
                with take:
                    k = next(counter)
                spec = traffic_lib.closed_request(mix["requests"], ctx.seed, k)
                served.request(spec, time.monotonic())

        start = time.monotonic()
        threads = [threading.Thread(target=client, args=(mix["ramp_s"] * i / clients,),
                                    name=f"bench-client-{i}", daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        t0 = start + mix["ramp_s"] + mix["settle_s"]
        time.sleep(max(0.0, t0 - time.monotonic()))
        ctx.mark_setup_done(ramp_s=mix["ramp_s"] + mix["settle_s"])
        observed = serving.observe_window(served, t0)
        # past the window's end without a break: the completion that closes
        # the rate's interval is inside, the clients that follow are not cut
        time.sleep(mix["tail_s"])
        stop.set()
        peak = ctx.memory_peak()
        served.shutdown()
        for t in threads:
            t.join(10.0)
    except BaseException:
        served.shutdown()
        raise
    served.records.sort(key=lambda r: r["end"])
    # judged: the requests that ended inside the window
    measured = [r for r in served.records if t0 <= r["end"] < t0 + ctx.seconds]
    return serving.finish(served, "serve-closed", t0, observed, measured, len(measured),
                          executables, peak)
