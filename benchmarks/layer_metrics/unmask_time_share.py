"""Device self time of the operations under the loop's own stages (`loop/sample`: x0 and
its confidence over the vocabulary; `loop/unmask`: the threshold-else-rank rule;
`loop/finish`: the commit) over device busy time, by `lib/stage_times.py`."""
from lib import stage_times

LAYER, UNIT, MOVES = "fused decode loop (block diffusion)", "%", "serve_tok_s"


def read(run):
    if "blocks_committed" not in (run.get("model1") or {}):
        return None
    return stage_times.share(run, ("loop",))
