"""The least time the residual mixers of the prompts prefilled could take on the
chip's memory bandwidth (the streams read once for the coefficients and the
sublayer's input, read again with the sublayer's output and written once, in
every sublayer: `costs_xing.mixer_bytes`) over the device self time under `hc/*`
inside `jit_prefill_impl`: a mean prompt of the window's admissions times the
prefills the trace holds."""
from lib import costs_xing as costs, scope_reduce

LAYER, UNIT, MOVES = "residual streams", "%", "serve_tok_s"


def read(run):
    seconds = scope_reduce.scope_seconds(run, "jit_prefill_impl", "hc/")
    traced = scope_reduce.runs_in_window(run, "jit_prefill_impl")
    lens = [r["prompt_len"] for r in run["records"]
            if r["ok"] and run["t0"] <= r["sent"] < run["t0"] + run["seconds"]]
    if not seconds or not traced or not lens or "hc_mult" not in run["config"]:
        return None
    nbytes = traced * costs.mixer_bytes(run["config"], sum(lens) / len(lens))
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / seconds
