"""Device time in Mosaic custom calls over busy time, in the chat cell: serving
has one Mosaic kernel, the decode step's paged attention
(paddle_tpu/ops/paged_attention.py). A program without it reads 0."""
LAYER, UNIT, MOVES = "decode/prefill math", "%", "tpot_mean_ms"


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["kernel_s"] / trace["busy_s"] if trace else None
