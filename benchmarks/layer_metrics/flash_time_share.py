"""Device time in Mosaic custom calls (the flash kernels) over busy time."""
LAYER, UNIT, MOVES = "attention kernels", "%", "train_tok_s"


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["kernel_s"] / trace["busy_s"] if trace else None
