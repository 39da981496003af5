"""Jitted functions jax traced INSIDE the traces and lowerings of the start's
executables (a Pallas body under `jax.jit(inline=True)`, a kernel's call under
`shard_map`): the sum of the records' `inner_traces` before `t0`. What a layer
unrolled without a shared trace multiplies."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "count", "setup_s"


def read(run):
    return setup_phases.value(run, "inner_traces")
