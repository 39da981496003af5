"""Seconds importing the program (`import paddle_tpu`, jax with it): the
program's `setup/import` phase, its package's first line to its last. The TPU
client's own start comes after it and is not in it."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "import_s")
