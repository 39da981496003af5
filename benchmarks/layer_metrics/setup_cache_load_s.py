"""Seconds in which the start LOADED executables from the persistent compile
cache: a hit's whole backend phase (the key, the read, the executable onto the
device), the program's `compile/cache_load`. What `compile_s` is on a warm
start."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "s", "setup_s"


def read(run):
    return setup_phases.value(run, "cache_load_s")
