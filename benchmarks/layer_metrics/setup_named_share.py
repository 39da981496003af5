"""Coverage: of `setup_s`, the share inside the UNION of the program's set-up
spans (`setup/import`, `serving/engine_build` and its children, `compile/trace`,
`compile/lower`, `compile/backend`, `compile/cache_load`) across threads. It
guards the other set-up metrics: what lies under no span, they cannot see (the
TPU client's start, an executable's first run, the harness's weights and ramp)."""
from lib import setup_phases

LAYER, UNIT, MOVES = "compile cache", "%", "setup_s"


def read(run):
    return setup_phases.value(run, "named_share")
