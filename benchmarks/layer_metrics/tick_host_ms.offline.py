"""Host time of an engine tick that launched or collected a decode dispatch:
the program's `serving/engine_step` span less its waits for the device (its
`serving/tick/collect`, and inside its admit phase `serving/wait/first_token`
and `serving/wait/fence`), per such tick whole inside the traced window."""
from lib import program_spans

LAYER, UNIT, MOVES = "engine: queue, admission", "ms", "serve_tok_s"


def read(run):
    return program_spans.value(run, "tick_host_ms")
