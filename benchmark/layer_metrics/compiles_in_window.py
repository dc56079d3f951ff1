"""Compilations inside the measured window: the deltas of the program's
``jit.compile`` and ``jit.recompile`` counters. 0 is expected."""

LAYER = "entry"
UNIT = "count"
MOVES = "step_ms"


def read(summary, counters, context):
    return counters.get("compiles_in_window")
