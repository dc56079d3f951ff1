"""Device time of the selection of a learned sparse attention per step:
everything under the scope ``F.dsa_select`` (index scores of every causal
pair, the exact ``top_k``-th largest a row, the int8 selection: the kernel
``dsa_select`` on the chip, a sort a block of rows elsewhere), the
recomputed call of the backward pass included, over the traced steps
(``benchmark/scope_time.py``). Nothing to read in a program without the
op."""
from benchmark import scope_time

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return scope_time.scope_ms(summary, context, "F.dsa_select")
