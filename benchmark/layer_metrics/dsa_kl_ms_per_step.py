"""Device time of the indexer's loss per step: everything under the scope
``F.dsa_indexer_loss`` (one pass over the causal score tiles that makes
the heads' probabilities from the flash call's kept statistics, the
loss and the gradients of qI, kI and w; kept by name, so a recomputed block
does not run it twice), over the traced steps
(``benchmark/scope_time.py``). Nothing to read in a program without the
op."""
from benchmark import scope_time

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return scope_time.scope_ms(summary, context, "F.dsa_indexer_loss")
