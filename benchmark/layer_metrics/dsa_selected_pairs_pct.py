"""Share of the causal (row, key) pairs that the learned selection kept:
the program's device counters ``dsa.pairs_selected`` over
``dsa.pairs_causal`` (``nn.SparseGroupedQueryAttention.stats``, added to
inside the compiled step by every layer, every step since the process
began; both in the same unit of pairs). 31,458,304 of 134,225,920 at
16,384 positions and ``topk`` 2,048: 23.4 %. Nothing to read in a program
without the counters."""

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    try:
        from paddle_tpu.monitor import device_counters
    except ImportError:
        return None
    seen = device_counters.read("dsa.")
    if not seen.get("dsa.pairs_causal"):
        return None
    return 100.0 * seen["dsa.pairs_selected"] / seen["dsa.pairs_causal"]
