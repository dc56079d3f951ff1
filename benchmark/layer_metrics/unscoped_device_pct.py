"""Share of the device's busy time in phase ``none``: instructions the
program's ledger does not know, or that carry no root scope and serve no
instruction that does. What ``fwd_ms``, ``bwd_ms`` and ``optimizer_ms``
leave out. Joined in ``benchmark/program_trace.py``."""
from benchmark import program_trace

LAYER = "monitor"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    out = program_trace.phases(summary, context)
    if out is None or not out["busy_s"]:
        return None
    return 100.0 * out["phase_s"]["none"] / out["busy_s"]
