"""Device time under an ``F.*`` scope of the program, wherever the op was
called from: the own time, in a traced run, of every instruction whose
region (``program_trace.phases(...)["regions"]``) has the scope on its
path, forward and backward phase added up, as ``region_time.py`` reads a
layer class."""
import re

from benchmark import program_trace


def scope_ms(summary, context, scope):
    """ms per step, or None where there is no trace, no ledger, or no
    instruction under the scope (a program without the op)."""
    out = program_trace.phases(summary, context)
    if out is None:
        return None
    under = re.compile(r"(^|/)%s(/|$)" % re.escape(scope))
    sec = sum(s for (_, region), s in out["regions"].items()
              if under.search(region))
    return 1e3 * sec / out["steps"] if sec else None
