"""Median of single step times in the measured window (lag-1 completions
on the host clock): the step without the stalls. The end-to-end ``step_ms``
is the whole window over all its steps; where the two part, steps were
lost to stalls and not to a slower step."""
from benchmark import stats

LAYER = "entry"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if len(counters.get("step_s", ())) < 20:
        return None
    return 1e3 * stats.percentile(counters["step_s"], 50)
