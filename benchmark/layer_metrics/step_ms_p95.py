"""95th percentile of single step times in the measured window (lag-1
completions on the host clock). The host clock is off by some half a
millisecond on each single step, so this stands here without a bound and
not among the end-to-end metrics. With 200 steps or more it has ten beyond
it; with fewer it nears the maximum, and under 20 there is nothing worth
reading."""
from benchmark import stats

LAYER = "entry"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if len(counters.get("step_s", ())) < 20:
        return None
    return 1e3 * stats.percentile(counters["step_s"], 95)
