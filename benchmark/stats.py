"""Arithmetic from a list of completion times to the end-to-end numbers.

The loop blocks on the loss of the previous step while the next one is
queued (lag 1), so every step has a completion time and the device queue
never drains. The end-to-end numbers are taken over all the work and all
the time of the window: every step that completed, from the start of the
window to the last completion. The host clock is off by some half a
millisecond, which that span of seconds does not feel; single step times
(``step_times``) feel it, and are read only by per-layer metrics.
"""
import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as numpy's default."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def step_times(t_start, completions):
    """Seconds from each completion to the one before; the first from the
    start of the window."""
    out, prev = [], t_start
    for t in completions:
        out.append(t - prev)
        prev = t
    return out


def seconds_per_step(t_start, completions):
    """The time from the start of the window to the last completion, over
    the steps that completed: a stall anywhere in the window is in it."""
    if not completions:
        raise ValueError("no step completed in the window")
    return (completions[-1] - t_start) / len(completions)


def throughput(units_per_step, t_start, completions, chips):
    """Units of every step that completed, over the time from the start of
    the window to the last completion, per chip."""
    if not completions:
        raise ValueError("no step completed in the window")
    return units_per_step * len(completions) / (completions[-1] - t_start) \
        / chips
