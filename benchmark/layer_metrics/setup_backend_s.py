"""Seconds of backend compile, or of cache key and load, in all the programs
of set-up, hits and misses, the step's and the small programs around it: the
sum of ``backend_s`` over the records of the program's
``monitor.xla.programs()`` whose ``at_step_calls`` is at most
``CHECKED_STEPS + WARM_STEPS`` of ``jobs/train_loop.py`` (set-up ends where
the window begins; the reference's programs come after the window's calls).
Less ``first_call_backend_s`` it is what the programs outside the step cost.
Nothing to read in a program without the list."""

LAYER = "entry"
UNIT = "s"
MOVES = "setup_s"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
        from benchmark.jobs.train_loop import CHECKED_STEPS, WARM_STEPS
    except ImportError:
        return None
    if not hasattr(monitor.xla, "programs"):
        return None
    return sum(p["backend_s"] for p in monitor.xla.programs()
               if p["at_step_calls"] <= CHECKED_STEPS + WARM_STEPS)
