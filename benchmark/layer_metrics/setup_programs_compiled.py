"""Programs XLA compiled during set-up, the step's included: the records of
the program's ``monitor.xla.programs()`` (one a backend compile, from JAX's
own events) whose ``cache_hit`` is False and whose ``at_step_calls`` is at
most ``CHECKED_STEPS + WARM_STEPS`` of ``jobs/train_loop.py``: set-up ends
where the window begins, and the reference compiles its own programs after
the window's calls, in the same process. 0 where the cache served them all.
Nothing to read in a program without the list."""

LAYER = "entry"
UNIT = "count"
MOVES = "setup_s"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
        from benchmark.jobs.train_loop import CHECKED_STEPS, WARM_STEPS
    except ImportError:
        return None
    if not hasattr(monitor.xla, "programs"):
        return None
    return sum(1 for p in monitor.xla.programs()
               if p["at_step_calls"] <= CHECKED_STEPS + WARM_STEPS
               and p["cache_hit"] is False)
