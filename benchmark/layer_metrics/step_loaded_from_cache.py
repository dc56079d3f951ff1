"""1 where the persistent compile cache served the step's executable, 0 where
XLA compiled it: ``cache_hit`` of the step's record, JAX's own
``/jax/compilation_cache/cache_hits`` / ``cache_misses`` event as the
program's listener saw it inside ``lowered.compile()``. The step's record is
the first ``jit.*`` label the program's ``monitor.xla`` captured: the one
``to_static`` function a cell's trainer builds (the reference compiles
through plain ``jax.jit`` and leaves no such label). Nothing to read where
no cache is configured, or in a program whose record has no such field."""

LAYER = "entry"
UNIT = "count"
MOVES = "setup_s"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    label = next((l for l in monitor.xla.labels() if l.startswith("jit.")),
                 None)
    record = monitor.xla.get(label) if label else None
    if not record or record.get("cache_hit") is None:
        return None
    return int(record["cache_hit"])
