"""Seconds the step's first call spent in ``lowered.compile()``: ``backend_s`` of
the step's record (the program's span ``xla.backend_compile``): the cache key's
hashing of the module, then XLA's compile or, where ``step_loaded_from_cache``
reads 1, the persistent cache's load. The step's record is the first ``jit.*``
label the program's ``monitor.xla`` captured: the one ``to_static`` function a
cell's trainer builds (the reference compiles through plain ``jax.jit`` and
leaves no such label). Nothing to read in a program whose record has no such
field."""

LAYER = "entry"
UNIT = "s"
MOVES = "setup_s"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    label = next((l for l in monitor.xla.labels() if l.startswith("jit.")),
                 None)
    record = monitor.xla.get(label) if label else None
    if not record:
        return None
    return record.get("backend_s")
