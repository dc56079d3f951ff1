"""Seconds ``import paddle_tpu`` took in this process: the program's gauge
``runtime.import_s``, set at the end of ``paddle_tpu/__init__.py`` from a
clock read at its first line (jax's own import is in it where nothing had
imported jax before; ``run.py: check_device`` has). Nothing to read in a
program without the gauge."""

LAYER = "entry"
UNIT = "s"
MOVES = "setup_s"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    return monitor.registry().value("runtime.import_s", None)
