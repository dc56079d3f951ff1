"""``pl.pallas_call`` equations the step's jaxpr holds, each lowered to Mosaic
once in every process's set-up (a jaxpr that several call sites share through
an inner ``jax.jit`` counts once, as JAX lowers it once a module):
``pallas_instances`` of the step's record, counted by
``monitor.xla.count_pallas`` from the traced jaxpr. 0, not nothing, in a step
without kernels. The step's record is the first ``jit.*`` label the program's
``monitor.xla`` captured: the one ``to_static`` function a cell's trainer
builds (the reference compiles through plain ``jax.jit`` and leaves no such
label). Nothing to read in a program whose record has no such field."""

LAYER = "ops"
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
    if not record:
        return None
    return record.get("pallas_instances")
