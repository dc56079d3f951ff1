"""Device time of the Pallas (Mosaic) kernels per step: the sum of the
durations of the ``tpu_custom_call`` events on the first device, over the
traced steps. Nothing to read where the step holds no kernel."""

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if not summary.get("kernels") or not summary.get("steps"):
        return None
    return 1e3 * sum(sec for _, sec in summary["kernels"]) / summary["steps"]
