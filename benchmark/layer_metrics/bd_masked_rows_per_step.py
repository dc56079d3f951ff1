"""Positions that carried a loss weight (were masked in the noisy copy), per
optimizer step: the program's device counters ``diffusion.masked_rows``
over ``diffusion.steps`` (``models.sdar_moe.SDARMoEForBlockDiffusion.
stats``, added to inside the compiled step), every step since the process
began. The traffic's noise level is uniform over [0.05, 1]: 0.525 of the
data tokens on average. Nothing to read in a program without the
counters."""

LAYER = "ops"
UNIT = "count"
MOVES = "step_ms"


def read(summary, counters, context):
    try:
        from paddle_tpu.monitor import device_counters
    except ImportError:
        return None
    seen = device_counters.read("diffusion.")
    if not seen.get("diffusion.steps"):
        return None
    return seen["diffusion.masked_rows"] / seen["diffusion.steps"]
