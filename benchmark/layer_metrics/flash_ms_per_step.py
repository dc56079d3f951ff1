"""Device time of the flash-attention kernels per step: the own time of
the Pallas kernels named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` (the ``name=`` of their ``pl.pallas_call``, read from the
compiled step's HLO by ``monitor.profile.instruction_ledger``), over the
traced steps. Nothing to read where the step holds none."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "flash_")
