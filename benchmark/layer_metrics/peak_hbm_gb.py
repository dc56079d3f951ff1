"""The run's ``memory_peak_bytes`` in GB, read after the measured window
and before the reference runs: on the fullest chip, the runtime's peak of
live buffers plus its peak of memory reserved for running programs'
temporaries (``jobs/train_loop.py: device_peak_bytes``). The runtime gives
no peak of the sum, so this is an upper bound of what the chip held where
the two peaks fell apart in time. Headroom buys batch."""

LAYER = "device"
UNIT = "GB"
MOVES = "step_ms"


def read(summary, counters, context):
    if "memory_peak_bytes" not in counters:
        return None
    return counters["memory_peak_bytes"] / 1e9
