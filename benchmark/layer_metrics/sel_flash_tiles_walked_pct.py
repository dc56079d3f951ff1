"""Share of the causal score tiles that the flash kernels walk under a
selection: the program's counters ``flash_attention.selected_tiles_walked``
over ``flash_attention.selected_tiles_causal`` (the forward kernel's own
bounds, added up per kernel call site as the step is traced). 100 today: a
token-level selection empties no 512 x 512 tile at random weights, so the
kernels walk the causal call's tiles and mask inside them; a kernel that
skipped tiles would read lower. Nothing to read in a program without the
counters."""

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    seen = monitor.snapshot("flash_attention.selected_tiles")
    causal = seen.get("flash_attention.selected_tiles_causal")
    if not causal:
        return None
    return 100.0 * seen.get("flash_attention.selected_tiles_walked",
                            0) / causal
