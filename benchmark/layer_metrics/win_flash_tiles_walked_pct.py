"""Share of the score tiles of the whole square that the flash kernels
walk under the sliding window: the program's counters
``flash_attention.window_tiles`` over ``window_tiles`` +
``flash_attention.window_tiles_skipped`` (the forward kernel's own bounds,
added up per windowed call site as the step is traced; the global layers'
call sites are not in them). 252 of 1,024 a head at 512 x 512, 16,384
positions and a window of 4,096: 24.6 %. Nothing to read in a program
without the counters."""

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    seen = monitor.snapshot("flash_attention.window_tiles")
    walked = seen.get("flash_attention.window_tiles")
    skipped = seen.get("flash_attention.window_tiles_skipped")
    if not walked or skipped is None:
        return None
    return 100.0 * walked / (walked + skipped)
