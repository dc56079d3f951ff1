"""Share of the score tiles of the whole (two copies x two copies)
rectangle that the flash kernels walk under the block-diffusion structure:
the program's counters ``flash_attention.tiles`` over ``tiles`` +
``flash_attention.tiles_skipped`` (the forward kernel's own bounds, added
up per kernel call site as the step is traced). ``n (n + 1) + n`` of ``4
n^2`` for ``n`` blocks a copy: 28.1 % at 512 x 512 and 8,192 tokens.
Nothing to read in a program without the second counter, or another
family's."""

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "sdar_moe":
        return None
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    seen = monitor.snapshot("flash_attention.tiles")
    walked = seen.get("flash_attention.tiles")
    skipped = seen.get("flash_attention.tiles_skipped")
    if not walked or skipped is None:
        return None
    return 100.0 * walked / (walked + skipped)
