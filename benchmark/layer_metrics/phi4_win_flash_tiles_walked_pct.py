"""Score tiles the windowed flash call sites of a ``phi4_flash`` step walk
against the tiles the window NEEDS: the program's counters
``flash_attention.window_tiles`` (the forward kernel's own bounds, added up
per windowed call site as the step is traced) over
``flash_attention.window_tiles_needed`` (the least tiles of the same size
that could hold the pairs the window allows: the allowed pairs over a
tile's). 100 % would be a walk with no pair outside the window; under a
window of 512 a q-block's rows see 512 + BQ - 1 keys, so 512 x 512 tiles
read about 200 % and the 512 x 1,024 of the block rule before its clause
for a window narrower than a k-block (``flash_attention.py:
_window_blocks``) about 400 %. Nothing to read in a program without the
counters (the parent's) or in another family's cell."""

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "phi4_flash":
        return None
    try:
        from paddle_tpu import monitor
    except ImportError:
        return None
    seen = monitor.snapshot("flash_attention.window_tiles")
    walked = seen.get("flash_attention.window_tiles")
    needed = seen.get("flash_attention.window_tiles_needed")
    if not walked or not needed:
        return None
    return 100.0 * walked / needed
