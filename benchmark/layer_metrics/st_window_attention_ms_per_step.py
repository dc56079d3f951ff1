"""Device time of the WINDOW layers' attention of a ``smallthinker`` step:
the regions ``GroupedQueryAttention_<k>`` of the layers that
``sliding_window_layout`` marks (``st_attention_ms_per_step`` less the
global layers'). A layer's scope is numbered in first-call order, which is
the stack's order: the i-th smallest ``k`` among the step's regions is
layer ``i``. Nothing where the step's attention regions are not one a held
layer, or in another family's program.

The window layers' kernels are one lowering a module (``_win_fwd`` /
``_win_bwd`` behind a module-level ``jax.jit``), so the device trace gives
all six layers' kernel time the labels of the FIRST window layer's call
site: the sum over the window layers, which is what this reads, is right;
a split of it by layer would not be."""
import re

from benchmark import program_trace, smallthinker_costs

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"

_SCOPE = re.compile(r"(?:^|/)GroupedQueryAttention_(\d+)(?:/|$)")


def read(summary, counters, context):
    cfg = context["config"]
    if cfg.get("family") != "smallthinker":
        return None
    out = program_trace.phases(summary, context)
    if out is None:
        return None
    by_scope = {}
    for (_, region), sec in out["regions"].items():
        found = _SCOPE.search(region)
        if found:
            k = int(found.group(1))
            by_scope[k] = by_scope.get(k, 0.0) + sec
    windowed = smallthinker_costs.windowed_layers(cfg)
    if len(by_scope) != len(windowed):
        return None
    sec = sum(by_scope[k] for k, w in zip(sorted(by_scope), windowed) if w)
    return 1e3 * sec / out["steps"] if sec else None
