"""Device time of the multi-token-prediction module per step: everything
under the regions ``MultiTokenPredictor_<k>`` (``models.joyai_llm_flash.
MultiTokenPredictor``: its two norms, ``eh_proj``, its latent attention and
its expert layer) and ``SharedHead_<k>`` (its norm and its pass through the
main model's head), forward + backward with the recomputed forward, over
the traced steps (``benchmark/region_time.py``). Its attention and expert
layer are also in ``mla_attention_ms_per_step`` and ``moe_ms_per_step``;
its look-up in the shared embedding and its loss are not in it."""
from benchmark import program_trace, region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    out = program_trace.phases(summary, context)
    if out is None:
        return None
    sec = region_time.class_seconds(out, "MultiTokenPredictor") \
        + region_time.class_seconds(out, "SharedHead")
    return 1e3 * sec / out["steps"] if sec else None
