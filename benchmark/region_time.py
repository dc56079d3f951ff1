"""Device time of a part of the model, by the class of the layer that
made it: the own time, in a traced run, of every instruction whose region
(``program_trace.phases(...)["regions"]``: the path of layer and ``F.*``
scopes the program gave it) lies under an instance of the class, forward
and backward phase added up — a recomputed forward runs in the backward
phase and is in it. An instruction that holds several regions gives each
its modelled share (``program_trace.py``)."""
import re

from benchmark import program_trace


def class_seconds(out, cls):
    """Seconds, over all traced steps, under instances ``<cls>_<k>``."""
    under = re.compile(r"(^|/)%s_\d+(/|$)" % re.escape(cls))
    return sum(sec for (_, region), sec in out["regions"].items()
               if under.search(region))


def class_ms(summary, context, cls):
    """ms per step, or None where there is no trace, no ledger, or no
    instance of the class in the step (a program without the class)."""
    out = program_trace.phases(summary, context)
    if out is None:
        return None
    sec = class_seconds(out, cls)
    return 1e3 * sec / out["steps"] if sec else None


def moe_counters():
    """The program's device counters ``moe.*`` since the process began, or
    None where the program has none (a program without the layer) or no
    call was counted."""
    try:
        from paddle_tpu.monitor import device_counters
    except ImportError:
        return None
    seen = device_counters.read("moe.")
    return seen if seen.get("moe.steps") else None
