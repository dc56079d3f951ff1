"""Does a cell's `correct` see a program that computes another model?

    python3 scripts/cell_faults.py --workload smallthinker_21b_a3b.causal_pretrain_16k \
        --seed 3000041701 --faults router_behind_attention,window_ignored \
        [--out chiprun_out/faults.json]
    python3 scripts/cell_faults.py --workload lfm2_8b_a1b.causal_pretrain_2x8k \
        --seed 3000043701 --faults gates_left_out,sequences_run_on
    python3 scripts/cell_faults.py --workload keye_vl2_30b_a3b.sparse_causal_16k \
        --seed 3000004751 \
        --faults selection_left_out,indexer_loss_left_out,position_rows_collapsed

For each named fault: the program with that fault planted, driven through
the steps `correct` checks at the cell's own size (``benchmark/control.py``'s
own path: one trainer, the reference's weights from the seed), against the
float32 reference computed once. Prints every number beside the cell's
limit and whether it holds; a fault the limits do not catch is a finding
for PERF.md, not an error. Not run by the benchmark's runs.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, run as harness          # noqa: E402


def router_behind_attention():
    """The usual placement: the router reads what the experts read."""
    from paddle_tpu import nn
    forward = nn.RoutedMoE.forward
    nn.RoutedMoE.forward = lambda self, u, router_input=None: forward(self, u)
    return lambda: setattr(nn.RoutedMoE, "forward", forward)


def window_ignored():
    """Every window layer attends causally over the whole sequence (its
    rotary positions stay)."""
    from paddle_tpu.nn import hybrid
    init = hybrid.GroupedQueryAttention.__init__

    def patched(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.window = None
    hybrid.GroupedQueryAttention.__init__ = patched
    return lambda: setattr(hybrid.GroupedQueryAttention, "__init__", init)


def gates_left_out():
    """The short convolution without its two gates: ``y = conv(u)`` where
    the layer has ``c * conv(b * u)``."""
    from paddle_tpu.nn import hybrid
    ssm = hybrid.S

    class Ungated:
        def __getattr__(self, name):
            return getattr(ssm, name)

        @staticmethod
        def gated_short_conv(bcx, weight):
            return ssm.causal_conv1d(bcx[:, :, 2 * weight.shape[0]:], weight)
    hybrid.S = Ungated()
    return lambda: setattr(hybrid, "S", ssm)


def sequences_run_on():
    """The batch taken as one long sequence by the short convolution: the
    first ``taps - 1`` rows of every sequence but the first read the tail
    of the sequence before."""
    from paddle_tpu.nn import hybrid
    forward = hybrid.GatedShortConv.forward

    def patched(self, u):
        b, s, d = u.shape
        return forward(self, u.reshape([1, b * s, d])).reshape([b, s, d])
    hybrid.GatedShortConv.forward = patched
    return lambda: setattr(hybrid.GatedShortConv, "forward", forward)


def selection_left_out():
    """Dense causal attention: every causal key kept."""
    from paddle_tpu.ops import sparse_attention
    select = sparse_attention.dsa_select
    sparse_attention.dsa_select = lambda qi, ki, w, top_k: select(
        qi, ki, w, int(qi.shape[2]))
    return lambda: setattr(sparse_attention, "dsa_select", select)


def indexer_loss_left_out():
    """``L_I`` left out of the step's loss: the indexers get no gradient."""
    from paddle_tpu.models.keye_vl import KeyeVL2ForCausalLM
    loss = KeyeVL2ForCausalLM.loss
    KeyeVL2ForCausalLM.loss = \
        lambda self, logits, ids, weights, indexer_loss: loss(
            self, logits, ids, weights, indexer_loss * 0.0)
    return lambda: setattr(KeyeVL2ForCausalLM, "loss", loss)


def position_rows_collapsed():
    """Height and width ids replaced by the temporal one: one-axis
    positions over the image spans."""
    import paddle_tpu as pt
    from paddle_tpu.models.keye_vl import KeyeVL2ForCausalLM
    forward = KeyeVL2ForCausalLM.forward
    KeyeVL2ForCausalLM.forward = lambda self, ids, at: forward(
        self, ids, pt.ops.manip.stack([at[0], at[0], at[0]]))
    return lambda: setattr(KeyeVL2ForCausalLM, "forward", forward)


FAULTS = {f.__name__: f for f in (
    router_behind_attention, window_ignored, gates_left_out,
    sequences_run_on, selection_left_out, indexer_loss_left_out,
    position_rows_collapsed)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic = harness.resolve(manifest, args.workload)
    limits = harness.cell_limits(cell)
    harness.check_device(cell)
    import paddle_tpu as pt
    pt.device.enable_compilation_cache(min_compile_time_secs=0.0)
    job = harness.load_module("jobs", traffic["job"])
    family = harness.load_module("families", cfg["family"])
    traffic = dict(traffic, chips=cell["chips"])
    reference = family.reference
    leaves = reference.compared_leaves(cfg)

    got = {}
    for name in [f for f in args.faults.split(",") if f]:
        undo = FAULTS[name]()
        try:
            got[name] = control.program_readings(
                job, family, cfg, traffic, [args.seed], harness.say)[args.seed]
        finally:
            undo()
    batches = job.make_pool(family, cfg, traffic,
                            args.seed)[:job.CHECKED_STEPS]
    want = reference.train(cfg, cfg["assumed"]["optimizer"], args.seed,
                           batches)
    rows = {}
    for name, numbers in got.items():
        rows[name] = []
        for number, value, key, note in job.numbers_compared(numbers, want,
                                                             leaves):
            holds = value <= limits[key]
            rows[name].append({"number": number, "value": value,
                               "limit": limits[key], "holds": holds,
                               "note": note})
            harness.say("fault", fault=name, number=number, value=value,
                        limit=limits[key], holds=holds, note=note)
        harness.say("fault", fault=name,
                    correct=all(r["holds"] for r in rows[name]))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "rows": rows,
                       "raw": {"reference": want, "program": got}}, f,
                      indent=1)


if __name__ == "__main__":
    main()
