"""Does a cell's `correct` see a program that computes another model?

    python3 scripts/cell_faults.py --workload smallthinker_21b_a3b.causal_pretrain_16k \
        --seed 3000041701 --faults router_behind_attention,window_ignored \
        [--out chiprun_out/faults.json]
    python3 scripts/cell_faults.py --workload lfm2_8b_a1b.causal_pretrain_2x8k \
        --seed 3000043701 --faults gates_left_out,sequences_run_on
    python3 scripts/cell_faults.py --workload keye_vl2_30b_a3b.sparse_causal_16k \
        --seed 3000004751 \
        --faults selection_left_out,indexer_loss_left_out,position_rows_collapsed
    python3 scripts/cell_faults.py --workload phi4_mini_flash.causal_pretrain \
        --seed 3000050701 \
        --faults lambda_taken_as_zero,diff_window_ignored,memory_behind_the_gate,cross_reads_its_own_stream,bfloat16_scan_state

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


def lambda_taken_as_zero():
    """Plain attention in differential attention's place: ``lambda = 0``,
    so a pair gives ``(1 - lambda_init) RMSNorm(A1 V)`` and the odd heads'
    map is dropped."""
    from paddle_tpu.ops import nn_ops
    combine = nn_ops._differential_heads

    def patched(ctx, lq1, lk1, lq2, lk2, weight, *, lambda_init, epsilon):
        return _plain_heads(ctx, weight, lambda_init, epsilon)
    nn_ops._differential_heads = patched
    return lambda: setattr(nn_ops, "_differential_heads", combine)


def _plain_heads(ctx, weight, lambda_init, epsilon):
    import jax
    import jax.numpy as jnp
    b, h, s, w = ctx.shape
    o = ctx.astype(jnp.float32).reshape(b, h // 2, 2, s, w)[:, :, 0]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + epsilon)
    out = (1.0 - lambda_init) * o * weight.astype(jnp.float32)
    return jnp.moveaxis(out, 1, 2).reshape(b, s, h // 2 * w).astype(ctx.dtype)


def diff_window_ignored():
    """Every windowed differential attention layer attends causally over
    the whole sequence."""
    from paddle_tpu.nn import hybrid
    init = hybrid.DifferentialAttention.__init__

    def patched(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.window = None
    hybrid.DifferentialAttention.__init__ = patched
    return lambda: setattr(hybrid.DifferentialAttention, "__init__", init)


def memory_behind_the_gate():
    """The memory tapped BEHIND the Mamba layer's gate: the memory units
    read ``y * silu(z)`` where the model hands on ``y``."""
    from paddle_tpu.nn import hybrid
    ssm = hybrid.S

    class Gated:
        def __getattr__(self, name):
            return getattr(ssm, name)

        @staticmethod
        def selective_scan(*args, **kwargs):
            gated, _ = ssm.selective_scan(*args, **kwargs)
            return gated, gated
    hybrid.S = Gated()
    return lambda: setattr(hybrid, "S", ssm)


def cross_reads_its_own_stream():
    """A cross attention layer reading keys and values of ITS OWN block's
    input: the giver's two projections and norm applied to the stream that
    enters the reader, where the model reads what layer N/2 + 1 made from
    its own."""
    from paddle_tpu.models.phi4_flash import Phi4FlashForCausalLM
    handed_to = Phi4FlashForCausalLM.handed_to

    def patched(self, block, h, handed):
        if block.kind != "cross_attention":
            return handed_to(self, block, h, handed)
        giver = next(b for b in self.layers if b.kind == "full_attention")
        return giver.mixer.key_value(giver.input_layernorm(h))
    Phi4FlashForCausalLM.handed_to = patched
    return lambda: setattr(Phi4FlashForCausalLM, "handed_to", handed_to)


def bfloat16_scan_state():
    """Mamba-1's state rounded to bfloat16 after every position (the
    reference's own time-step recurrence with ``state_dtype``, in the
    portable path's place; the kernels refused)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import phi4_flash as reference
    from paddle_tpu.ops import pallas, ssm
    scan, supported = ssm._selective_scan, pallas.selective_scan_mod.supported

    def patched(x, dt, a, b, c, d_skip, *, chunk):
        f32 = jnp.float32
        return jax.vmap(lambda x, dt, b, c: reference.selective_scan(
            x, dt, a.astype(f32), b, c, d_skip.astype(f32), jnp.bfloat16))(
                *(t.astype(f32) for t in (x, dt, b, c)))
    ssm._selective_scan = patched
    pallas.selective_scan_mod.supported = lambda *a, **k: False

    def undo():
        ssm._selective_scan = scan
        pallas.selective_scan_mod.supported = supported
    return undo


FAULTS = {f.__name__: f for f in (
    router_behind_attention, window_ignored, gates_left_out,
    sequences_run_on, selection_left_out, indexer_loss_left_out,
    position_rows_collapsed, lambda_taken_as_zero, diff_window_ignored,
    memory_behind_the_gate, cross_reads_its_own_stream,
    bfloat16_scan_state)}


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
