"""The traffic kind ``train_loop``: a user's training loop.

A pool of host batches made from the seed; each step takes the next host
batch through ``pt.to_tensor`` (the copy to the device is inside the
window), calls the one compiled step (one optimizer step per dispatch)
and then blocks on the loss of the step before, so the device queue never
drains and every step still has a completion time.

Set-up builds ONE trainer from the seed, drives it through its first
steps through that same call and feed (the steps the plain reference
follows), warms up, and hands the same object to the window.
"""
import gc
import math

import numpy as np

from benchmark import stats
from benchmark.reference.common import diff_norms

CHECKED_STEPS = 3     # the steps the plain reference follows
WARM_STEPS = 5        # between them and the window
TRACE_STEPS = 10      # profiled after the window in a traced run


class Drive:
    """What one stretch of the loop saw, on the host clock."""

    def __init__(self, t_start):
        self.t_start = t_start
        self.completions = []     # time each step's loss reached the host
        self.dispatch_s = []      # time each call of the step took to return
        self.losses = []
        self.error = None

    @property
    def failed(self):
        bad = sum(1 for x in self.losses if not math.isfinite(x))
        return bad + (1 if self.error is not None else 0)

    @property
    def attempted(self):
        return len(self.dispatch_s) + (1 if self.error is not None else 0)


def drive(step, pool, first, clock, until=None, steps=None):
    """Run the loop from pool position ``first`` until ``until`` on
    ``clock`` or for ``steps`` steps; the last step is waited for."""
    import jax
    import paddle_tpu as pt

    out, pending, i = Drive(clock()), None, first
    while (until is None or clock() < until) and \
            (steps is None or i - first < steps):
        try:
            with jax.profiler.TraceAnnotation("bench.feed"):
                feed = [pt.to_tensor(a) for a in pool[i % len(pool)]]
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                t = clock()
                loss = step(*feed)
                out.dispatch_s.append(clock() - t)
        except Exception as e:          # the run reports it and is not correct
            out.error = repr(e)
            break
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                out.losses.append(float(pending.numpy()))
                out.completions.append(clock())
        pending, i = loss, i + 1
    if pending is not None:
        with jax.profiler.TraceAnnotation("bench.fetch"):
            out.losses.append(float(pending.numpy()))
            out.completions.append(clock())
    return out


def device_peak_bytes():
    """``memory_peak_bytes``: the most the fullest chip can have held, from
    the runtime's two peaks: that of live buffers (``peak_bytes_in_use``)
    plus that of the memory it reserved for running programs' temporaries
    (``peak_bytes_reserved``). This TPU runtime counts the two apart (a
    BERT-base step's 5 GB of temporaries are not in ``peak_bytes_in_use``),
    and gives no peak of their sum: where the two peaks fell at different
    times the sum is an upper bound of the true peak (cell 1: 6.95 GB
    against 6.43 GB in XLA's analysis of the step)."""
    import jax
    return max(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
               for s in (d.memory_stats() for d in jax.local_devices()))


def leaf_gaps(got, want, leaves):
    """(median, worst, the worst leaf's name) of the gaps, over ``leaves``,
    between the program's norm of a leaf and the reference's: the gap of
    the norms, not the norm of a difference, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    floor = float(np.median([want[k] for k in leaves]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            for k in leaves}
    if not all(math.isfinite(g) for g in gaps.values()):
        return float("inf"), float("inf"), None
    where = max(gaps, key=gaps.get)
    return float(np.median(list(gaps.values()))), gaps[where], where


def numbers_compared(got, want, leaves):
    """[(name, value, key of its limit, note)]: every number `correct`
    compares. A gradient's worst leaf is what a lower precision fails with
    room in every cell; its median leaf carries the roundings that all
    leaves share (a scale at the top of the backward pass) and is held
    against a gradient scaled wrong as a whole. What each limit rests on
    is in the cell's file, benchmark/limits/<cell>.json."""
    out = []
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), 1):
        gap = abs(a - b) / abs(b) if math.isfinite(a) else float("inf")
        out.append((f"loss_gap_step{i}", gap,
                    "loss_gap_first" if i == 1 else "loss_gap_later",
                    f"program {a!r} reference {b!r}"))
    for key in ("first_grad_norm", "delta_norm"):
        median, worst, where = leaf_gaps(got[key], want[key], leaves)
        out.append((f"{key}_gap_median", median, f"{key}_gap_median",
                    f"over {len(leaves)} leaves"))
        out.append((f"{key}_gap_worst", worst, f"{key}_gap_worst",
                    f"worst leaf {where}"))
    return out


def compare(got, want, limits, leaves, say):
    """Every number compared, printed beside its limit; True when all
    hold. ``leaves`` are the parameters whose norms are compared."""
    ok = True
    for name, value, limit_key, note in numbers_compared(got, want, leaves):
        holds = value <= limits[limit_key]
        ok = ok and holds
        say("correct", number=name, value=repr(value),
            limit=limits[limit_key], holds=holds,
            note=note.replace(" ", "_"))
    return ok


def checked_steps(trainer, pool, reference, cfg, seed, clock):
    """Drive a trainer that holds the seed's weights through the steps the
    reference follows, through the window's own call and feed. Returns the
    numbers `correct` compares, and the stretches of the loop driven."""
    import jax

    first = drive(trainer.step, pool, 0, clock, steps=1)
    got = {"first_grad_norm": trainer.first_gradient_norms()}
    rest = drive(trainer.step, pool, 1, clock, steps=CHECKED_STEPS - 1)
    if first.error or rest.error:
        raise RuntimeError(f"the step raised in set-up: "
                           f"{first.error or rest.error}")
    got["loss"] = first.losses + rest.losses
    got["delta_norm"] = {k: float(v) for k, v in jax.device_get(
        jax.jit(diff_norms)(trainer.parameters(),
                            reference.init_weights(cfg, seed))).items()}
    return got, (first, rest)


def make_pool(family, cfg, traffic, seed):
    rng = np.random.default_rng(seed)
    return [family.host_batch(cfg, traffic, rng)
            for _ in range(traffic["pool_size"])]


def run(cell, cfg, traffic, limits, family, seed, seconds, trace_dir, clock,
        say):
    """One cell, once. Returns the harness's view of the run. ``limits``
    are the cell's own (benchmark/limits/<cell>.json)."""
    import jax
    from paddle_tpu import monitor

    monitor.enable()
    registry = monitor.registry()

    def compiles():
        return int(registry.value("jit.compile", 0)) + \
            int(registry.value("jit.recompile", 0))

    traffic = dict(traffic, chips=cell["chips"])
    pool = make_pool(family, cfg, traffic, seed)
    reference = family.reference
    trainer = family.build(cfg, traffic, reference.init_weights(cfg, seed))
    step = trainer.step

    got, checked = checked_steps(trainer, pool, reference, cfg, seed, clock)
    say("setup", note="trace+compile_or_load+1_step", compiles=compiles(),
        first_call_s=f"{checked[0].completions[0] - checked[0].t_start:.1f}")
    warm = drive(step, pool, CHECKED_STEPS, clock, steps=WARM_STEPS)
    position = CHECKED_STEPS + WARM_STEPS

    # what the collector costs the loop is a note, not a change to the loop
    pauses, began = [], []
    def on_gc(phase, info):
        (began if phase == "start" else pauses).append(clock())
    gc.callbacks.append(on_gc)
    before = compiles()
    t_window = clock()
    window = drive(step, pool, position, clock, until=t_window + seconds)
    gc.callbacks.remove(on_gc)
    position += window.attempted
    compiles_in_window = compiles() - before
    peak = device_peak_bytes()
    say("device", memory_stats=jax.local_devices()[0].memory_stats())

    traced = None
    if trace_dir is not None:
        # the Python tracer would time every call of the host's loop
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            traced = drive(step, pool, position, clock, steps=TRACE_STEPS)
        finally:
            jax.profiler.stop_trace()

    errors = [d.error for d in (*checked, warm, window, traced)
              if d is not None and d.error is not None]
    for e in errors:
        say("error", step_raised=e.replace(" ", "_"))
    hyper = cfg["assumed"]["optimizer"]
    del trainer, step
    gc.collect()

    t = clock()
    want = reference.train(cfg, hyper, seed, pool[:CHECKED_STEPS])
    say("correct", reference_s=f"{clock() - t:.1f}",
        reference_steps=CHECKED_STEPS, precision="float32_highest")
    ok = compare(got, want, limits,
                 reference.compared_leaves(cfg), say) and not errors \
        and window.failed == 0
    say("correct", losses_in_window_finite=window.failed == 0,
        first_losses=[round(x, 4) for x in got["loss"]],
        last_loss=round(window.losses[-1], 4) if window.losses else None)

    step_s = stats.step_times(window.t_start, window.completions)
    typical = stats.percentile(step_s, 50)
    say("window", steps=len(step_s), typical_step_ms=f"{1e3 * typical:.3f}",
        slow_steps=sum(1 for x in step_s if x > 1.5 * typical),
        lost_ms=f"{1e3 * (sum(step_s) - len(step_s) * typical):.1f}",
        longest_step_ms=f"{1e3 * max(step_s):.1f}",
        gc_collections=len(pauses),
        gc_ms=f"{1e3 * sum(e - b for b, e in zip(began, pauses)):.1f}")
    units = family.units_per_step(traffic)
    per_s = stats.throughput(units, window.t_start, window.completions,
                             cell["chips"])
    return {
        "correct": bool(ok),
        "attempted": window.attempted,
        "failed": window.failed,
        "t_window": t_window,
        "memory_peak_bytes": int(peak),
        "end_to_end": {
            family.THROUGHPUT: per_s,
            "step_ms": 1e3 * stats.seconds_per_step(window.t_start,
                                                    window.completions),
        },
        "flops_per_s_chip": per_s * family.flops_per_unit(cfg, traffic),
        "counters": {
            "compiles_in_window": compiles_in_window,
            "dispatch_s": window.dispatch_s,
            "step_s": step_s,
            "memory_peak_bytes": int(peak),
            "traced_steps": len(traced.completions) if traced else 0,
        },
    }
