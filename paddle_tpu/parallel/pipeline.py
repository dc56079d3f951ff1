"""User-facing pipeline (pp) stage sharding for the fleet bridge.

TPU-native rebuild of Fleet's pipeline strategy (reference:
python/paddle/fluid/optimizer.py:PipelineOptimizer +
incubate/fleet/collective DistributedStrategy pipeline mode). The
reference splits the Program into per-device section programs and
streams microbatches between them. The GSPMD formulation used here:
a trunk of IDENTICAL blocks (transformer encoder layers) has its
per-block parameters stacked on a leading axis sharded over the mesh's
`pp` axis — every stage's weights live only on its pipeline group — and
the forward is one `lax.scan` over the stacked axis. XLA then streams
each stage's (stage-resident) weights/activations with its own
collectives. This is the standard JAX/GSPMD pipeline recipe
("stacked-scan with stage-sharded weights"); the lower-level explicit
GPipe microbatch schedule over `ppermute` lives in parallel/megatron.py.

The stacked module is a drop-in replacement for a LayerList trunk:
optimizer/state_dict/checkpoint all see ordinary (sharded) Parameters.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..tensor import Tensor, Parameter
from ..dispatch import apply
from .. import autograd as _ag
from ..nn.layer import Layer
from .collective import axis_size as _axis_size

__all__ = ["PipelineStack", "PipelineSchedule", "build_schedule",
           "pipeline_step"]


class PipelineStack(Layer):
    """Stack N identical blocks into stage-sharded scanned weights.

    blocks: list/LayerList of structurally identical Layers (same param
    names/shapes). mesh + pipeline_axis: where the stacked axis lives.
    spec_fn(name, shape) -> PartitionSpec gives the per-block placement
    (e.g. megatron tp specs); the pp axis is prepended to it.
    """

    def __init__(self, blocks, mesh=None, pipeline_axis="pp",
                 spec_fn=None, remat=False):
        super().__init__()
        blocks = list(blocks)
        if not blocks:
            raise ValueError("PipelineStack needs at least one block")
        # remat: jax.checkpoint each stage inside the scan (recompute
        # activations during backward — the fleet recompute strategy
        # applied to the stacked trunk)
        # not `_remat`: that name is nn.Layer's memory-plan policy slot,
        # which memory_plan's process-wide layer hook reads
        self._stage_remat = bool(remat)
        self._template = blocks[0]
        # template params are NOT trainable on their own — exclude the
        # template from registration (its holders get swapped per step)
        self._sub_layers.pop("_template", None)
        object.__setattr__(self, "_template", blocks[0])

        names = list(blocks[0].state_dict().keys())
        self._names = names
        self._flat_names = []
        for name in names:
            per = [b.state_dict()[name].data for b in blocks]
            stk = jnp.stack(per)
            if mesh is not None:
                spec = spec_fn(name, per[0].shape) if spec_fn else P()
                full = P(*((pipeline_axis,) + tuple(spec)))
                stk = jax.device_put(stk, NamedSharding(mesh, full))
            flat = "stk_" + name.replace(".", "__")
            setattr(self, flat, Parameter(stk))
            self._flat_names.append(flat)
        self.num_blocks = len(blocks)

    def forward(self, x, *extras):
        from .. import random as prandom
        from ..nn.moe import MoEFFN
        stacked = [self._parameters[n] for n in self._flat_names]
        template = self._template
        # the template is unregistered (its params are placeholders), so
        # train/eval mode must be forwarded by hand
        template.train() if self.training else template.eval()
        names = self._names
        # MoE sublayers stash an aux (load-balance) loss during forward —
        # a scan-body tracer if left on the template. Thread the aux
        # values out as scan OUTPUTS and re-stash the per-trunk total on
        # this Layer (moe_aux_loss collects it from here).
        moe_subs = [l for l in template.sublayers(include_self=True)
                    if isinstance(l, MoEFFN)]

        def impl(x, rng_key, *rest):
            stk = rest[:len(names)]
            extra_arr = rest[len(names):]

            def stage_call(h, sub, *slices):
                # stochastic ops (dropout) inside the scan body must draw
                # from a key CARRIED through the scan — letting them
                # advance the global key would leak a scan-body tracer
                # into it (same invariant as jit.recompute)
                holders = template.state_dict()
                saved = {}
                saved_key = prandom._global_key.data
                prandom._global_key.data = sub
                try:
                    for name, sl in zip(names, slices):
                        saved[name] = holders[name].data
                        holders[name].data = sl
                    with _ag.no_grad():
                        out = template(Tensor(h),
                                       *[Tensor(e) for e in extra_arr])
                    out = out.data if isinstance(out, Tensor) else out
                    auxs = tuple(l.aux_loss.data for l in moe_subs)
                finally:
                    prandom._global_key.data = saved_key
                    for name, v in saved.items():
                        holders[name].data = v
                return out, auxs

            if self._stage_remat:
                stage_call = jax.checkpoint(stage_call)

            def body(carry, slices):
                h, key = carry
                key, sub = jax.random.split(key)
                out, auxs = stage_call(h, sub, *slices)
                return (out, key), auxs

            (h, _), auxs = lax.scan(body, (x, rng_key), tuple(stk))
            # auxs: tuple of [num_blocks] arrays — total load-balance aux
            total_aux = None
            for a in auxs:
                s = jnp.sum(a)
                total_aux = s if total_aux is None else total_aux + s
            return (h, total_aux) if moe_subs else h

        args = (x, prandom.next_key_graph()) + tuple(stacked) + \
            tuple(extras)
        if not moe_subs:
            self.aux_loss = None
            return apply(impl, args, name="pipeline_stack")
        h, aux = apply(impl, args, name="pipeline_stack", n_out=2)
        self.aux_loss = aux
        return h


# ---------------------------------------------------------------------------
# Explicit microbatch schedules: GPipe, 1F1B, interleaved 1F1B.
#
# Reference: fluid/optimizer.py PipelineOptimizer splits the Program into
# per-device section programs and streams microbatches through them (GPipe
# order, schedule fixed by the section runner). The TPU rebuild makes the
# schedule a first-class object: a [T, n_ranks] table of (op, microbatch,
# chunk) slots produced by a dependency-respecting simulator, with analytic
# bubble/memory accounting, executed by `pipeline_step` as one lax.scan of
# lax.switch ticks over a ppermute ring inside shard_map.
#
# Schedule facts (fwd and bwd both 1 time unit):
#   gpipe        bubble = (n-1)/(m+n-1)    peak live acts = m
#   1f1b         bubble = (n-1)/(m+n-1)    peak live acts = min(m, n)
#   interleaved  bubble ~ (n-1)/(v*m+n-1)  peak live acts ~ min(m, n)+v-1
# (n = ranks, m = microbatches, v = chunks/rank). Non-interleaved 1F1B
# matches GPipe in TIME and wins on MEMORY (activations freed as soon as
# their backward runs); the interleaved schedule also shrinks the time
# bubble by ~v.

_IDLE, _FWD, _BWD = 0, 1, 2


class PipelineSchedule:
    """A simulated pipeline timeline.

    table: int32 [T, n_ranks, 3] of (op, microbatch, chunk) — op 0/1/2 =
    idle/forward/backward; chunk is the virtual-stage index on that rank
    (always 0 unless interleaved). Stage s = chunk * n_ranks + rank."""

    def __init__(self, kind, table, n_ranks, n_micro, n_chunks):
        self.kind = kind
        self.table = table
        self.n_ranks = n_ranks
        self.n_micro = n_micro
        self.n_chunks = n_chunks

    @property
    def n_ticks(self):
        return self.table.shape[0]

    def bubble_fraction(self, bwd_cost=1.0):
        """Idle fraction of the timeline. bwd_cost weights backward ops
        (Megatron's accounting uses ~2.0: bwd is two matmul passes);
        each tick's duration is the COSTLIEST op running in it (lockstep
        SPMD: every rank waits for the slowest)."""
        ops = self.table[:, :, 0]
        cost = {_IDLE: 0.0, _FWD: 1.0, _BWD: float(bwd_cost)}
        tick_len = np.array([max(cost[int(o)] for o in row)
                             for row in ops])
        busy = sum(cost[int(o)] for row in ops for o in row)
        total = float(tick_len.sum()) * self.n_ranks
        return 1.0 - busy / total if total else 0.0

    def render(self):
        """ASCII timeline (ranks x ticks): F3/B3 = fwd/bwd of microbatch
        3; for interleaved, chunk c shows as c:F3. Debugging aid."""
        # one fixed cell width keeps tick columns vertically aligned
        width = 1 + len(str(self.n_micro - 1)) + (
            2 if self.n_chunks > 1 else 0)
        lines = []
        for r in range(self.n_ranks):
            cells = []
            for t in range(self.n_ticks):
                op, mb, c = self.table[t, r]
                if op == _IDLE:
                    cells.append(".".center(width))
                else:
                    tag = "F" if op == _FWD else "B"
                    pre = f"{c}:" if self.n_chunks > 1 else ""
                    cells.append(f"{pre}{tag}{mb}".rjust(width))
            lines.append(f"rank{r}: " + " ".join(cells))
        return "\n".join(lines)

    def peak_live_activations(self):
        """Max over (rank, chunk) of simultaneously-saved fwd activations
        (saved at F, freed at the matching B) — the per-stage activation
        memory the schedule needs."""
        peak = 0
        for r in range(self.n_ranks):
            live = {}
            for t in range(self.n_ticks):
                op, mb, c = self.table[t, r]
                if op == _FWD:
                    live[c] = live.get(c, 0) + 1
                    peak = max(peak, live[c])
                elif op == _BWD:
                    live[c] = live.get(c, 0) - 1
        return peak


def _rank_orders(kind, n, m, v):
    """Per-rank total op order (list of (op, mb, chunk) per rank)."""
    if kind == "gpipe":
        return [[(_FWD, mb, 0) for mb in range(m)]
                + [(_BWD, mb, 0) for mb in reversed(range(m))]
                for _ in range(n)]
    if kind == "1f1b":
        orders = []
        for r in range(n):
            w = min(m, n - 1 - r)          # warmup forwards
            ops = [(_FWD, mb, 0) for mb in range(w)]
            fwd, bwd = w, 0
            while fwd < m:                  # steady 1F1B
                ops.append((_FWD, fwd, 0)); fwd += 1
                ops.append((_BWD, bwd, 0)); bwd += 1
            while bwd < m:                  # cooldown backwards
                ops.append((_BWD, bwd, 0)); bwd += 1
            orders.append(ops)
        return orders
    if kind == "interleaved":
        if m % n != 0:
            raise ValueError("interleaved schedule needs n_micro % "
                             "n_ranks == 0 (Megatron constraint)")
        orders = []
        for r in range(n):
            # forward/backward enumeration: groups of n microbatches cycle
            # through the chunks (Megatron interleaved order)
            fseq, bseq = [], []
            for g in range(m // n):
                base = g * n
                for c in range(v):
                    fseq += [(_FWD, base + i, c) for i in range(n)]
                for c in reversed(range(v)):
                    bseq += [(_BWD, base + i, c) for i in range(n)]
            warm = min(len(fseq), (n - 1 - r) * 2 + (v - 1) * n)
            ops = fseq[:warm]
            fi, bi = warm, 0
            while fi < len(fseq):
                ops.append(fseq[fi]); fi += 1
                ops.append(bseq[bi]); bi += 1
            ops += bseq[bi:]
            orders.append(ops)
        return orders
    raise ValueError(f"unknown schedule kind {kind!r}")


def build_schedule(kind, n_ranks, n_micro, n_chunks=1):
    """Simulate `kind` into a dependency-valid timeline.

    Greedy lockstep simulation: at each tick every rank runs the next op
    in its order whose dependencies completed on an EARLIER tick (the
    activation/cotangent ride one ppermute hop between ticks):
      F(s, mb) needs F(s-1, mb);  B(s, mb) needs F(s, mb) and B(s+1, mb)
    where stage s = chunk * n_ranks + rank runs on rank s % n_ranks."""
    n, m, v = n_ranks, n_micro, n_chunks
    if kind != "interleaved" and v != 1:
        raise ValueError("n_chunks > 1 only for the interleaved schedule")
    orders = _rank_orders(kind, n, m, v)
    done_f, done_b = {}, {}   # (stage, mb) -> completion tick
    idx = [0] * n
    rows = []
    t = 0
    limit = 4 * v * (m + n) + 16
    while any(idx[r] < len(orders[r]) for r in range(n)):
        if t > limit:
            raise RuntimeError(f"schedule {kind} deadlocked (bug in the "
                               "per-rank order)")
        row = []
        fired = []
        for r in range(n):
            if idx[r] >= len(orders[r]):
                row.append((_IDLE, 0, 0))
                continue
            op, mb, c = orders[r][idx[r]]
            s = c * n + r
            if op == _FWD:
                ready = (s == 0) or done_f.get((s - 1, mb), t) < t
            else:
                last = s == v * n - 1
                ready = done_f.get((s, mb), t) < t and (
                    last or done_b.get((s + 1, mb), t) < t)
            if ready:
                row.append((op, mb, c))
                fired.append((r, op, s, mb))
                idx[r] += 1
            else:
                row.append((_IDLE, 0, 0))
        for r, op, s, mb in fired:
            (done_f if op == _FWD else done_b)[(s, mb)] = t
        rows.append(row)
        t += 1
    table = np.asarray(rows, np.int32)
    return PipelineSchedule(kind, table, n, m, v)


def pipeline_step(schedule, stage_fn, loss_fn, params, x_micro,
                  labels_micro, axis="pp"):
    """Execute one fwd+bwd pipeline pass under `schedule`. Runs INSIDE
    shard_map with `axis` bound (one rank per pipeline stage).

    params: pytree whose leaves carry a leading [n_chunks] axis (this
    rank's virtual stages; n_chunks=1 for gpipe/1f1b).
    stage_fn(x, p_chunk) -> y with y.shape == x.shape.
    loss_fn(y, labels_mb) -> scalar (per-microbatch mean).
    x_micro: [m, ...] stage-0 inputs; labels_micro: [m, ...] last-stage
    targets (replicated — each rank reads only what its ops use).

    Returns (loss, grads): loss = mean over microbatches (on every rank);
    grads = pytree like params. BACKWARD IS MANUAL — per-tick jax.vjp with
    recompute-from-saved-input (the activation a B op consumes is the
    stage INPUT saved by its F op; the stage is re-run inside vjp), so
    activation memory follows the schedule's peak_live_activations, not
    the autodiff engine's whole-timeline saves."""
    n = _axis_size(axis)
    r = lax.axis_index(axis)
    m = schedule.n_micro
    v = schedule.n_chunks
    assert schedule.n_ranks == n, (schedule.n_ranks, n)
    table = jnp.asarray(schedule.table)          # [T, n, 3]
    A = schedule.peak_live_activations() + 2     # act/inbox slots (+transit)

    x_shape = x_micro.shape[1:]
    zero_x = jnp.zeros(x_shape, x_micro.dtype)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]

    def tick(carry, trow):
        acts, inbox_f, inbox_b, grads, loss_acc = carry
        op, mb, c = trow[r, 0], trow[r, 1], trow[r, 2]
        s = c * n + r                             # global stage id
        slot = mb % A
        p_c = jax.tree_util.tree_map(lambda l: l[c], params)

        def do_idle(acts, grads, loss_acc):
            return acts, zero_x, zero_x, grads, loss_acc

        def do_fwd(acts, grads, loss_acc):
            x = jnp.where(s == 0, x_micro[mb], inbox_f[c, slot])
            acts = acts.at[c, slot].set(x)
            y = stage_fn(x, p_c)
            return acts, y, zero_x, grads, loss_acc

        def do_bwd(acts, grads, loss_acc):
            x = acts[c, slot]

            def full(x, p):
                y = stage_fn(x, p)
                return y, loss_fn(y, labels_micro[mb])

            (y, lval), vjp_fn = jax.vjp(full, x, p_c)
            is_last = s == v * n - 1
            ct_y = jnp.where(is_last, jnp.zeros_like(y), inbox_b[c, slot])
            ct_l = jnp.where(is_last, 1.0 / m, 0.0)
            dx, dp = vjp_fn((ct_y.astype(y.dtype),
                             jnp.asarray(ct_l, lval.dtype)))
            grads = jax.tree_util.tree_map(
                lambda g, d: g.at[c].add(d), grads, dp)
            loss_acc = loss_acc + jnp.where(is_last, lval / m, 0.0)
            return acts, zero_x, dx, grads, loss_acc

        acts, y_out, dx_out, grads, loss_acc = lax.switch(
            op, (do_idle, do_fwd, do_bwd), acts, grads, loss_acc)

        # ride the ring every tick (collectives must run on all ranks).
        # Each payload is tagged with its microbatch (-1 = nothing) and
        # the RECEIVER's chunk index — stage s+1 lives on rank (s+1)%n at
        # chunk (s+1)//n — and filed into the receiver's (chunk, mb)
        # inbox slot. The last stage sends no activation; stage 0 sends
        # no cotangent.
        sent_f = jnp.where((op == _FWD) & (s < v * n - 1), mb, -1)
        sent_fc = jnp.clip((s + 1) // n, 0, v - 1)
        sent_b = jnp.where((op == _BWD) & (s > 0), mb, -1)
        sent_bc = jnp.clip((s - 1) // n, 0, v - 1)
        recv_y = lax.ppermute(y_out, axis, fwd_perm)
        recv_fmb = lax.ppermute(sent_f, axis, fwd_perm)
        recv_fc = lax.ppermute(sent_fc, axis, fwd_perm)
        recv_dx = lax.ppermute(dx_out, axis, bwd_perm)
        recv_bmb = lax.ppermute(sent_b, axis, bwd_perm)
        recv_bc = lax.ppermute(sent_bc, axis, bwd_perm)
        fslot = jnp.clip(recv_fmb, 0) % A
        bslot = jnp.clip(recv_bmb, 0) % A
        inbox_f = inbox_f.at[recv_fc, fslot].set(
            jnp.where(recv_fmb >= 0, recv_y, inbox_f[recv_fc, fslot]))
        inbox_b = inbox_b.at[recv_bc, bslot].set(
            jnp.where(recv_bmb >= 0, recv_dx, inbox_b[recv_bc, bslot]))
        return (acts, inbox_f, inbox_b, grads, loss_acc), None

    acts0 = jnp.zeros((v, A) + x_shape, x_micro.dtype)
    inbox0 = jnp.zeros((v, A) + x_shape, x_micro.dtype)
    grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
    carry0 = (acts0, inbox0, inbox0, grads0,
              jnp.zeros((), jnp.float32))
    (_, _, _, grads, loss), _ = lax.scan(tick, carry0, table)
    # the last stage lives on one rank: hand every pp rank the loss and
    # the stage-sharded grads stay local (stage s params live where s runs)
    loss = lax.psum(loss, axis)
    return loss, grads
