"""paddle_tpu.optimizer — the optimizer suite.

TPU-native rebuild of the reference's optimizers
(reference: python/paddle/fluid/optimizer.py — SGD, Momentum, LarsMomentum,
Adagrad, DecayedAdagrad, Adadelta, Adam, Adamax, Lamb, RMSProp, Ftrl,
Dpsgd, ModelAverage, ExponentialMovingAverage, LookaheadOptimizer,
RecomputeOptimizer, PipelineOptimizer; and the C++ adam_op/momentum_op
kernels).

Design: each optimizer implements one pure `_rule(param, grad, slots, lr)`
over jnp arrays. In dygraph the rule runs eagerly per parameter; under
``jit.to_static`` the whole loop is traced into the train step, so XLA fuses
all parameter updates with the backward pass (the reference needs a fused
multi-tensor adam CUDA kernel for this; XLA's fusion gives it for free —
the Adam update rides the weight-gradient matmul's epilogue, see
adam_rule.py). Slot state lives in Tensors, so it is carried state for
to_static and checkpointable.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor, Parameter
from ..regularizer import WeightDecayRegularizer, L2Decay
from ..clip import ClipGradBase
from .. import monitor as _monitor
from ..resilience import guard as _rguard
from . import lr as lr_sched
from .adam_rule import adam_rule
from .lr import LRScheduler


class Optimizer:
    """Base optimizer (reference: optimizer.py:Optimizer)."""

    # flat-arena capability: subclasses that support the zero-copy flat
    # parameter arena (optimizer.arena) name their per-element slot
    # buffers here; None = unsupported (flat_arena=True raises)
    _arena_slots = None
    _arena_pows = ()

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 regularization=None, grad_sync=None, flat_arena=False):
        if parameters is not None and not isinstance(parameters,
                                                     (list, tuple)):
            parameters = list(parameters)
        self._parameter_list = list(parameters) if parameters else None
        self._arena = None
        self._flat_arena = False
        # memory_plan hooks: host offload of the arena's slot buffers
        # (memory_plan.attach_offload) and the bf16-view dtype the
        # arena binds inside traces (fp32 master weights)
        self._offloader = None
        self._arena_view_dtype = None
        if flat_arena:
            self.set_flat_arena(True)
        # gradient-sync scheduler (parallel.overlap): a mode string
        # ("exact"|"quantized"|"overlap") or a GradSyncScheduler. Under
        # GSPMD the grads reaching step() are already reduced, so at
        # this level the scheduler contributes lag-1 apply pipelining +
        # comm.* accounting; wire-level bucketed/quantized reduces live
        # in explicit-DDP loops (scheduler.reduce) and megatron.
        self._grad_sync = None
        if grad_sync is not None:
            self.set_grad_sync(grad_sync)
        self._grad_clip = grad_clip
        # weight_decay may be a float (L2) or a regularizer object
        wd = weight_decay if weight_decay is not None else regularization
        if isinstance(wd, (int, float)):
            wd = L2Decay(float(wd))
        self._regularization = wd
        self._lr_scheduler = None
        self._lr_decay = None
        from ..fluid.dygraph_lr import LearningRateDecay
        if isinstance(learning_rate, LRScheduler):
            self._lr_scheduler = learning_rate
            learning_rate._owner = self
            lr_value = learning_rate.last_lr
        elif isinstance(learning_rate, LearningRateDecay):
            # 1.x dygraph decay protocol: the OPTIMIZER calls the decay
            # each step (reference optimizer.py dygraph minimize path),
            # vs LRScheduler's user-driven scheduler.step().
            self._lr_decay = learning_rate
            # current-step value WITHOUT advancing, so get_lr() is right
            # before training. peek() — NOT step(): LinearLrWarmup's
            # step() calls a wrapped inner decay, which would advance
            # the inner schedule once before training ever starts.
            lr_value = float(learning_rate.peek())
        else:
            lr_value = float(learning_rate)
        # lr lives on device so compiled steps treat it as input state
        self._lr_tensor = Tensor(jnp.asarray(lr_value, jnp.float32),
                                 name="learning_rate")
        self._accumulators = {}  # id(param) -> {slot_name: Tensor}
        self._aux_state = {}     # scalar aux state (step counters etc.)

    # -- lr management ------------------------------------------------------
    def _set_lr_value(self, value):
        self._lr_tensor.data = jnp.asarray(value, jnp.float32)

    def set_lr(self, value):
        self._set_lr_value(value)

    def get_lr(self):
        if self._lr_scheduler is not None:
            return self._lr_scheduler.last_lr
        return float(jax.device_get(self._lr_tensor.data))

    @property
    def _learning_rate(self):
        return self._lr_tensor.data

    # -- slots --------------------------------------------------------------
    def _slot(self, param, name, init=None, shape=None, dtype=None):
        pid = id(param)
        slots = self._accumulators.setdefault(pid, {})
        if name not in slots:
            shape = shape if shape is not None else param.data.shape
            dtype = dtype or param.data.dtype
            value = jnp.zeros(shape, dtype) if init is None else jnp.full(
                shape, init, dtype)
            slots[name] = Tensor(value, name=f"{param.name}_{name}")
        return slots[name]

    # -- the per-parameter update rule (override) ---------------------------
    def _rule(self, p, g, slots, lr):
        raise NotImplementedError

    def _params(self):
        if self._parameter_list is None:
            raise ValueError(
                "optimizer constructed without `parameters`; pass "
                "parameters=model.parameters() (reference dygraph requires "
                "parameter_list too)")
        return self._parameter_list

    # -- apply --------------------------------------------------------------
    def step(self):
        """Apply one update from accumulated .grad (reference: dygraph
        minimize path in optimizer.py:Optimizer.apply_gradients)."""
        if _monitor.enabled():
            _monitor.counter(f"optimizer.step.{type(self).__name__}").inc()
        with _monitor.trace.span("optimizer.step",
                                 cls=type(self).__name__):
            self._step_body()

    def set_grad_sync(self, grad_sync):
        """Attach a gradient-sync scheduler (a mode string builds one
        over the registered mesh). See parallel.overlap."""
        from ..parallel.overlap import GradSyncScheduler
        if isinstance(grad_sync, str):
            if grad_sync == "exact":
                self._grad_sync = None
                return self
            grad_sync = GradSyncScheduler(mode=grad_sync)
        self._grad_sync = grad_sync
        return self

    # -- flat parameter arena ------------------------------------------------
    def set_flat_arena(self, enable=True):
        """Toggle the zero-copy flat parameter arena (optimizer.arena):
        one contiguous buffer per dtype holds every trainable param and
        its mirrored slot state, so the per-step path has no
        concat/split HBM traffic. Adam/AdamW only. Disabling dissolves
        the arena back into ordinary per-leaf slots (values preserved),
        so the knob can flip mid-training."""
        enable = bool(enable)
        if enable:
            if self._arena_slots is None:
                raise ValueError(
                    f"flat_arena is not supported by "
                    f"{type(self).__name__}; use Adam or AdamW")
            self._flat_arena = True
            # the arena itself builds lazily (_ensure_arena) once every
            # parameter has concrete data
        else:
            if self._arena is not None:
                a = self._arena
                a.sync_leaves()
                self._accumulators.pop(id(a), None)
                for p in self._params():
                    if id(p) in a.param_ids:
                        self._accumulators[id(p)] = a.leaf_slot_tensors(p)
                a.dissolve()
                self._arena = None
            self._flat_arena = False
        return self

    def _ensure_arena(self):
        """Build (or rebuild after a structure change) the flat arena
        over the current trainables, adopting any existing per-leaf slot
        values; registers the flat buffers as ONE accumulators entry so
        jit/Executor carry them as donated state."""
        from .arena import ParamArena
        trainables = [p for p in self._params() if not p.stop_gradient]
        if self._arena is not None:
            if self._arena.matches(trainables):
                if self._arena.needs_repack:
                    self._arena.repack_leaves()
                self._arena.view_dtype = self._arena_view_dtype
                return self._arena
            # membership/dtype changed: dissolve into per-leaf slots
            # first so the new arena adopts the live values
            self.set_flat_arena(False)
            self._flat_arena = True
        arena = ParamArena(trainables, slot_names=self._arena_slots,
                           pow_names=self._arena_pows,
                           adopt=self._accumulators)
        for p in trainables:
            self._accumulators.pop(id(p), None)
        self._accumulators[id(arena)] = arena.holders()
        arena.view_dtype = self._arena_view_dtype
        self._arena = arena
        return arena

    def _arena_apply(self, arena, packed, lr):
        """Apply the flat update for every packed dtype group (subclass
        hook — only arena-capable classes are reachable here)."""
        raise NotImplementedError

    def _step_body(self):
        if self._lr_decay is not None:
            # host-side schedule: advance + refresh the device lr tensor
            # (under jit the tensor is input state, so no retrace)
            self._set_lr_value(self._lr_decay())
        params_grads = [(p, p._grad) for p in self._params()
                        if not (p.stop_gradient or p._grad is None)]
        if self._grad_sync is not None:
            params_grads = self._grad_sync.process(params_grads)
            if params_grads is None:
                return  # lag-1 warm-up: this step's grads are in flight
        # reference order (optimizer.py:apply_gradients): clip raw grads
        # first, then append the regularization term. Per-param clips
        # (set_gradient_clip param_list) go first, then the optimizer's
        # own clip or the fluid-global strategy.
        per_param = []
        for p, g in params_grads:
            pc = getattr(p, "grad_clip", None)
            if pc is not None and g is not None:
                g = pc([(p, g)])[0][1]
            per_param.append((p, g))
        params_grads = per_param
        grad_clip = self._grad_clip
        if grad_clip is None:
            from ..clip import get_gradient_clip
            grad_clip = get_gradient_clip()
        if grad_clip is not None:
            params_grads = grad_clip(params_grads)
        regularized = []
        for p, g in params_grads:
            if g is None:
                regularized.append((p, g))
                continue
            reg = p.regularizer or self._regularization
            if isinstance(reg, WeightDecayRegularizer):
                g = g + reg.grad_term(p.data)
            regularized.append((p, g))
        params_grads = regularized
        lr = self._lr_tensor.data
        g = _rguard.active()
        if g is not None:
            # resilience NaN guard: snapshot / apply / where-select (the
            # AMP scaler scheme — jit-safe, so a to_static-fused train
            # step skips poisoned updates inside the compiled computation)
            finite = _rguard.guarded_apply(
                self, params_grads,
                lambda: self._apply_update(params_grads, lr))
            g.note_device_flag(finite, optimizer=self)
            return
        self._apply_update(params_grads, lr)

    def _apply_update(self, params_grads, lr):
        """The raw update: the flat arena's apply or the per-param
        _rule loop (split from step() so the resilience guard can
        bracket it with its snapshot/select machinery). Under an armed
        profiler the whole body runs inside a stable ``opt.<Cls>``
        named_scope, so monitor.profile can attribute the update math —
        one flag check when profiling is off."""
        if self._flat_arena and self._arena_slots is not None:
            arena = self._ensure_arena()
            # offload is an EAGER-path mechanism (the split step runs
            # the apply outside jit); inside a trace the transfers would
            # clobber tracers, so the hooks are gated on concrete buffers
            offload = self._offloader is not None and not arena.traced
            if offload:
                # wait for the H2D prefetch and rebind the moments
                # before the fused apply reads them
                self._offloader.collect(arena)
            # the grad pack (one ordered concat per dtype group) happens
            # OUTSIDE the opt.* scope — it is attributed to arena.pack,
            # and the opt.* region itself stays pure elementwise math
            packed = arena.pack_grads(params_grads)
            if packed is None:
                self._post_step()
                return
            if _monitor.profile.live and _monitor.profile.armed():
                with _monitor.profile.scope(
                        _monitor.profile.optimizer_scope(self)):
                    self._arena_apply(arena, packed, lr)
            else:
                self._arena_apply(arena, packed, lr)
            arena.finish_step()
            if offload:
                # page the just-updated moments out + start the next
                # prefetch; both overlap the next step's fwd/bwd
                self._offloader.page_out(arena)
            self._post_step()
            return
        if _monitor.profile.live and _monitor.profile.armed():
            with _monitor.profile.scope(
                    _monitor.profile.optimizer_scope(self)):
                return self._apply_update_body(params_grads, lr)
        return self._apply_update_body(params_grads, lr)

    def _apply_update_body(self, params_grads, lr):
        for p, g in params_grads:
            if g is None:
                continue
            self._pre_param(p)
            slots = self._accumulators.get(id(p), {})
            new_p, new_slots = self._rule(
                p.data, g, {n: t.data for n, t in slots.items()}, lr)
            p.data = new_p
            for n, v in new_slots.items():
                self._slot(p, n).data = v
        self._post_step()

    def _ensure_all_slots(self):
        """Create every accumulator eagerly (used by jit.to_static so slot
        Tensors exist before tracing rather than materializing as tracers).
        In flat-arena mode the arena's flat buffers ARE the accumulators —
        no per-leaf slots exist."""
        if self._flat_arena and self._arena_slots is not None:
            self._ensure_arena()
            return
        for p in self._params():
            if not p.stop_gradient:
                self._pre_param(p)

    def _pre_param(self, p):
        # ensure slots exist before _rule reads them
        pass

    def _post_step(self):
        pass

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        """reference dygraph semantics: grads already accumulated by
        loss.backward(); minimize applies them. In static mode the Program
        records this optimizer instead (see paddle_tpu.static)."""
        from ..dispatch import in_static_mode
        if in_static_mode():
            if self._lr_decay is not None:
                # the static Executor never calls step(), so the decay
                # would silently pin lr at its first value — the
                # reference raises for this lr type in static graphs too
                raise TypeError(
                    "1.x dygraph LearningRateDecay objects are "
                    "dygraph-only; in static mode use the functional "
                    "decays (fluid.layers.exponential_decay, ...) or an "
                    "optimizer.lr.LRScheduler")
            from ..static import record_optimizer
            return record_optimizer(self, loss)
        if loss is not None and loss._tape_node is not None and all(
                p._grad is None for p in self._params()
                if not p.stop_gradient):
            loss.backward()
        self.step()
        return None, None

    def clear_grad(self):
        for p in self._params():
            p.clear_gradient()

    clear_gradients = clear_grad

    # -- state dict ---------------------------------------------------------
    def state_dict(self):
        out = {"lr": self.get_lr()}
        names = {}
        named = [(p.name or f"param_{i}", p)
                 for i, p in enumerate(self._params())]
        if self._arena is not None:
            # emit standard per-leaf pname@slot views sliced from the
            # flat buffers — an arena checkpoint restores into a
            # per-leaf optimizer unchanged (and vice versa). Offloaded
            # moments come back device-resident first: the per-leaf
            # slicing needs settled arrays, and checkpoint exactness
            # requires the in-flight round trip to have landed.
            if self._offloader is not None:
                self._offloader.materialize(self._arena)
            self._arena.sync_leaves()
            out.update(self._arena.per_leaf_state(named))
        for pname, p in named:
            for sname, t in self._accumulators.get(id(p), {}).items():
                out[f"{pname}@{sname}"] = t
            names[pname] = p
        out["__aux__"] = dict(self._aux_state)
        if self._lr_scheduler is not None:
            out["__lr_sched__"] = self._lr_scheduler.state_dict()
        if self._lr_decay is not None:
            out["__lr_decay__"] = {"step_num": self._lr_decay.step_num}
        return out

    def set_state_dict(self, state):
        if self._flat_arena and self._arena_slots is not None:
            # build (or repack) the arena first so per-leaf checkpoint
            # slots scatter straight into the flat layout
            self._ensure_arena()
            if self._offloader is not None:
                self._offloader.materialize(self._arena)
        for i, p in enumerate(self._params()):
            pname = p.name or f"param_{i}"
            if self._arena is not None and id(p) in self._arena.param_ids:
                vals = {k.split("@", 1)[1]:
                        (v.data if isinstance(v, Tensor) else v)
                        for k, v in state.items()
                        if k.startswith(pname + "@")}
                if vals:
                    self._arena.load_leaf_state(p, vals)
                continue
            if not p.stop_gradient:
                self._pre_param(p)  # scalar slots (beta pows) get real shapes
            for key, value in state.items():
                if key.startswith(pname + "@"):
                    sname = key.split("@", 1)[1]
                    data = value.data if isinstance(value, Tensor) else value
                    slots = self._accumulators.setdefault(id(p), {})
                    if sname in slots:
                        slots[sname].set_value(data)
                    else:  # unknown slot: adopt the checkpoint's shape/dtype
                        arr = jnp.asarray(data)
                        self._slot(p, sname, shape=arr.shape,
                                   dtype=arr.dtype).set_value(arr)
        if "__aux__" in state:
            self._aux_state.update(state["__aux__"])
        if "__lr_sched__" in state and self._lr_scheduler is not None:
            self._lr_scheduler.set_state_dict(state["__lr_sched__"])
        if "__lr_decay__" in state and self._lr_decay is not None:
            self._lr_decay.step_num = state["__lr_decay__"]["step_num"]


# ---------------------------------------------------------------------------
# concrete rules

class SGD(Optimizer):
    """reference: optimizer.py:SGDOptimizer / sgd_op.cc"""

    def _rule(self, p, g, slots, lr):
        return p - lr * g, {}


class Momentum(Optimizer):
    """reference: MomentumOptimizer / momentum_op.cc"""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _pre_param(self, p):
        self._slot(p, "velocity")

    def _rule(self, p, g, slots, lr):
        v = self._momentum * slots["velocity"] + g
        if self._nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class DGCMomentum(Momentum):
    """reference: DGCMomentumOptimizer (deep gradient compression over
    NCCL rings). On TPU the all-reduce rides ICI inside the compiled step
    — sparsifying it would force gather/scatter HBM traffic that costs
    more than it saves — so this keeps DGC's momentum-correction update
    (momentum on the (virtually) compressed gradient, which for the
    identity sparsity equals plain momentum) and accepts the DGC
    signature for porting parity."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), parameters=None,
                 use_nesterov=False, num_trainers=None, **kw):
        super().__init__(learning_rate, momentum, parameters,
                         use_nesterov, **kw)
        self._rampup_begin_step = rampup_begin_step
        self._sparsity = list(sparsity)


DGCMomentumOptimizer = DGCMomentum


class LarsMomentum(Optimizer):
    """reference: LarsMomentumOptimizer / lars_momentum_op.cc — layer-wise
    adaptive rate scaling (large-batch training)."""

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay

    def _pre_param(self, p):
        self._slot(p, "velocity")

    def _rule(self, p, g, slots, lr):
        pn = jnp.sqrt(jnp.sum(jnp.square(p)))
        gn = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (pn > 0) & (gn > 0),
            lr * self._lars_coeff * pn / (gn + self._lars_wd * pn + 1e-12),
            lr)
        v = self._momentum * slots["velocity"] + local_lr * (
            g + self._lars_wd * p)
        return p - v, {"velocity": v}


class Adagrad(Optimizer):
    """reference: AdagradOptimizer / adagrad_op.cc"""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, parameters=None,
                 initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _pre_param(self, p):
        self._slot(p, "moment", init=self._init_acc)

    def _rule(self, p, g, slots, lr):
        m = slots["moment"] + g * g
        return p - lr * g / (jnp.sqrt(m) + self._eps), {"moment": m}


class DecayedAdagrad(Optimizer):
    """reference: DecayedAdagradOptimizer / decayed_adagrad_op.cc"""

    def __init__(self, learning_rate=0.001, decay=0.95, epsilon=1e-6,
                 parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._decay = decay
        self._eps = epsilon

    def _pre_param(self, p):
        self._slot(p, "moment")

    def _rule(self, p, g, slots, lr):
        m = self._decay * slots["moment"] + (1 - self._decay) * g * g
        return p - lr * g / (jnp.sqrt(m) + self._eps), {"moment": m}


class Adadelta(Optimizer):
    """reference: AdadeltaOptimizer / adadelta_op.cc"""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._eps = epsilon
        self._rho = rho

    def _pre_param(self, p):
        self._slot(p, "avg_squared_grad")
        self._slot(p, "avg_squared_update")

    def _rule(self, p, g, slots, lr):
        rho, eps = self._rho, self._eps
        asg = rho * slots["avg_squared_grad"] + (1 - rho) * g * g
        update = -jnp.sqrt((slots["avg_squared_update"] + eps) /
                           (asg + eps)) * g
        asu = rho * slots["avg_squared_update"] + (1 - rho) * update * update
        return p + lr * update, {"avg_squared_grad": asg,
                                 "avg_squared_update": asu}


class Adam(Optimizer):
    """reference: AdamOptimizer / adam_op.cc (incl. beta-pow accumulators).
    The update itself is :func:`adam_rule.adam_rule`, per leaf here and
    over the flat buffers in arena mode."""

    _arena_slots = ("moment1", "moment2")
    _arena_pows = ("beta1_pow", "beta2_pow")
    _wd = 0.0  # AdamW's decoupled decay

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, lazy_mode=False, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _pre_param(self, p):
        self._slot(p, "moment1")
        self._slot(p, "moment2")
        # float32 whatever the parameter's dtype: 0.999 is 1.0 in
        # bfloat16, and 1 - beta2_pow == 0 stops every update
        self._slot(p, "beta1_pow", init=1.0, shape=(), dtype=jnp.float32)
        self._slot(p, "beta2_pow", init=1.0, shape=(), dtype=jnp.float32)

    def _rule(self, p, g, slots, lr):
        b1p = slots["beta1_pow"] * self._beta1
        b2p = slots["beta2_pow"] * self._beta2
        new_p, m, v = adam_rule(
            p, g, slots["moment1"], slots["moment2"], lr, b1p, b2p,
            beta1=self._beta1, beta2=self._beta2, eps=self._eps,
            weight_decay=self._wd)
        return new_p, {"moment1": m, "moment2": v, "beta1_pow": b1p,
                       "beta2_pow": b2p}

    def _arena_apply(self, arena, packed, lr):
        """Flat-arena update: one adam_rule call per dtype group,
        reading/writing the arena buffers in place — no per-step
        gather/scatter over the param set. Beta-pow bias correction is
        shared per group (arena packing already warned if adopted pows
        disagreed)."""
        for grp, flat_g, mask in packed:
            m = grp.slots["moment1"]
            v = grp.slots["moment2"]
            b1p = grp.pows["beta1_pow"].data * jnp.asarray(
                self._beta1, grp.pows["beta1_pow"].data.dtype)
            b2p = grp.pows["beta2_pow"].data * jnp.asarray(
                self._beta2, grp.pows["beta2_pow"].data.dtype)
            new_p, new_m, new_v = adam_rule(
                grp.flat.data, flat_g, m.data, v.data, lr, b1p, b2p,
                beta1=self._beta1, beta2=self._beta2, eps=self._eps,
                weight_decay=self._wd, mask=mask)
            grp.flat.data = new_p
            m.data = new_m
            v.data = new_v
            grp.pows["beta1_pow"].data = b1p
            grp.pows["beta2_pow"].data = b2p


class AdamW(Adam):
    """Decoupled weight decay (reference: AdamW in later paddle; also the
    natural TPU formulation — decay fuses into the same update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         **kw)
        self._wd = float(weight_decay) if not isinstance(
            weight_decay, WeightDecayRegularizer) else weight_decay.coeff
        self._regularization = None  # decoupled — not added to grad


class Adamax(Optimizer):
    """reference: AdamaxOptimizer / adamax_op.cc"""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _pre_param(self, p):
        self._slot(p, "moment")
        self._slot(p, "inf_norm")
        self._slot(p, "beta1_pow", init=1.0, shape=())

    def _rule(self, p, g, slots, lr):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        b1p = slots["beta1_pow"] * b1
        m = b1 * slots["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * slots["inf_norm"], jnp.abs(g))
        new_p = p - lr / (1 - b1p) * m / (u + eps)
        return new_p, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Lamb(Optimizer):
    """reference: LambOptimizer / lamb_op.cc — layer-adaptive Adam for
    large-batch BERT training."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 exclude_from_weight_decay_fn=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _pre_param(self, p):
        self._slot(p, "moment1")
        self._slot(p, "moment2")
        self._slot(p, "beta1_pow", init=1.0, shape=())
        self._slot(p, "beta2_pow", init=1.0, shape=())
        self._current_param = p

    def _rule(self, p, g, slots, lr):
        b1, b2, eps = self._beta1, self._beta2, self._eps
        b1p = slots["beta1_pow"] * b1
        b2p = slots["beta2_pow"] * b2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * g * g
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        wd = self._wd
        if self._exclude_fn is not None and self._exclude_fn(
                self._current_param):
            wd = 0.0
        r = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        pn = jnp.sqrt(jnp.sum(jnp.square(p)))
        rn = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v,
                                    "beta1_pow": b1p, "beta2_pow": b2p}


class RMSProp(Optimizer):
    """reference: RMSPropOptimizer / rmsprop_op.cc"""

    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _pre_param(self, p):
        self._slot(p, "mean_square")
        self._slot(p, "momentum")
        if self._centered:
            self._slot(p, "mean_grad")

    def _rule(self, p, g, slots, lr):
        rho, eps = self._rho, self._eps
        ms = rho * slots["mean_square"] + (1 - rho) * g * g
        new_slots = {"mean_square": ms}
        if self._centered:
            mg = rho * slots["mean_grad"] + (1 - rho) * g
            denom = ms - mg * mg + eps
            new_slots["mean_grad"] = mg
        else:
            denom = ms + eps
        mom = self._momentum * slots["momentum"] + lr * g / jnp.sqrt(denom)
        new_slots["momentum"] = mom
        return p - mom, new_slots


class Ftrl(Optimizer):
    """reference: FtrlOptimizer / ftrl_op.cc"""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _pre_param(self, p):
        self._slot(p, "squared")
        self._slot(p, "linear")

    def _rule(self, p, g, slots, lr):
        l1, l2, lrp = self._l1, self._l2, self._lr_power
        sq = slots["squared"]
        new_sq = sq + g * g
        sigma = (jnp.power(new_sq, -lrp) - jnp.power(
            jnp.maximum(sq, 1e-30), -lrp)) / lr
        lin = slots["linear"] + g - sigma * p
        pre = jnp.power(new_sq, -lrp) / lr + 2 * l2
        x = l1 * jnp.sign(lin) - lin
        new_p = jnp.where(jnp.abs(lin) > l1, x / pre, 0.0)
        return new_p, {"squared": new_sq, "linear": lin}


class Dpsgd(Optimizer):
    """reference: DpsgdOptimizer / dpsgd_op.cc — differentially-private SGD
    (clip + gaussian noise)."""

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16,
                 sigma=1.0, parameters=None, **kw):
        super().__init__(learning_rate, parameters, **kw)
        self._clip = clip
        self._batch_size = batch_size
        self._sigma = sigma

    def _rule(self, p, g, slots, lr):
        from .. import random as prandom
        gn = jnp.sqrt(jnp.sum(jnp.square(g)))
        g = g / jnp.maximum(1.0, gn / self._clip)
        noise = jax.random.normal(prandom.next_key(), g.shape,
                                  g.dtype) * self._sigma * self._clip
        g = (g + noise) / self._batch_size
        return p - lr * g, {}


# ---------------------------------------------------------------------------
# meta-optimizers / wrappers

class ExponentialMovingAverage:
    """reference: optimizer.py:ExponentialMovingAverage — shadow weights with
    apply()/restore() context."""

    def __init__(self, decay=0.999, thres_steps=None):
        self._decay = decay
        self._shadow = {}
        self._backup = {}
        self._step = 0
        self._params = None

    def update(self, parameters=None):
        if parameters is not None:
            self._params = list(parameters)
        self._step += 1
        d = min(self._decay, (1 + self._step) / (10 + self._step))
        for p in self._params:
            pid = id(p)
            if pid not in self._shadow:
                self._shadow[pid] = p.data
            else:
                self._shadow[pid] = d * self._shadow[pid] + (1 - d) * p.data

    def apply(self, parameters=None):
        params = list(parameters) if parameters is not None else self._params
        for p in params:
            self._backup[id(p)] = p.data
            if id(p) in self._shadow:
                p.data = self._shadow[id(p)]
        return _EMAGuard(self, params)

    def restore(self, parameters=None):
        params = list(parameters) if parameters is not None else self._params
        for p in params:
            if id(p) in self._backup:
                p.data = self._backup.pop(id(p))


class _EMAGuard:
    def __init__(self, ema, params):
        self._ema, self._params = ema, params

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._ema.restore(self._params)


class ModelAverage(ExponentialMovingAverage):
    """reference: optimizer.py:ModelAverage — running average of weights over
    a window; same apply/restore protocol."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000):
        super().__init__(decay=0.0)
        self._sum = {}
        self._count = {}
        self._max_window = max_average_window

    def update(self, parameters=None):
        if parameters is not None:
            self._params = list(parameters)
        for p in self._params:
            pid = id(p)
            if pid not in self._sum or self._count[pid] >= self._max_window:
                self._sum[pid] = p.data
                self._count[pid] = 1
            else:
                self._sum[pid] = self._sum[pid] + p.data
                self._count[pid] += 1
            self._shadow[pid] = self._sum[pid] / self._count[pid]


class LookAhead:
    """reference: LookaheadOptimizer — slow/fast weights."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        self.inner = inner_optimizer
        self._alpha = alpha
        self._k = k
        self._step = 0
        self._slow = {}

    def step(self):
        self.inner.step()
        self._step += 1
        if self._step % self._k == 0:
            for p in self.inner._params():
                pid = id(p)
                slow = self._slow.get(pid, p.data)
                if pid not in self._slow:
                    self._slow[pid] = p.data
                    continue
                slow = slow + self._alpha * (p.data - slow)
                self._slow[pid] = slow
                p.data = slow

    def minimize(self, loss, **kw):
        if loss is not None and loss._tape_node is not None:
            loss.backward()
        self.step()

    def clear_grad(self):
        self.inner.clear_grad()

    clear_gradients = clear_grad


class RecomputeOptimizer:
    """reference: RecomputeOptimizer — gradient checkpointing. On TPU this
    is `jax.checkpoint` applied to the forward segments; use
    paddle_tpu.jit.recompute(fn) on the blocks to rematerialize, then train
    with the inner optimizer as usual."""

    def __init__(self, optimizer):
        self.inner = optimizer

    def __getattr__(self, item):
        return getattr(self.inner, item)


# fluid-era aliases (reference exports *Optimizer names)
SGDOptimizer = SGD
MomentumOptimizer = Momentum
LarsMomentumOptimizer = LarsMomentum
AdagradOptimizer = Adagrad
DecayedAdagradOptimizer = DecayedAdagrad
AdadeltaOptimizer = Adadelta
AdamOptimizer = Adam
AdamaxOptimizer = Adamax
LambOptimizer = Lamb
RMSPropOptimizer = RMSProp
FtrlOptimizer = Ftrl
DpsgdOptimizer = Dpsgd
LookaheadOptimizer = LookAhead


class PipelineOptimizer:
    """reference: optimizer.py:PipelineOptimizer — pipeline-parallel
    training. On TPU, pipeline parallelism is a mesh axis, not an optimizer
    wrapper: see paddle_tpu.parallel.megatron (GPipe microbatch ring over
    ppermute). This class keeps API parity and delegates stepping to the
    inner optimizer."""

    def __init__(self, optimizer, num_microbatches=1, **kw):
        self.inner = optimizer
        self.num_microbatches = num_microbatches

    def __getattr__(self, item):
        return getattr(self.inner, item)
