"""paddle_tpu.ops.pallas — hand-fused TPU kernels.

TPU-native rebuild of the reference's fused CUDA kernels
(reference: paddle/fluid/operators/fused/fused_elemwise_activation_op.cu,
layer_norm_op.cu, softmax_with_cross_entropy_op.cu). Each kernel runs
compiled on TPU and in interpret mode on CPU (tests), and exposes a custom
VJP so the tape/jit path differentiates through it.
"""
import contextlib
import threading
import warnings

from ...device import is_tpu_backend


def interpret_mode():
    """Pallas ``interpret=`` for every kernel call: True only off-TPU
    (the CPU tests). On a ``tpu`` backend this is False without
    exception, so a kernel there is compiled or the call raises —
    nothing on that path can reach the interpreter."""
    return not is_tpu_backend()


def on_tpu():
    """True when the compiled-kernel path is live. Layers use this to
    auto-enable Pallas kernels on TPU while keeping CPU tests on the
    (fast) XLA path; interpret-mode tests opt in via force flags."""
    return not interpret_mode()


# Per-kernel default overrides: None = auto.
_overrides = {}
_KERNELS = ("layer_norm", "flash_attention", "softmax_xent", "batch_norm",
            "ssd_scan", "causal_conv1d", "gated_rms_norm",
            "moe_scatter_add", "gated_short_conv", "moe_grouped",
            "qk_heads", "dsa_select", "dsa_kl", "mla_heads",
            "selective_scan")

# Auto defaults from one builder-run v5e ablation (2026-07-31, superseded
# toolchain, not reproduced — docs/performance.md carries the table):
# layer_norm is the only unconditional win (+0.4%); softmax_xent loses
# 1.7% at seq-128 shapes (its value is the O(N·V) HBM saving, opt-in);
# flash_attention wins only once S^2 scores dominate — seq-gated via
# _flash_min_seq below. configure(kernel=True/False) still forces any
# of them either way.
# batch_norm: built to attack the ResNet trace's BN-bound 70% (same
# record), auto-off until scripts/bench_pallas_bn.py proves it
# beats the (already once-fixed) XLA schedule on the chip.
# There is no Adam kernel: XLA carries the update in the weight-gradient
# matmul's epilogue, which a Mosaic call cannot be (PERF.md section 6, PR 29).
# ssd_scan: on, measured on the v5e of this toolchain (PERF.md section 6,
# PR 28). At the nemotron cell's size (1 x 8,192 positions, 64 heads x 64
# in 8 groups, state 128, chunk 128, bfloat16) the kernel pair takes 0.57
# ms a layer forward and 1.68 backward where ops/ssm.py: _ssd took 3.9 and
# 11.1; in nemotron3_nano_30b_a3b.causal_pretrain F.ssd_scan fell from
# 59.9 to 12.2 ms of a step and the step from 337.9 to 301.4 ms
# (medians of five shared-seed pairs). Beside
# this switch the op looks at the call: shapes the tiles do not fit keep
# _ssd (ssd_scan.supported), as does everything off-TPU or under a mesh.
# causal_conv1d, gated_rms_norm: on, measured on the same v5e (PERF.md
# section 6, PR 30). Alone at the nemotron cell's size, bfloat16: the
# convolution (1 x 8,192 x 6,144, 4 taps, SiLU) 0.59 ms forward and 1.21
# backward where ops/ssm.py's _conv1d took 1.78 and 5.81; the gated norm
# (1 x 8,192 x 4,096 in 8 groups) 0.35 and 0.55 where ops/nn_ops.py's
# _rms_norm took 2.13 and 3.42. In nemotron3_nano_30b_a3b.causal_pretrain
# F.causal_conv1d fell from 26.05 to 9.88 ms of a step, the gated
# F.rms_norm from 21.58 to 4.17, the step from 301.4 to 262.2 ms (means
# of two shared-seed pairs). Each op looks at the call as ssd_scan does
# (causal_conv1d.supported, gated_rms_norm.supported; an F.rms_norm
# without a gate never asks).
# moe_scatter_add: on, measured on the same v5e (PERF.md section 6, PR
# 38). Alone at the sdar cell's size (16 held gated experts of width 768
# over 16,384 x 2,048 bfloat16 rows) F.moe_experts takes 4.6 ms forward
# and 11.0 forward + backward where XLA's scatter-add, which copies the
# float32 accumulator whole once an expert, took 7.6 and 17.2; in
# sdar_30b_a3b_chat.block_diffusion_8k the expert layers fell from 120.4
# to 74.8 ms of a step and the step from 562.0 to 516.2 ms. F.moe_experts
# looks at the call (moe_scatter_add.supported: d whole 128-lane tiles, a
# row tile for every rung); the kernel is a module-level jax.jit, so a
# step lowers it once a rung, not once a call site.
# moe_grouped: on, measured on the same v5e (PERF.md section 6, PR 44).
# F.moe_experts alone, a forward call + a forward-and-backward call, the
# per-expert ladder against the grouped path (one sort a layer, one
# grouped product a matrix and pass, a row tile of 256, the float32
# matrices cast block by block in VMEM): 32.4 -> 24.1 ms at the lfm2
# cell's shape (8 held experts 1,792 wide over 2 x 8,192 x 2,048 rows,
# ~20 k live), 15.1 -> 13.8 smallthinker, 15.7 -> 12.8 sdar, 10.0 -> 7.2
# joyai; the products alone 11.7 ms at lfm2's shape where jax's megablox
# takes 15.4 at its best tiling and lax.ragged_dot 19.7
# (scripts/tune_moe.py). In the cells: lfm2's step 292.6 -> 265.5 ms,
# joyai's 394.2 -> 380.6, sdar's 424.1 -> 410.8, smallthinker's 631.4 ->
# 616.1. F.moe_experts looks at the call (moe_grouped.supported: d and the
# experts' width whole 128-lane tiles - nemotron's 1,856 is not, and keeps
# the ladder); every kernel is a module-level jax.jit, one lowering a
# distinct shape.
# qk_heads: on, measured on the same v5e (PERF.md section 6, PR 45).
# F.qk_heads takes a projection's result [B, S, H D] to the flash kernels'
# [B, H, S, D] through the head norm and the half-split rotation in one
# pass each way. In sdar_30b_a3b_chat.block_diffusion_8k (16,384 rows x
# 32 + 4 heads of 128, norm + rotation, five layers) qk_heads_fwd reads
# 0.46 ms a layer's q and k (80 % of the HBM peak at one read and one write
# of 151 MB) and qk_heads_bwd 0.73 (76 % at three passes), 8.2 ms a step
# with the replays, where the chain of XLA fusions behind F.rms_norm,
# transpose and F.rotary_embedding took 45.1 ms on the q side alone (its
# slices, concatenations and pads at half a vreg's width are passes of
# their own, in float32); the attention layers fell from 294.1 to 256.6
# ms of a step and the step from 410.9 to 372.4. In
# smallthinker_21b_a3b.causal_pretrain_16k (28 + 4 heads, rotation alone,
# six window layers) 0.38 and 0.61 ms (85 % and 81 %), the window layers
# 286.8 -> 264.4 ms, the step 616.4 -> 595.1. The op looks at the call
# (qk_heads.supported: a head whole 128-lane tiles, rows whole row tiles,
# positions one a row; lfm2's heads of 64 keep the composition); both
# kernels are module-level jax.jits, one lowering a distinct shape and
# staging.
# mla_heads: on, measured on the same v5e (PERF.md section 6, PR 48).
# F.mla_heads takes a latent attention's q_b_proj and kv_b_proj results and
# the one rotary key head to the flash call's q, K [B, H, S, 192] and V
# [B, H, S, 128] in one pass each way: the interleaved rotation of each
# head's 64 rotary lanes (de-interleaved by a product with a 0/1 matrix in
# VMEM), the key head behind every head's k_nope, K split from V, the
# transposes in the index maps, the key head's gradient summed over the
# heads in VMEM. In joyai_llm_flash.causal_pretrain (8,192 rows x 32 heads
# of 128 + 64 | 128 + 128, six latent attentions, every block recomputed)
# mla_heads_fwd reads 0.80-0.89 ms a call and mla_heads_bwd 0.85 (504 MB a
# call, 570 as HBM stores the 192-wide heads in 256 lanes: 83 % of the HBM
# peak), 15.2 ms a step in eighteen calls, where the chain of XLA
# transposes, slices, rotations, the key head's broadcast and the
# concatenations took 57.5; the latent attentions fell from 242.6 to 200.0
# ms of a step and the step from 381.2 to 339.5 (tokens/s +12.2 %). The op
# looks at the call (mla_heads.supported: a rotary part of 64 lanes, nope
# and v whole 128-lane tiles, heads in pairs, rows whole row tiles); both
# kernels are module-level jax.jits.
_AUTO_ON = {"layer_norm": True, "flash_attention": True,
            "softmax_xent": False, "batch_norm": False, "ssd_scan": True,
            "causal_conv1d": True, "gated_rms_norm": True,
            "moe_scatter_add": True, "gated_short_conv": True,
            "moe_grouped": True, "qk_heads": True, "dsa_select": True,
            "dsa_kl": True, "mla_heads": True, "selective_scan": True}


# flash is an O(S^2)-score win: below some sequence length the XLA sdpa
# (one fused attention) beats the blocked kernel's overheads. The
# crossover (512; 0 = flash whenever enabled) was set on a superseded
# toolchain and has not been re-derived: no cell runs both paths at one
# length. What the benchmark measures on the v5e today (PERF.md §5-6,
# PR 40): at seq 512 (bert_base.pretrain_seq512, 16 x 12 heads x 512 x 64)
# the two flash kernels take 6.64 ms of a 64.0 ms step, 41.4 % of their
# roofline; at seq 128 the gate sends attention to sdpa (attention core
# 4.3 ms of 56.3). Whether sdpa would beat 6.64 ms at 512, or flash 4.3 ms
# at 128, is unmeasured.
_FLASH_MIN_SEQ_DEFAULT = 512
_flash_min_seq = _FLASH_MIN_SEQ_DEFAULT
_UNSET = object()


# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map"), so a
# jit.to_static step whose state or inputs span several devices traces
# with the AUTO defaults off — said once, out loud. shard_map trainers
# (parallel.megatron) call the kernels per shard and are not affected;
# configure(kernel=True) still forces a kernel, and lowering then raises.
_spmd = threading.local()


@contextlib.contextmanager
def gspmd_trace(n_devices):
    """Entered by jit.py around the trace of a step that spans
    ``n_devices`` > 1 devices."""
    if not getattr(_spmd, "depth", 0) and on_tpu() and any(
            _AUTO_ON[k] and _overrides.get(k) is None for k in _KERNELS):
        warnings.warn(
            f"Pallas kernels are off inside this compiled step: its state "
            f"spans {n_devices} devices under GSPMD, which cannot "
            f"partition a Mosaic kernel; the XLA paths are traced instead",
            stacklevel=3)
    _spmd.depth = getattr(_spmd, "depth", 0) + 1
    try:
        yield
    finally:
        _spmd.depth -= 1


def configure(flash_min_seq=_UNSET, **kernels):
    """configure(layer_norm=False, softmax_xent=None, ...) — override the
    auto default for named kernels ('layer_norm', 'flash_attention',
    'softmax_xent', 'batch_norm', 'ssd_scan', 'causal_conv1d',
    'gated_rms_norm', 'moe_scatter_add', 'gated_short_conv',
    'moe_grouped', 'qk_heads', 'dsa_select', 'dsa_kl', 'mla_heads',
    'selective_scan'); any
    other name raises
    ValueError. None restores auto.
    flash_min_seq=N routes sequences shorter than N to XLA sdpa even
    with the flash kernel enabled (N=0 disables the gate);
    flash_min_seq=None restores the measured default crossover,
    matching the kernel knobs' None-resets semantics.

    The flag is read when an op traces, so call configure() BEFORE the
    first jitted step — a step already compiled keeps the kernel choice
    it was traced with."""
    global _flash_min_seq
    if flash_min_seq is not _UNSET:
        _flash_min_seq = _FLASH_MIN_SEQ_DEFAULT \
            if flash_min_seq is None else int(flash_min_seq)
    for k, v in kernels.items():
        if k not in _KERNELS:
            raise ValueError(
                f"unknown pallas kernel {k!r}; known: {_KERNELS}")
        if v is None:
            _overrides.pop(k, None)
        else:
            _overrides[k] = bool(v)


def enabled(kernel, seq_len=None):
    """Effective default for one kernel, honoring configure() overrides
    (and the flash seq-length crossover when seq_len is given)."""
    if kernel not in _KERNELS:
        raise ValueError(
            f"unknown pallas kernel {kernel!r}; known: {_KERNELS}")
    v = _overrides.get(kernel)
    on = (on_tpu() and _AUTO_ON[kernel]
          and not getattr(_spmd, "depth", 0)) if v is None else v
    if on and kernel == "flash_attention" and seq_len is not None and \
            seq_len < _flash_min_seq:
        return False
    return on


from . import layer_norm as layer_norm_mod
from . import softmax_xent as softmax_xent_mod
from . import flash_attention as flash_attention_mod
from . import batch_norm as batch_norm_mod
from . import ssd_scan as ssd_scan_mod
from . import causal_conv1d as causal_conv1d_mod
from . import gated_rms_norm as gated_rms_norm_mod
from . import moe_scatter_add as moe_scatter_add_mod
from . import moe_grouped as moe_grouped_mod
from . import qk_heads as qk_heads_mod
from . import dsa as dsa_mod
from . import mla_heads as mla_heads_mod
from . import selective_scan as selective_scan_mod

from .layer_norm import layer_norm
from .softmax_xent import softmax_cross_entropy
from .flash_attention import flash_attention
from .batch_norm import fused_batch_norm_train
