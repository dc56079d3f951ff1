"""NemotronH on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/nemotron_h.py,
which imports nothing of paddle_tpu): the model's logits, loss and every
gradient, the mixer and the model through the stage kernels, grouped-query
attention through the flash kernels, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
The contract with the reference is tests/family_contract.py's; the scan,
convolution and norm alone are in tests/test_ssm_ops.py, the expert layer
and its counters in tests/test_routed_moe_layer.py.
"""
import functools
import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                     # noqa: E402
from paddle_tpu import monitor, nn                          # noqa: E402
from paddle_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                          NemotronHForCausalLM)
from paddle_tpu.nn import functional as F                   # noqa: E402
from benchmark.reference import nemotron_h as R             # noqa: E402
from family_contract import (Family, Reference,             # noqa: E402
                             check_matches_reference,
                             check_trains_through_to_static, ids,
                             plain as _plain)


_ids = functools.partial(ids, seq=21)


FAMILY = Family(R, NemotronHForCausalLM, NemotronHConfig.tiny,
                batch=lambda seed: (_ids(seed=seed),))


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        reference, recompute):
    seen = check_matches_reference(reference, recompute)
    assert len(R.compared_leaves(seen.cfg)) >= 16


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    # bf16 products against float32: the losses agree to bf16's rounding
    got, _ = check_trains_through_to_static(reference, rtol=2e-3)
    assert got[2] < got[0]


def test_pattern_and_config_are_checked():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(hybrid_override_pattern="MEX*")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(num_hidden_layers=5)
    assert len(NemotronHConfig().hybrid_override_pattern) == 52


# -- the mixer's convolution and gated norm as kernels -----------------------

# the tiny widths padded to what the kernels' tiles take: 128 positions,
# heads of 64 in two groups (a group of the norm is 128 lanes), state 32
# (the convolution runs over 256 + 2 * 2 * 32 = 384 channels)
_KERNEL_WIDTHS = dict(mamba_head_dim=64, ssm_state_size=32, n_groups=2)


def _with_stage_kernels(forced, run):
    """``run()`` with the two kernel pairs forced (interpret mode) or
    left to the registry (off on a CPU), and how many call sites traced
    each: ((conv kernel, conv XLA), (norm kernel, norm XLA))."""
    from paddle_tpu.ops import pallas as P

    def traced():
        conv, norm = (monitor.snapshot(k) for k in ("causal_conv1d",
                                                     "rms_norm"))
        return np.array([
            [conv.get("causal_conv1d.kernel_traced", 0),
             conv.get("causal_conv1d.xla_traced", 0)],
            [norm.get("rms_norm.gated_kernel_traced", 0),
             norm.get("rms_norm.gated_xla_traced", 0)]], int)

    if forced:
        P.configure(causal_conv1d=True, gated_rms_norm=True)
    try:
        before = traced()
        out = run()
        return out, (traced() - before).tolist()
    finally:
        P.configure(causal_conv1d=None, gated_rms_norm=None)


def test_mixer_gives_the_same_result_and_gradients_through_the_kernels():
    pt.seed(3)
    mixer = nn.Mamba2Mixer(64, 4, 64, 32, n_groups=2, chunk_size=32)
    u = np.random.default_rng(1).normal(size=(2, 128, 64)).astype(np.float32)
    probe = np.random.default_rng(2).normal(size=(2, 128, 64)).astype(
        np.float32)

    def run():
        x = pt.to_tensor(u)
        x.stop_gradient = False
        for p in mixer.parameters():
            p._grad = None
        y = mixer(x)
        (y * pt.to_tensor(probe)).sum().backward()
        return [y.numpy(), np.asarray(x._grad)] + [
            np.asarray(p._grad) for _, p in mixer.named_parameters()]

    plain, took = _with_stage_kernels(False, run)
    assert took == [[0, 1], [0, 1]]
    kernels, took = _with_stage_kernels(True, run)
    assert took == [[1, 0], [1, 0]]
    names = ["y", "u"] + [n for n, _ in mixer.named_parameters()]
    assert len(plain) == len(names) == 10
    for name, a, b in zip(names, plain, kernels):
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max(), name


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_gives_the_same_loss_and_gradients_through_the_kernels(
        reference, recompute):
    """The whole model (``ME`` at the padded widths), every block
    recomputed or not: the loss and every parameter's gradient with the
    convolution and the gated norm through their kernels are the portable
    paths', and both are the reference's."""
    model, cfg, _ = reference.model(recompute=recompute, num_hidden_layers=2,
                                    hybrid_override_pattern="ME",
                                    **_KERNEL_WIDTHS)
    ids = _ids(rows=2, seq=128)

    def run():
        for p in model.parameters():
            p._grad = None
        loss = model.loss(model(pt.to_tensor(ids)), pt.to_tensor(ids))
        loss.backward()
        return float(loss.numpy()), {n: np.asarray(p._grad)
                                     for n, p in model.named_parameters()}

    (plain_loss, plain), took = _with_stage_kernels(False, run)
    assert took == [[0, 1], [0, 1]]         # one mixer, traced once
    (loss, grads), took = _with_stage_kernels(True, run)
    assert took == [[1, 0], [1, 0]]
    assert abs(loss - plain_loss) < 1e-6
    want_loss, want = reference.loss_and_grad(cfg, (ids,))
    assert abs(loss - float(want_loss)) < 1e-5
    assert grads and set(grads) == set(want)
    for name, g in grads.items():
        scale = np.abs(plain[name]).max() + 1e-12
        assert np.abs(g - plain[name]).max() / scale < 2e-5, name
        ref = np.asarray(want[name])
        assert np.abs(g - ref).max() / (np.abs(ref).max() + 1e-12) < 5e-5, \
            name


# -- grouped-query attention ------------------------------------------------

def test_gqa_through_the_flash_kernels_at_head_size_128_causal():
    layer = nn.GroupedQueryAttention(64, 4, 2, 128, causal=True)
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(4), (1, 40, 64))))
    got = layer(x, force_flash=True)            # the kernels, interpreted
    q, k, v = (layer._heads(proj(x), 1, 40, count) for proj, count in (
        (layer.q_proj, 4), (layer.k_proj, 2), (layer.v_proj, 2)))
    plain = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    plain = layer.o_proj(plain.transpose([0, 2, 1, 3]).reshape([1, 40, 512]))
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-5)
    # off a TPU, and unforced, the dispatch takes that same path
    np.testing.assert_allclose(layer(x).numpy(), plain.numpy(), atol=1e-6)
    # and both are the reference's attention
    w = {k + ".weight": getattr(layer, k).weight.data
         for k in ("q_proj", "k_proj", "v_proj", "o_proj")}
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=128)
    want = R._attention(cfg, w, x.data[0], _plain)
    np.testing.assert_allclose(got.numpy()[0], want, atol=2e-5)
    with pytest.raises(ValueError, match="key/value"):
        nn.GroupedQueryAttention(64, 4, 3, 16)
