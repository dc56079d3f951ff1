"""What a recomputed block keeps (PR 42). The flash kernels' vjp-forward
rules leave o and the two statistic rows under names
(``ops/pallas/flash_attention.py: RESULT_NAMES``), and ``jit.recompute``
without a policy keeps those names (``memory_plan.KERNEL_RESULTS``): the
backward replays a block without its forward flash kernel. ``"full"`` and
``"dots"`` mean what they meant, a block with no flash call traces what it
traced, and outside a checkpoint a name changes no program. Counts, bits and
texts, in interpret mode on the CPU: no device number."""
import functools
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import jit, memory_plan, monitor, nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import flash_attention
from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod

HEADS, HEAD, SEQ, BLOCKS = 2, 64, 256, 3
# what the block's attention is: the three custom_vjps of the flash module,
# forced through their kernels, and plain XLA attention (no flash call)
KINDS = {
    "flash": dict(causal=True, force=True),
    "flash_win": dict(causal=True, window=128, force=True),
    "flash_bd": dict(diffusion_block=32, force=True),
    "no_flash": None,
}
FORWARD_KERNELS = ("flash_fwd", "flash_win_fwd", "flash_bd_fwd")


class _Block(nn.Layer):
    def __init__(self, kind):
        super().__init__()
        width = HEADS * HEAD
        self.kind = kind
        self.qkv = nn.Linear(width, 3 * width, bias_attr=False)
        self.out = nn.Linear(width, width, bias_attr=False)

    def forward(self, x):
        rows = x.shape[1]
        q, k, v = (t.reshape([1, rows, HEADS, HEAD]).transpose([0, 2, 1, 3])
                   for t in self.qkv(x).split(3, axis=-1))
        if KINDS[self.kind] is None:
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                               training=False)
        else:
            o = flash_attention(q, k, v, **KINDS[self.kind])
        o = o.transpose([0, 2, 1, 3]).reshape([1, rows, HEADS * HEAD])
        return x + self.out(o)


def _kernel_calls(jaxpr):
    """name -> how many times the program CALLS the kernel: a jaxpr that
    several equations share through an inner ``jax.jit`` is entered once an
    equation (``monitor.xla.count_pallas`` enters it once: instances)."""
    calls = {}
    todo = [getattr(jaxpr, "jaxpr", jaxpr)]
    while todo:
        for eqn in todo.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                calls[name] = calls.get(name, 0) + 1
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        todo.append(sub)
    return calls


_MAKE_ENTRY = jit.StaticFunction._make_entry


def _step(monkeypatch, kind, policy):
    """(jaxpr, loss and gradients) of one compiled step over three blocks of
    ``kind``: under ``jit.recompute`` with ``policy`` (None: no policy
    named), or called plainly where ``policy`` is "none"."""
    seen = []

    def make_entry(self, *args, **kwargs):
        entry = _MAKE_ENTRY(self, *args, **kwargs)
        jitted = entry["jitted"]

        def run(state, arrays):
            seen.append(jitted.trace(state, arrays).jaxpr)
            return jitted(state, arrays)
        entry["jitted"] = run
        return entry

    monkeypatch.setattr(jit.StaticFunction, "_make_entry", make_entry)
    pt.seed(7)
    blocks = nn.LayerList([_Block(kind) for _ in range(BLOCKS)])
    key = jax.random.key(3)
    for i, p in enumerate(blocks.parameters()):
        p.set_value(0.05 * jax.random.normal(jax.random.fold_in(key, i),
                                             tuple(p.shape)))

    def step(x):
        h = x
        for block in blocks:
            h = block(h) if policy == "none" \
                else jit.recompute(block, h, policy=policy)
        loss = (h * h).mean()
        loss.backward()
        return [loss] + [p.grad for p in blocks.parameters()]

    x = jax.random.normal(jax.random.key(11), (1, SEQ, HEADS * HEAD))
    out = jit.to_static(step, models=[blocks], optimizers=[])(pt.to_tensor(x))
    return seen[0], [np.asarray(t.numpy()) for t in out]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_default_policy_replays_a_block_without_its_forward_kernel(
        monkeypatch, kind):
    """Three blocks: under ``"full"`` the step calls a forward kernel twice
    a block (the second time only to rebuild o, m and l), under the default
    once, and the gradients of the two and of a step without any checkpoint
    are the same bits. A block with no flash call traces one jaxpr under
    both."""
    kept, got = _step(monkeypatch, kind, None)
    full, want = _step(monkeypatch, kind, "full")
    _, plain = _step(monkeypatch, kind, "none")

    def forward_calls(jaxpr):
        calls = _kernel_calls(jaxpr)
        return sum(calls.get(name, 0) for name in FORWARD_KERNELS)

    if KINDS[kind] is None:
        assert forward_calls(full) == forward_calls(kept) == 0
        # the same equations inside and around the checkpoints, which
        # print their policy
        policy_out = functools.partial(re.sub, r"policy=.*", "policy=")
        assert policy_out(str(kept)) == policy_out(str(full))
        assert str(kept).count("policy=") == BLOCKS
    else:
        assert forward_calls(full) == 2 * BLOCKS
        assert forward_calls(kept) == BLOCKS
        assert _kernel_calls(kept) == {
            name: BLOCKS for name in (kind + "_fwd", kind + "_bwd")}
        # what is lowered to Mosaic: the windowed kernels sit behind
        # module-level jits (one instance a form), the others are one
        # instance a call
        gone = 1 if kind == "flash_win" else BLOCKS
        assert monitor.xla.count_pallas(full)[0] \
            - monitor.xla.count_pallas(kept)[0] == gone
    assert len(got) == len(want) == len(plain) == 1 + 2 * BLOCKS
    for a, b, c in zip(got, want, plain):
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_the_named_policies_mean_what_they_meant():
    assert memory_plan.checkpoint_policy("full") is None
    assert memory_plan.checkpoint_policy(None) is None
    assert memory_plan.checkpoint_policy("dots") \
        is jax.checkpoint_policies.checkpoint_dots
    kept = memory_plan.checkpoint_policy(memory_plan.KERNEL_RESULTS)
    # one object a process: JAX keys a checkpoint's partial evaluation of
    # an inner jit by the policy's identity
    assert kept is memory_plan.checkpoint_policy(memory_plan.KERNEL_RESULTS)
    assert flash_mod.RESULT_NAMES == ("flash_out", "flash_stats")
    with pytest.raises(ValueError):
        memory_plan.checkpoint_policy("flash")
    # not a name of ``to_static(remat=)`` or of a layer rule
    with pytest.raises(ValueError):
        memory_plan.MemoryPolicy(remat=memory_plan.KERNEL_RESULTS)


# sha256 of the StableHLO that value-and-gradients of the flash custom_vjp
# lowers to for a TPU at BERT's seq-512 call (16 x 12 x 512 x 64 under a key
# mask), taken at this PR's parent, e256fce: outside a checkpoint a name is
# an identity and reaches no program
PARENT_STABLEHLO_SEQ512 = "3a18b0f395c710b4"


def _program_text(text):
    """The lowered text without what moves with a source line or with the
    number of equations traced: the kernels' serialized bodies (they carry
    the line numbers of flash_attention.py; digests in
    test_flash_block_diffusion.py hold their jaxprs) and the counter behind
    a private function's name."""
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "", text)
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


def test_outside_a_checkpoint_a_name_changes_no_program(monkeypatch):
    from paddle_tpu.ops import pallas as P
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    q = S((16, 12, 512, 64), bf)
    mask = S((16, 1, 1, 512), jnp.float32)
    bq, bk = flash_mod._blocks_that_fit(512, 64, 64, 2, 512, 1024)
    mode = flash_mod._mask_mode(mask.shape, 16, 12, 512, 512)

    def value_and_grads(q, k, v, mask):
        def loss(q, k, v):
            return jnp.sum(flash_mod._flash(
                q, k, v, flash_mod._canon_mask(mask), mode,
                jnp.zeros((2,), jnp.int32), False, None, bq, bk,
                0.0).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    traced = jax.jit(value_and_grads).trace(q, q, q, mask)
    assert str(traced.jaxpr).count("name[name=flash_") == 3
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert hashlib.sha256(_program_text(text).encode()).hexdigest()[:16] \
        == PARENT_STABLEHLO_SEQ512


def test_the_two_counters_count(monkeypatch):
    def read():
        return (monitor.snapshot("flash_attention").get(
                    "flash_attention.results_named", 0),
                {k.rsplit(".", 1)[1]: v for k, v in
                 monitor.snapshot("recompute.placed").items()})

    named, placed = read()
    _step(monkeypatch, "flash", None)
    _step(monkeypatch, "no_flash", "full")
    _step(monkeypatch, "no_flash", "dots")
    after, placed_after = read()
    gained = {k: placed_after[k] - placed.get(k, 0) for k in placed_after}
    # one a traced vjp-forward (three blocks, each traced once under its
    # checkpoint), one a checkpoint placed, by the policy's name
    assert after - named == BLOCKS
    assert {k: v for k, v in gained.items() if v} == {
        memory_plan.KERNEL_RESULTS: BLOCKS, "full": BLOCKS, "dots": BLOCKS}
