"""The compiled step with its state, and the public read-backs that the
correctness check needs. Shared by the family files."""
from benchmark.reference.common import leaf_norms


class Trainer:
    def __init__(self, model, optimizer, step, grad_slot, grad_scale):
        """``grad_slot`` is the optimizer slot that, after exactly one
        step, holds the first gradient divided by ``grad_scale``."""
        self.model, self.optimizer, self.step = model, optimizer, step
        self._slot, self._scale = grad_slot, grad_scale

    def load(self, weights):
        """Put the seed's arrays (named as the reference names them) into
        the model's parameters. Buffers keep their defaults."""
        params = dict(self.model.named_parameters())
        if set(params) != set(weights):
            raise SystemExit(
                "the benchmark's weights and the model's parameters differ: "
                f"{sorted(set(params) ^ set(weights))[:6]}")
        for name, holder in params.items():
            if tuple(holder.shape) != tuple(weights[name].shape):
                raise SystemExit(f"{name}: model {tuple(holder.shape)}, "
                                 f"benchmark {tuple(weights[name].shape)}")
            holder.set_value(weights[name])

    def reset(self, weights):
        """Back to the state of a fresh trainer that holds ``weights``:
        zero moments, unit beta powers. For control.py, which reads a dozen
        seeds through one compiled step."""
        import jax.numpy as jnp
        self.load(weights)
        state = self.optimizer.state_dict()
        self.optimizer.set_state_dict({
            k: (jnp.ones_like if k.endswith("_pow") else jnp.zeros_like)(
                v.data)
            for k, v in state.items() if "@" in k})

    def parameters(self):
        return {k: v.data for k, v in self.model.named_parameters()}

    def first_gradient_norms(self):
        """Norm of every leaf of the gradient the optimizer was handed in
        its first step, worked out from its state after that step."""
        import jax
        state = self.optimizer.state_dict()
        slot = {id(p): f"{p.name or f'param_{i}'}@{self._slot}"
                for i, p in enumerate(self.model.parameters())}
        norms = jax.device_get(jax.jit(leaf_norms)(
            {k: state[slot[id(p)]].data
             for k, p in self.model.named_parameters()}))
        return {k: float(v) * self._scale for k, v in norms.items()}
