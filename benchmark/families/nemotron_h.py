"""Causal pre-training of a ``nemotron_h`` hybrid (Mamba-2 mixers, routed
experts, grouped-query attention) through the system under test: the
BERT family's recipe — AdamW over float32 master weights, bf16 autocast,
one optimizer step per dispatch of one ``jit.to_static`` step — with every
block recomputed in the backward pass (``NemotronHConfig.recompute``), as
a model of this size is trained. See ``bert_pretrain.py`` for what a
family file gives the job."""
import numpy as np

from benchmark import nemotron_h_costs
from benchmark.families.trainer import Trainer
from benchmark.reference import nemotron_h as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.nemotron_h.NemotronHConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
    "time_step_min", "time_step_max", "time_step_floor",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "n_routed_experts_published", "first_expert_held",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "layer_norm_epsilon", "initializer_range", "rescale_prenorm_residual")


class _Trainer(Trainer):
    def reset(self, weights):
        """``Trainer.reset`` one array at a time: beside 10.7 GB of state
        the chip has no room for a second copy of both moments."""
        import jax.numpy as jnp
        self.load(weights)
        for name, slot in self.optimizer.state_dict().items():
            if "@" in name:
                slot.data = (jnp.ones_like if name.endswith("_pow")
                             else jnp.zeros_like)(slot.data)


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return nemotron_h_costs.train_flops_per_token(cfg, traffic["seq_len"])


def host_batch(cfg, traffic, rng):
    """Token ids, uniform over the slice of the vocabulary held here; no
    document boundaries. The labels are the ids, shifted in the step."""
    rows = traffic["batch_per_chip"] * traffic["chips"]
    return (rng.integers(0, cfg["vocab_size"], (rows, traffic["seq_len"]),
                         dtype=np.int32),)


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"nemotron_h trains with AdamW, the configuration "
                         f"says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = NemotronHForCausalLM(NemotronHConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def nemotron_step(ids):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(nemotron_step, models=[model],
                                               optimizers=[o]),
                       "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
