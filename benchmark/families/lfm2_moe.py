"""Causal pre-training of ``lfm2_moe`` (gated short-convolution layers
beside grouped-query attention, a dense layer and then sigmoid-routed
gated experts, a tied head) through the system under test: the
``smallthinker`` family's recipe — AdamW over float32 master weights, bf16
autocast, one optimizer step per dispatch of one ``jit.to_static`` step,
every block recomputed in the backward pass, the ``nemotron_h`` family's
trainer and batches of token ids (``batch_per_chip`` sequences a step).
See ``bert_pretrain.py`` for what a family file gives the job."""
from benchmark import lfm2_costs
from benchmark.families.nemotron_h import _Trainer, host_batch  # noqa: F401
from benchmark.reference import lfm2_moe as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.lfm2.Lfm2MoeConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "layer_types", "first_layer",
    "num_attention_heads", "num_key_value_heads", "rope_theta", "norm_eps",
    "conv_L_cache", "conv_bias", "num_experts", "num_experts_published",
    "first_expert_held", "num_experts_per_tok", "norm_topk_prob",
    "use_expert_bias", "routed_scaling_factor", "initializer_range")


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return lfm2_costs.train_flops_per_token(cfg, traffic["seq_len"])


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"lfm2_moe trains with AdamW, the configuration "
                         f"says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def lfm2_step(ids):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(
        lfm2_step, models=[model], optimizers=[o]),
        "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
