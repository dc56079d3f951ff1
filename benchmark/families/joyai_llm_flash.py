"""Causal pre-training of ``joyai_llm_flash`` (multi-head latent attention,
a dense gated MLP layer, routed gated experts, a multi-token-prediction
module) through the system under test: the ``nemotron_h`` family's recipe
— AdamW over float32 master weights, bf16 autocast, one optimizer step per
dispatch of one ``jit.to_static`` step, every block recomputed in the
backward pass, that family's token batches and trainer — with the two-term
loss of ``JoyAIFlashForCausalLM.loss``. See ``bert_pretrain.py`` for what a
family file gives the job."""
from benchmark import joyai_llm_flash_costs
from benchmark.families.nemotron_h import (_Trainer, host_batch,  # noqa: F401
                                           units_per_step)
from benchmark.reference import joyai_llm_flash as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.joyai_llm_flash.JoyAIFlashConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers",
    "num_nextn_predict_layers", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_theta", "rope_interleave", "n_routed_experts",
    "n_routed_experts_published", "first_expert_held", "n_shared_experts",
    "num_experts_per_tok", "first_k_dense_replace", "moe_layer_freq",
    "routed_scaling_factor", "rms_norm_eps", "initializer_range",
    "mtp_loss_weight")


def flops_per_unit(cfg, traffic):
    return joyai_llm_flash_costs.train_flops_per_token(cfg,
                                                       traffic["seq_len"])


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.joyai_llm_flash import (JoyAIFlashConfig,
                                                   JoyAIFlashForCausalLM)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"joyai_llm_flash trains with AdamW, the "
                         f"configuration says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def joyai_step(ids):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits, mtp_logits = model(ids)
        loss = model.loss(logits.astype("float32"),
                          mtp_logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(joyai_step, models=[model],
                                               optimizers=[o]),
                       "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
