"""Causal pre-training of ``smallthinker`` (grouped-query attention in two
kinds of layer — windowed and rotated, global and position-free —, a
soft-max router that reads the layer's input, ReLU-gated routed experts)
through the system under test: the ``sdar_moe`` family's recipe — AdamW
over float32 master weights, bf16 autocast, one optimizer step per dispatch
of one ``jit.to_static`` step, every block recomputed in the backward pass,
the ``nemotron_h`` family's trainer and batches of token ids. See
``bert_pretrain.py`` for what a family file gives the job."""
from benchmark import smallthinker_costs
from benchmark.families.nemotron_h import _Trainer, host_batch  # noqa: F401
from benchmark.reference import smallthinker as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.smallthinker.SmallThinkerConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "moe_ffn_hidden_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "rope_layout", "sliding_window_layout", "sliding_window_size",
    "moe_num_primary_experts", "moe_num_primary_experts_published",
    "first_expert_held", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob", "rms_norm_eps",
    "initializer_range")


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return smallthinker_costs.train_flops_per_token(cfg, traffic["seq_len"])


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"smallthinker trains with AdamW, the "
                         f"configuration says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = SmallThinkerForCausalLM(SmallThinkerConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def smallthinker_step(ids):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(
        smallthinker_step, models=[model], optimizers=[o]),
        "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
