"""Causal pre-training of ``phi4_flash`` (a decoder-hybrid-decoder: Mamba-1
mixers and windowed differential attention, one full attention layer, then
gated memory units and cross attention that read ONE layer's scan memory
and ONE layer's keys and values; a tied head) through the system under
test: the ``lfm2_moe`` family's recipe — AdamW over float32 master weights,
bf16 autocast, one optimizer step per dispatch of one ``jit.to_static``
step, every block recomputed in the backward pass, the ``nemotron_h``
family's trainer and batches of token ids. See ``bert_pretrain.py`` for
what a family file gives the job."""
from benchmark import phi4_flash_costs
from benchmark.families.nemotron_h import _Trainer, host_batch  # noqa: F401
from benchmark.reference import phi4_flash as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.phi4_flash.Phi4FlashConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "sliding_window",
    "mb_per_layer", "layer_norm_eps", "mamba_d_state", "mamba_d_conv",
    "mamba_expand", "mamba_dt_rank", "layer_plan", "first_layer",
    "num_hidden_layers_published", "initializer_range", "lambda_std")


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return phi4_flash_costs.train_flops_per_token(cfg, traffic["seq_len"])


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.phi4_flash import (Phi4FlashConfig,
                                              Phi4FlashForCausalLM)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"phi4_flash trains with AdamW, the configuration "
                         f"says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = Phi4FlashForCausalLM(Phi4FlashConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def phi4_step(ids):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(
        phi4_step, models=[model], optimizers=[o]),
        "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
