"""Block-diffusion training of ``sdar_moe`` (grouped-query attention with
head norms and rotary positions under the block-diffusion structure,
routed gated experts behind a soft-max router) through the system under
test: the ``nemotron_h`` family's recipe — AdamW over float32 master
weights, bf16 autocast, one optimizer step per dispatch of one
``jit.to_static`` step, every block recomputed in the backward pass, that
family's trainer — with batches of (clean ids, noisy ids, loss weights)
and the loss of ``SDARMoEForBlockDiffusion.loss``. See ``bert_pretrain.py``
for what a family file gives the job."""
import numpy as np

from benchmark import sdar_moe_costs
from benchmark.families.nemotron_h import _Trainer
from benchmark.reference import sdar_moe as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.sdar_moe.SDARMoEConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "num_experts", "num_experts_published",
    "first_expert_held", "num_experts_per_tok", "norm_topk_prob",
    "rms_norm_eps", "initializer_range", "block_length", "mask_token_id")


def units_per_step(traffic):
    """DATA tokens a step: each goes through the stack twice, as a noisy
    and as a clean row."""
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return sdar_moe_costs.train_flops_per_token(cfg, traffic["seq_len"])


def host_batch(cfg, traffic, rng):
    """(clean ids, noisy ids, loss weights). Ids uniform over the slice of
    the vocabulary held here without its last row, which is the mask
    token; one noise level t a block of ``block_length`` positions,
    uniform over the traffic's range; each token of the block masked
    independently with probability t; weight 1 / t at a masked position,
    0 elsewhere."""
    rows = traffic["batch_per_chip"] * traffic["chips"]
    seq, block, mask_id = (traffic["seq_len"], cfg["block_length"],
                           cfg["mask_token_id"])
    clean = rng.integers(0, mask_id, (rows, seq), dtype=np.int32)
    t = np.repeat(rng.uniform(traffic["noise_level_min"],
                              traffic["noise_level_max"],
                              (rows, seq // block)), block, axis=1)
    masked = rng.random((rows, seq)) < t
    return (clean, np.where(masked, np.int32(mask_id), clean),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.sdar_moe import (SDARMoEConfig,
                                            SDARMoEForBlockDiffusion)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"sdar_moe trains with AdamW, the configuration "
                         f"says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = SDARMoEForBlockDiffusion(SDARMoEConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def sdar_step(clean, noisy, weights):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model(noisy, clean)
        loss = model.loss(logits.astype("float32"), clean, weights)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(sdar_step, models=[model],
                                               optimizers=[o]),
                       "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
