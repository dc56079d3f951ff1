"""BERT pre-training through the system under test: the model, the
optimizer and the one compiled step of a user's loop, as ``chip_smoke.py``
and ``bench.py`` set them up (AdamW, bf16 autocast, f32 master weights,
one optimizer step per dispatch).

A family file gives the job what belongs to one kind of model:

    THROUGHPUT           name of the cell's throughput metric
    units_per_step       tokens (or images) one step consumes on all chips
    flops_per_unit       model flops per unit, for the MFU printed beside
    host_batch           one seeded host batch, as numpy arrays
    build                the trainer: ``step(*tensors) -> loss``, and how to
                         read its parameters and its first gradient back
"""
import numpy as np

from benchmark import kernel_costs
from benchmark.reference import bert_pretrain as reference
from benchmark.families.trainer import Trainer

THROUGHPUT = "tokens_per_s_chip"

_CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "intermediate_size",
                "hidden_dropout_prob", "attention_probs_dropout_prob",
                "max_position_embeddings", "type_vocab_size",
                "layer_norm_eps")


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return kernel_costs.transformer_train_flops_per_token(
        kernel_costs.bert_matmul_params(cfg), cfg["num_hidden_layers"],
        cfg["hidden_size"], traffic["seq_len"])


def host_batch(cfg, traffic, rng):
    """ids, segment ids, masked-LM labels (-1 = not masked, 15 % masked)
    and next-sentence labels; every row differs."""
    rows = traffic["batch_per_chip"] * traffic["chips"]
    seq, vocab = traffic["seq_len"], cfg["vocab_size"]
    ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
    split = rng.integers(1, seq, (rows, 1))
    types = (np.arange(seq)[None, :] >= split).astype(np.int32)
    mlm = np.where(rng.random((rows, seq)) < traffic["mlm_probability"],
                   rng.integers(0, vocab, (rows, seq)), -1).astype(np.int32)
    nsp = rng.integers(0, 2, (rows,), dtype=np.int32)
    return ids, types, mlm, nsp


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, optimizer as opt
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"bert_pretrain trains with AdamW, the "
                         f"configuration says {hyper['name']!r}")
    pt.seed(0)
    model = BertForPretraining(BertConfig(**{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def bert_step(ids, types, mlm, nsp):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits, nsp_logits = model(ids, types)
        loss = model.loss(logits.astype("float32"),
                          nsp_logits.astype("float32"), mlm, nsp)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = Trainer(model, o, jit.to_static(bert_step, models=[model],
                                              optimizers=[o]),
                      "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
