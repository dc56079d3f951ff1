"""Causal training of the ``keye_vl`` language model (grouped-query
attention over a learned selection of keys under three-axis rotary
positions, soft-max routed experts) in the sparse stage, through the
system under test: the ``nemotron_h`` family's recipe — AdamW over float32
master weights, bf16 autocast, one optimizer step per dispatch of one
``jit.to_static`` step, every block recomputed in the backward pass, that
family's trainer — with batches of (ids, position ids, label weights) and
the loss of ``KeyeVL2ForCausalLM.loss``: the language-model loss plus every
layer's indexer loss. See ``bert_pretrain.py`` for what a family file
gives the job."""
import numpy as np

from benchmark import keye_vl_costs
from benchmark.families.nemotron_h import _Trainer
from benchmark.reference import keye_vl as reference

THROUGHPUT = "tokens_per_s_chip"

# the configuration's keys that models.keye_vl.KeyeVL2TextConfig takes
_CONFIG_KEYS = (
    "vocab_size", "hidden_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "rope_theta", "rope_scaling", "num_experts",
    "num_experts_published", "first_expert_held", "num_experts_per_tok",
    "norm_topk_prob", "rms_norm_eps", "initializer_range", "sa_config")


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"] * traffic["seq_len"]


def flops_per_unit(cfg, traffic):
    return keye_vl_costs.train_flops_per_token(cfg, traffic["seq_len"])


def span_starts(traffic, rng):
    """Where the image spans start: multiples of 512 (or of a span's
    length, if that is shorter) drawn without overlap."""
    seq, n = traffic["seq_len"], traffic["image_spans"]
    length = traffic["image_grid"][0] * traffic["image_grid"][1]
    slot = min(512, length)
    per_span, slots = -(-length // slot), seq // slot
    free = slots - n * per_span
    if free < 0:
        raise SystemExit(f"{n} spans of {length} do not fit {seq} positions")
    first = np.sort(rng.choice(free + n, size=n, replace=False))
    return [int(c + i * (per_span - 1)) * slot for i, c in enumerate(first)]


def position_ids(traffic, starts):
    """int32 [3, seq]: Qwen2-VL's ``get_rope_index``. Text advances all
    three ids by one; a span of ``gh x gw`` that starts at counter ``c``
    has ``(c, c + row, c + col)`` and the counter resumes at ``c + max(gh,
    gw)``. Also bool [seq]: which positions lie in a span."""
    seq, (gh, gw) = traffic["seq_len"], traffic["image_grid"]
    ids = np.zeros((3, seq), np.int32)
    inside = np.zeros(seq, bool)
    row, col = (a.reshape(-1) for a in np.meshgrid(
        np.arange(gh), np.arange(gw), indexing="ij"))
    at, counter = 0, 0
    for start in list(starts) + [seq]:
        text = start - at
        ids[:, at:start] = counter + np.arange(text)
        counter += text
        if start < seq:
            span = slice(start, start + gh * gw)
            ids[:, span] = counter + np.stack([0 * row, row, col])
            inside[span] = True
            counter += max(gh, gw)
            at = start + gh * gw
    return ids, inside


def host_batch(cfg, traffic, rng):
    """(ids, position ids [3, seq], label weights): ids uniform over the
    slice of the vocabulary held here, no document boundaries; the image
    spans' starts from the seed; weight 0 where the NEXT position lies in a
    span (a span's rows are embedding rows and predict nothing) and at the
    last position, 1 elsewhere."""
    rows = traffic["batch_per_chip"] * traffic["chips"]
    seq = traffic["seq_len"]
    ids = rng.integers(0, cfg["vocab_size"], (rows, seq), dtype=np.int32)
    at, inside = position_ids(traffic, span_starts(traffic, rng))
    weights = np.append(~inside[1:], False).astype(np.float32)
    return ids, at, np.broadcast_to(weights, (rows, seq)).copy()


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, monitor, optimizer as opt
    from paddle_tpu.models.keye_vl import (KeyeVL2ForCausalLM,
                                           KeyeVL2TextConfig)

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "AdamW":
        raise SystemExit(f"keye_vl trains with AdamW, the configuration "
                         f"says {hyper['name']!r}")
    pt.seed(0)
    monitor.device_counters.reset()     # a run's counters are its trainer's
    model = KeyeVL2ForCausalLM(KeyeVL2TextConfig(
        recompute=traffic.get("recompute", True),
        **{k: cfg[k] for k in _CONFIG_KEYS}))
    o = opt.AdamW(learning_rate=hyper["learning_rate"], beta1=hyper["beta1"],
                  beta2=hyper["beta2"], epsilon=hyper["epsilon"],
                  weight_decay=hyper["weight_decay"],
                  parameters=model.parameters())

    def keye_step(ids, position_ids, weights):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits, indexer_loss = model(ids, position_ids)
        loss = model.loss(logits.astype("float32"), ids, weights,
                          indexer_loss)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # AdamW's moment1 after one step is (1 - beta1) * g
    trainer = _Trainer(model, o, jit.to_static(keye_step, models=[model],
                                               optimizers=[o]),
                       "moment1", 1.0 / (1.0 - hyper["beta1"]))
    trainer.load(weights)
    return trainer
