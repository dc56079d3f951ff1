"""Composed multi-axis training at BERT-base GEOMETRY through the
user-facing fleet API (VERDICT r3 #3).

Two mesh layouts on the 8-device CPU mesh:
  dp2 x pp2 x tp2 — 12x768 BERT (scaled seq/vocab), PipelineStack trunk,
    AdamW, dropout ON (exercises the RNG carry through the pp scan),
    flash-capable attention (XLA fallback off-TPU);
  dp2 x sp2 x ep2 — same geometry with MoE FFN layers sharded over ep
    and tokens sharded over (dp, sp).

reference: fleet collective DistributedStrategy + PipelineOptimizer
(python/paddle/fluid/incubate/fleet/collective/__init__.py,
fluid/optimizer.py)."""
import numpy as np
import pytest
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import nn, optimizer, jit
from paddle_tpu.models.bert import BertConfig, BertForPretraining
from paddle_tpu.parallel.fleet import Fleet, DistributedStrategy

BATCH, SEQ, VOCAB = 8, 64, 4096


def _base_cfg(**kw):
    # BERT-base geometry: 12 layers x 768 hidden x 12 heads x 3072 ffn.
    # seq/vocab scaled (the geometry is what stresses the shardings).
    d = dict(vocab_size=VOCAB, num_hidden_layers=12, hidden_size=768,
             num_attention_heads=12, intermediate_size=3072,
             max_position_embeddings=SEQ, use_recompute=True,
             use_flash_attention=True)
    d.update(kw)
    return BertConfig.base(**d)


def _data(rng_seed=0, batch=BATCH, seq=SEQ, vocab=VOCAB):
    rng = np.random.RandomState(rng_seed)
    ids = rng.randint(0, vocab, (batch, seq)).astype("i4")
    mlm = np.where(rng.rand(batch, seq) < 0.15,
                   rng.randint(0, vocab, (batch, seq)), -1).astype("i4")
    nsp = rng.randint(0, 2, (batch,)).astype("i4")
    return ids, mlm, nsp


def _train(model, fleet, steps, shard_tokens_over_sp=False,
           add_moe_aux=False):
    """Train `steps` on ONE batch; return (eval_before, train_losses,
    eval_after) — the eval losses are dropout-free, so fitting the batch
    must strictly reduce them (robust against dropout/Adam noise)."""
    # post-LN BERT at 12 layers diverges without warmup above ~1e-4;
    # 1e-5 memorizes the single batch monotonically
    o = fleet.distributed_optimizer(
        optimizer.AdamW(learning_rate=1e-5,
                        parameters=model.parameters()))

    def step(ids, mlm, nsp):
        logits, nsp_logits = model(ids)
        loss = model.loss(logits, nsp_logits, mlm, nsp)
        if add_moe_aux:
            loss = loss + nn.moe_aux_loss(model)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    def eval_loss(ids, mlm, nsp):
        logits, nsp_logits = model(ids)
        return model.loss(logits, nsp_logits, mlm, nsp)

    cstep = jit.to_static(step, models=[model], optimizers=[o])
    ceval = jit.to_static(eval_loss, models=[model], optimizers=[])
    ids, mlm, nsp = _data()
    if shard_tokens_over_sp:
        mesh = fleet.mesh
        tok = NamedSharding(mesh, P("dp", "sp"))
        row = NamedSharding(mesh, P("dp"))
        t = (pt.to_tensor(jax.device_put(ids, tok)),
             pt.to_tensor(jax.device_put(mlm, tok)),
             pt.to_tensor(jax.device_put(nsp, row)))
    else:
        t = fleet.shard_batch(pt.to_tensor(ids), pt.to_tensor(mlm),
                              pt.to_tensor(nsp))
    model.eval()
    before = float(ceval(*t).numpy())
    model.train()
    train_losses = [float(cstep(*t).numpy()) for _ in range(steps)]
    model.eval()
    after = float(ceval(*t).numpy())
    model.train()
    return before, train_losses, after


def test_composed_bert_base_dp_pp_tp_adamw_recompute():
    cfg = _base_cfg()
    pt.seed(7)
    model = BertForPretraining(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert n_params > 80e6  # genuinely base-sized trunk

    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = {"dp": 2, "pp": 2, "tp": 2}
    st.recompute = True  # per-stage jax.checkpoint inside the pp scan
    fleet.init(strategy=st)
    model.bert.encoder = fleet.pipeline_stack(list(model.bert.encoder))
    assert model.bert.encoder._stage_remat
    model = fleet.distributed_model(model)

    # trunk params stacked over pp AND column/row split over tp
    stk = model.bert.encoder
    qkv = stk._parameters["stk_attention__qkv__weight"]
    assert qkv.data.sharding.spec[0] == "pp"
    assert "tp" in jax.tree_util.tree_leaves(tuple(qkv.data.sharding.spec))

    before, losses, after = _train(model, fleet, steps=3)
    assert np.isfinite(losses).all(), losses
    assert after < before, (before, losses, after)


@pytest.mark.slow  # 91-96 s on the CPU (PR 29); tier-1 takes tests under 60 s
def test_composed_bert_base_dp_sp_ep_moe():
    cfg = _base_cfg(moe_num_experts=4, moe_every=3)
    pt.seed(7)
    model = BertForPretraining(cfg)
    assert any(l.moe is not None for l in model.bert.encoder)

    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = {"dp": 2, "sp": 2, "ep": 2}
    fleet.init(strategy=st)
    model = fleet.distributed_model(model)

    # expert-stacked weights live on the ep axis
    moe_layer = next(l for l in model.bert.encoder if l.moe is not None)
    assert moe_layer.moe.experts_w1.data.sharding.spec[0] == "ep"

    before, losses, after = _train(model, fleet, steps=3,
                                   shard_tokens_over_sp=True,
                                   add_moe_aux=True)
    assert np.isfinite(losses).all(), losses
    assert after < before, (before, losses, after)


def test_composed_model_checkpoint_roundtrip(tmp_path):
    """fleet.save_persistables / load_persistables on the COMPOSED model
    (pp-stacked trunk + MoE + optimizer slots): bit-exact restore with
    placements preserved (tiny scale; the geometry tests above cover
    scale)."""
    cfg = BertConfig.tiny(use_recompute=True, moe_num_experts=2,
                          moe_every=1, hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    pt.seed(11)
    model = BertForPretraining(cfg)
    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = {"dp": 2, "pp": 2, "tp": 2}
    st.recompute = True
    fleet.init(strategy=st)
    model.bert.encoder = fleet.pipeline_stack(list(model.bert.encoder))
    model = fleet.distributed_model(model)
    o = fleet.distributed_optimizer(
        optimizer.AdamW(learning_rate=1e-4,
                        parameters=model.parameters()))

    def step(ids, mlm, nsp):
        logits, nsp_logits = model(ids)
        loss = model.loss(logits, nsp_logits, mlm, nsp) + \
            nn.moe_aux_loss(model)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    cstep = jit.to_static(step, models=[model], optimizers=[o])
    ids, mlm, nsp = _data(batch=8, seq=32, vocab=cfg.vocab_size)
    t = fleet.shard_batch(pt.to_tensor(ids), pt.to_tensor(mlm),
                          pt.to_tensor(nsp))
    cstep(*t)

    ckpt = str(tmp_path / "composed_ckpt")
    fleet.save_persistables(dirname=ckpt, model=model, optimizer=o)
    before = {k: np.asarray(jax.device_get(v.data))
              for k, v in model.state_dict().items()}
    o_before = {k: np.asarray(jax.device_get(v.data))
                for k, v in _flat_opt_state(o).items()}
    loss_ref = float(cstep(*t).numpy())  # the step a resume must replay

    # clobber params AND optimizer slots, restore, compare bit-exact
    for p in model.parameters():
        p.data = p.data * 0.0
    for v in _flat_opt_state(o).values():
        v.data = v.data * 0.0
    fleet.load_persistables(dirname=ckpt, model=model, optimizer=o)
    for k, v in model.state_dict().items():
        np.testing.assert_array_equal(
            before[k], np.asarray(jax.device_get(v.data)), err_msg=k)
    for k, v in _flat_opt_state(o).items():
        np.testing.assert_array_equal(
            o_before[k], np.asarray(jax.device_get(v.data)), err_msg=k)
    stk = model.bert.encoder
    some = stk._parameters[stk._flat_names[0]]
    assert some.data.sharding.spec[0] == "pp"
    # dropout is off: the resumed step replays the reference step exactly
    loss_resumed = float(cstep(*t).numpy())
    np.testing.assert_allclose(loss_resumed, loss_ref, rtol=1e-6)


def _flat_opt_state(o):
    """name -> slot Tensor map for a (Distributed)Optimizer."""
    out = {}
    for pid, slots in o._accumulators.items():
        for sname, t in slots.items():
            out[f"{pid}.{sname}"] = t
    return out


def test_composed_ctr_sharded_embedding_dp_mp():
    """PS/CTR redesign at scale under the composed fleet stack
    (VERDICT r4 task 6): WideDeep AND DeepFM with 100k-row embedding
    tables row-sharded over mp (dp2 x mp2), AdamW; eval loss on the
    memorized batch must drop and the tables must actually carry
    P('mp', None). reference: fluid/incubate/fleet/parameter_server/
    distribute_transpiler/__init__.py."""
    from paddle_tpu.models.ctr import WideDeep, DeepFM

    rng = np.random.RandomState(0)
    batch, fields, dense_dim = 64, 26, 13
    ids = rng.randint(0, 100_000, (batch, fields)).astype("i4")
    dense = rng.rand(batch, dense_dim).astype("f4")
    label = rng.randint(0, 2, (batch, 1)).astype("i4")

    for cls in (WideDeep, DeepFM):
        pt.seed(0)
        fleet = Fleet()
        st = DistributedStrategy()
        st.mesh_shape = {"dp": 2, "mp": 2}
        fleet.init(strategy=st)
        model = cls(sparse_feature_number=100_000, sparse_num_field=fields,
                    dense_feature_dim=dense_dim, embedding_size=16,
                    layer_sizes=(64, 64), sharded=True)
        model = fleet.distributed_model(model)
        table = model.embedding.table if hasattr(model, "embedding") \
            else model.emb.table
        assert tuple(table.weight.data.sharding.spec)[0] == "mp"
        o = fleet.distributed_optimizer(
            optimizer.AdamW(learning_rate=1e-3,
                            parameters=model.parameters()))

        def step(ids, dense, label):
            loss = model.loss(model(ids, dense), label)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        cstep = jit.to_static(step, models=[model], optimizers=[o])
        t = fleet.shard_batch(pt.to_tensor(ids), pt.to_tensor(dense),
                              pt.to_tensor(label))
        losses = [float(cstep(*t).numpy()) for _ in range(6)]
        assert losses[-1] < losses[0], (cls.__name__, losses)
