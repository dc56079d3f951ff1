"""CTR training end-to-end: the parameter-server workflow, TPU-style.

    python examples/ctr_sharded.py

Covers the full fluid PS-era user journey rebuilt on a mesh:
  * fluid.dataset (DatasetFactory -> InMemoryDataset) parsing MultiSlot
    text files, load_into_memory + local_shuffle,
  * static Program + Executor.train_from_dataset over those batches,
  * then the dygraph/fleet version: WideDeep with its embedding tables
    row-sharded over the mesh's mp axis (the PS replacement,
    parallel/embedding.ShardedEmbedding), AdamW, compiled step.

reference: fluid/incubate/fleet/parameter_server +
python/paddle/fluid/dataset.py CTR examples.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np

import paddle_tpu as pt
from paddle_tpu import fluid, optimizer, static, jit


def write_multislot(path, n=512, fields=8, dense=4, vocab=1000):
    """label-free MultiSlot lines: ids slot, dense slot, label slot."""
    rng = np.random.RandomState(0)
    w = rng.randn(fields)
    with open(path, "w") as fh:
        for _ in range(n):
            ids = rng.randint(0, vocab, fields)
            d = rng.rand(dense)
            y = int((w[ids % fields].sum() + d.sum()) > fields * 0.45)
            fh.write(f"{fields} " + " ".join(map(str, ids)) +
                     f" {dense} " + " ".join(f"{v:.4f}" for v in d) +
                     f" 1 {y}\n")


def static_train_from_dataset(train_file):
    print("== static: Executor.train_from_dataset over fluid.dataset ==")
    pt.enable_static()
    try:
        prog, startup = static.Program(), static.Program()
        with static.program_guard(prog, startup):
            ids = static.data("ids", [None, 8], "int64")
            dense = static.data("dense", [None, 4], "float32")
            label = static.data("label", [None, 1], "float32")
            emb = fluid.layers.embedding(ids, (1000, 8))
            feat = fluid.layers.concat(
                [fluid.layers.reshape(emb, [-1, 64]), dense], axis=1)
            h = fluid.layers.fc(feat, size=32, act="relu")
            logit = fluid.layers.fc(h, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.sigmoid_cross_entropy_with_logits(
                    logit, label))
            optimizer.Adam(learning_rate=1e-2).minimize(loss)

        class V:
            def __init__(self, name, dtype):
                self.name, self.dtype = name, dtype
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(64)
        ds.set_filelist([train_file])
        ds.set_use_var([V("ids", "int64"), V("dense", "float32"),
                        V("label", "float32")])
        ds.load_into_memory()
        ds.local_shuffle()
        exe = static.Executor()
        exe.run(startup)
        for epoch in range(4):
            exe.train_from_dataset(prog, ds, fetch_list=[loss])
            out, = exe.run(prog, feed=next(iter(ds._batches())),
                           fetch_list=[loss])
            print(f"  epoch {epoch}: loss={float(out):.4f}")
    finally:
        pt.disable_static()


def fleet_sharded_widedeep():
    print("== fleet: WideDeep, embedding row-sharded over mp ==")
    from paddle_tpu.models.ctr import WideDeep
    from paddle_tpu.parallel.fleet import Fleet, DistributedStrategy

    pt.seed(0)
    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = {"dp": 2, "mp": 2}
    fleet.init(strategy=st)
    model = WideDeep(sparse_feature_number=10000, sparse_num_field=8,
                     dense_feature_dim=4, embedding_size=8,
                     layer_sizes=(32, 32), sharded=True)
    model = fleet.distributed_model(model)
    print("  table sharding:",
          model.embedding.table.weight.data.sharding.spec)
    o = fleet.distributed_optimizer(optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters()))

    def step(ids, dense, label):
        loss = model.loss(model(ids, dense), label)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    cstep = jit.to_static(step, models=[model], optimizers=[o])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 10000, (32, 8)).astype("i4")
    dense = rng.rand(32, 4).astype("f4")
    label = rng.randint(0, 2, (32, 1)).astype("i4")
    t = fleet.shard_batch(pt.to_tensor(ids), pt.to_tensor(dense),
                          pt.to_tensor(label))
    for i in range(6):
        loss = cstep(*t)
        if i % 2 == 0:
            print(f"  step {i}: loss={float(loss.numpy()):.4f}")


def main():
    with tempfile.TemporaryDirectory() as tmp:
        train_file = os.path.join(tmp, "train.txt")
        write_multislot(train_file)
        static_train_from_dataset(train_file)
    fleet_sharded_widedeep()


if __name__ == "__main__":
    import jax
    if jax.device_count() < 4:
        raise SystemExit(
            f"ctr_sharded.py shards over a dp2 x mp2 mesh and needs 4 "
            f"devices; JAX found {jax.device_count()} "
            f"({jax.default_backend()}). On a CPU host: JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=4")
    main()
