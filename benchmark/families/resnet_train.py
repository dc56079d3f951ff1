"""ResNet training through the system under test, as ``bench.py`` sets it
up: ``models.resnet`` in NCHW, Momentum, bf16 autocast, uint8 input
normalised on the device, one optimizer step per dispatch. See
``bert_pretrain.py`` for what a family file gives the job."""
import numpy as np

from benchmark import kernel_costs
from benchmark.families.trainer import Trainer
from benchmark.reference import resnet_train as reference

THROUGHPUT = "images_per_s_chip"


def units_per_step(traffic):
    return traffic["batch_per_chip"] * traffic["chips"]


def flops_per_unit(cfg, traffic):
    if cfg["depths"] != [3, 4, 6, 3] or traffic["image_size"] != 224:
        raise SystemExit("the flop count kept with the benchmark is "
                         "ResNet-50's at 224 x 224")
    return kernel_costs.RESNET50_TRAIN_FLOPS_PER_IMAGE


def host_batch(cfg, traffic, rng):
    """uint8 images [B, C, H, W] and labels; every row differs."""
    rows, size = units_per_step(traffic), traffic["image_size"]
    x = rng.integers(0, 256, (rows, cfg["in_channels"], size, size),
                     dtype=np.uint8)
    y = rng.integers(0, cfg["num_classes"], (rows,), dtype=np.int32)
    return x, y


def build(cfg, traffic, weights):
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, optimizer as opt
    from paddle_tpu.models.resnet import BottleneckBlock, ResNet

    hyper = cfg["assumed"]["optimizer"]
    if hyper["name"] != "Momentum":
        raise SystemExit(f"resnet_train trains with Momentum, the "
                         f"configuration says {hyper['name']!r}")
    if cfg["expansion"] != BottleneckBlock.expansion or \
            cfg["base_width"] != 64:
        raise SystemExit("models.resnet builds bottleneck blocks on a "
                         "base width of 64")
    pt.seed(0)
    model = ResNet(BottleneckBlock, cfg["depths"], cfg["num_classes"],
                   in_channels=cfg["in_channels"],
                   data_format=cfg["assumed"]["data_format"])
    o = opt.Momentum(learning_rate=hyper["learning_rate"],
                     momentum=hyper["momentum"],
                     parameters=model.parameters())

    def resnet_step(x, y):
        with amp.auto_cast(dtype=cfg["assumed"]["compute_dtype"]):
            logits = model((x.astype("float32") / 255.0 - 0.45) / 0.22)
        loss = pt.nn.functional.cross_entropy(logits.astype("float32"), y)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # Momentum's velocity after one step is g
    trainer = Trainer(model, o, jit.to_static(resnet_step, models=[model],
                                              optimizers=[o]),
                      "velocity", 1.0)
    trainer.load(weights)
    return trainer
