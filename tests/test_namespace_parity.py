"""Namespace-level parity: every reference __all__ name across
optimizer/initializer/metrics/clip/dygraph.nn/backward resolves, and the
newly added classes compute (reference: the corresponding fluid
modules)."""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer, metric, static, fluid


# Names that deliberately do NOT resolve, each with the reason. Keep
# this list short and honest — everything else in every reference
# __all__ must resolve (mechanical sweep below).
_PARITY_ALLOWLIST = {
    # none currently: CUDA-only surfaces (cuda_profiler,
    # load_op_library) resolve as explicit-error stubs that explain
    # their TPU replacement rather than being absent.
}


def _reference_all_names(path):
    """Every string literal inside list literals assigned/augmented to
    __all__ (covers `__all__ = [...]`, `__all__ = a.__all__ + [...]`,
    and `__all__ += [...]` — the dynamic `x.__all__` parts are covered
    by sweeping each submodule's own file)."""
    import ast
    try:
        tree = ast.parse(open(path, encoding="utf-8",
                              errors="replace").read())
    except SyntaxError:
        return []
    names = []

    def literals(node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.List):
                for e in sub.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, str):
                        names.append(e.value)

    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    target = node.value
        elif isinstance(node, ast.AugAssign):
            if isinstance(node.target, ast.Name) and \
                    node.target.id == "__all__":
                target = node.value
        if target is not None:
            literals(target)
    return names


def _resolve(dotted):
    """Import the longest importable prefix, then walk attributes."""
    import importlib
    parts = dotted.split(".")
    for k in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:k]))
        except ImportError:
            continue
        try:
            for p in parts[k:]:
                obj = getattr(obj, p)
            return obj
        except AttributeError:
            continue
    return None


_needs_reference = pytest.mark.skipif(
    not os.path.isdir("/root/reference/python/paddle"),
    reason="the reference tree (/root/reference) is not mounted here")


@_needs_reference
def test_every_reference_fluid_all_name_resolves():
    """Mechanical sweep (VERDICT r4 task 5): for EVERY module under
    reference fluid/, fluid/dygraph/, and fluid/layers/, each __all__
    name must resolve at the same module path in paddle_tpu — or at the
    parent package level, which is where reference users consume
    star-imported names (fluid.dygraph.nn.Conv2D is used as
    fluid.dygraph.Conv2D)."""
    import os

    ref_root = "/root/reference/python/paddle/fluid"
    sweeps = [(ref_root, "paddle_tpu.fluid"),
              (os.path.join(ref_root, "dygraph"),
               "paddle_tpu.fluid.dygraph"),
              (os.path.join(ref_root, "layers"),
               "paddle_tpu.fluid.layers")]
    missing = []
    checked = 0
    for base, target_pkg in sweeps:
        for fname in sorted(os.listdir(base)):
            if not fname.endswith(".py"):
                continue
            names = _reference_all_names(os.path.join(base, fname))
            if not names:
                continue
            mod_path = target_pkg if fname == "__init__.py" else \
                f"{target_pkg}.{fname[:-3]}"
            mod = _resolve(mod_path)
            parent = _resolve(target_pkg)
            for n in names:
                checked += 1
                if n in _PARITY_ALLOWLIST:
                    continue
                if (mod is not None and hasattr(mod, n)) or \
                        (parent is not None and hasattr(parent, n)):
                    continue
                missing.append(f"{mod_path}:{n}")
    assert checked > 500, f"sweep only found {checked} names — broken?"
    assert missing == [], f"{len(missing)} missing: {missing}"


# Reference-side __all__ defects (names the REFERENCE itself never
# defines), verified by reading the reference source:
_REFERENCE_ALL_BUGS = {
    # utils/__init__.py lists dump_config but no module defines it
    "dump_config",
    # dataset/conll05.py has __all__ = ['test, get_dict'] — one string
    # with a comma where two names were meant
    "test, get_dict",
}


def _reference_root_exports():
    """Names the reference re-exports at the bare `paddle` root (its
    __init__.py's top-level `from .x import y` statements): only THESE
    may satisfy the sweep at paddle_tpu's root — otherwise an unrelated
    top-level op (e.g. pt.split, the tensor op) would false-pass a
    same-named dataset/reader helper."""
    import ast
    tree = ast.parse(open("/root/reference/python/paddle/__init__.py",
                          encoding="utf-8", errors="replace").read())
    names = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                names.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                names.add((alias.asname or alias.name).split(".")[0])
    return names


@_needs_reference
def test_every_reference_toplevel_all_name_resolves():
    """Same mechanical sweep over the NON-fluid reference tree
    (python/paddle/**: tensor/, nn/, dataset/, reader/, distributed/,
    incubate/, utils/, ...). Resolution may land at an ancestor
    package — that is where the reference itself re-exports these for
    users (paddle.tensor.math.abs is consumed as paddle.abs) — but the
    bare paddle_tpu root only counts for names the reference root
    itself re-exports (see _reference_root_exports)."""
    import os

    ref_root = "/root/reference/python/paddle"
    root_ok = _reference_root_exports()
    missing = []
    checked = 0
    for dirpath, dirnames, files in os.walk(ref_root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("fluid", "tests", "libs", "proto")]
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fname), ref_root)
            names = _reference_all_names(os.path.join(dirpath, fname))
            if not names:
                continue
            mod = rel[:-3].replace(os.sep, ".")
            if mod.endswith(".__init__"):
                mod = mod[:-len(".__init__")]
            target = "paddle_tpu" + ("" if mod == "__init__"
                                     else "." + mod)
            parts = target.split(".")
            non_root = [_resolve(".".join(parts[:k]))
                        for k in range(len(parts), 1, -1)]
            root = _resolve(parts[0])
            for n in names:
                checked += 1
                if n in _REFERENCE_ALL_BUGS:
                    continue
                if any(o is not None and hasattr(o, n)
                       for o in non_root):
                    continue
                if mod == "__init__" or n in root_ok:
                    if root is not None and hasattr(root, n):
                        continue
                if n == parts[-1] and len(parts) > 1:
                    # reference pattern `module x defines x` (batch.py's
                    # batch): the parent-level attribute IS the name
                    parent = _resolve(".".join(parts[:-1]))
                    if parent is not None and hasattr(parent, n):
                        continue
                missing.append(f"{target}:{n}")
    assert checked > 300, f"sweep only found {checked} names — broken?"
    assert missing == [], f"{len(missing)} missing: {missing}"


def test_conv3d_transpose_layer():
    pt.seed(0)
    m = nn.Conv3DTranspose(2, 4, 2, stride=2)
    x = pt.to_tensor(np.random.rand(1, 2, 3, 3, 3).astype("f4"))
    out = m(x)
    assert out.shape == [1, 4, 6, 6, 6]
    out.sum().backward()
    assert np.isfinite(np.asarray(m.weight.grad)).all()


def test_tree_conv_neighborhood():
    pt.seed(1)
    tc = nn.TreeConv(feature_size=3, output_size=2, act=None)
    # star tree: node0 parent of 1 and 2
    nv = np.zeros((1, 3, 3), "f4")
    nv[0, 1] = [1, 0, 0]
    nv[0, 2] = [0, 1, 0]
    es = np.array([[[0, 1], [0, 2]]], "i4")
    out = tc(pt.to_tensor(nv), pt.to_tensor(es))
    assert out.shape == [1, 3, 2]
    # node0 aggregates its children through the child-side matrix
    w_child = np.asarray(tc.weight.numpy())[1]
    expect0 = nv[0, 1] @ w_child + nv[0, 2] @ w_child
    np.testing.assert_allclose(out.numpy()[0, 0], expect0, rtol=1e-5,
                               atol=1e-6)


def test_static_gradients_dygraph_path():
    x = pt.to_tensor(np.array([2.0, 3.0], "f4"))
    x.stop_gradient = False
    g = static.gradients((x * x).sum(), x)
    g0 = g[0] if isinstance(g, (list, tuple)) else g
    np.testing.assert_allclose(g0.numpy(), [4.0, 6.0], rtol=1e-6)


def test_dgc_momentum_matches_momentum():
    pt.seed(2)
    w1 = pt.Parameter(np.ones((4,), "f4"))
    w2 = pt.Parameter(np.ones((4,), "f4"))
    o1 = optimizer.DGCMomentumOptimizer(0.1, 0.9, parameters=[w1])
    o2 = optimizer.Momentum(0.1, 0.9, parameters=[w2])
    for o, w in ((o1, w1), (o2, w2)):
        (w * w).sum().backward()
        o.step()
        o.clear_grad()
    np.testing.assert_allclose(w1.numpy(), w2.numpy())


def test_detection_map_metric():
    det = np.array([[[1, 0.9, 0, 0, 10, 10]]], "f4")
    lab = np.array([[[1, 0, 0, 10, 10]]], "f4")
    m = metric.DetectionMAP(class_num=2)
    m.update(pt.to_tensor(det), pt.to_tensor(lab))
    assert m.accumulate() == pytest.approx(1.0)


def test_error_clip_applied_by_tape():
    """ErrorClipByValue clips the incoming error signal of the var it is
    attached to (reference fluid/clip.py semantics)."""
    x = pt.to_tensor(np.array([3.0, -3.0], "f4"))
    x.stop_gradient = False
    y = x * 10.0
    y.error_clip = fluid.clip.ErrorClipByValue(max=0.5)
    (y * 1.0).sum().backward()
    # dy arrives as ones → clipped to 0.5 → dx = 0.5 * 10
    np.testing.assert_allclose(np.asarray(x.grad), [5.0, 5.0])


def test_set_gradient_clip_consumed_by_optimizer():
    """set_gradient_clip's global strategy applies when the optimizer got
    no grad_clip of its own."""
    try:
        fluid.clip.set_gradient_clip(fluid.clip.GradientClipByValue(0.01))
        w = pt.Parameter(np.ones((4,), "f4"))
        o = optimizer.SGD(learning_rate=1.0, parameters=[w])
        (w * 100.0).sum().backward()  # raw grad = 100
        o.step()
        # clipped grad 0.01 → w = 1 - 0.01
        np.testing.assert_allclose(w.numpy(), 0.99, rtol=1e-6)
    finally:
        fluid.clip.set_gradient_clip(None)


def test_detection_map_accumulates_globally():
    """accumulate() is the dataset mAP over all banked batches, not a
    mean of per-batch mAPs."""
    m = metric.DetectionMAP(class_num=2)
    # batch 1: one gt, detected correctly at score 0.9
    m.update(pt.to_tensor(np.array([[[1, 0.9, 0, 0, 10, 10]]], "f4")),
             pt.to_tensor(np.array([[[1, 0, 0, 10, 10]]], "f4")))
    # batch 2: one gt, missed entirely; one false positive at HIGHER score
    m.update(pt.to_tensor(np.array([[[1, 0.95, 50, 50, 60, 60]]], "f4")),
             pt.to_tensor(np.array([[[1, 0, 0, 10, 10]]], "f4")))
    # global ranking: FP(0.95), TP(0.9) over npos=2:
    # AP = 0*... + (0.5-0)*prec@TP(=1/2) = 0.25
    assert m.accumulate() == pytest.approx(0.25, abs=1e-6)


def test_xavier_msra_uniform_kwarg():
    """Regression (review r3): the fluid spellings Xavier(uniform=...) /
    MSRA(uniform=...) dispatch to the right variant."""
    import paddle_tpu.initializer as I
    assert isinstance(I.Xavier(), I.XavierUniform)
    assert isinstance(I.Xavier(uniform=False), I.XavierNormal)
    assert isinstance(I.MSRA(), I.KaimingUniform)
    assert isinstance(I.MSRA(uniform=False), I.KaimingNormal)


def test_per_param_gradient_clip():
    """set_gradient_clip(param_list=...) clips only those params."""
    w1 = pt.Parameter(np.ones((2,), "f4"))
    w2 = pt.Parameter(np.ones((2,), "f4"))
    fluid.clip.set_gradient_clip(fluid.clip.GradientClipByValue(0.01),
                                 param_list=[w1])
    o = optimizer.SGD(learning_rate=1.0, parameters=[w1, w2])
    ((w1 + w2) * 100.0).sum().backward()
    o.step()
    np.testing.assert_allclose(w1.numpy(), 0.99, rtol=1e-5)  # clipped
    np.testing.assert_allclose(w2.numpy(), -99.0, rtol=1e-5)  # raw


def test_map_counts_undetected_classes():
    """Regression (review r3): a class with ground truth but zero
    detections contributes AP=0 instead of being dropped."""
    from paddle_tpu.fluid.layers_extra2 import _map_eval
    det = [np.array([[1, 0.9, 0, 0, 10, 10]], "f4")]
    lab = [np.array([[1, 0, 0, 10, 10], [2, 20, 20, 30, 30]], "f4")]
    m = _map_eval(det, lab, class_num=3, background_label=0)
    assert m == pytest.approx(0.5)  # (AP1=1.0 + AP2=0.0) / 2


def test_detection_map_difficult_excluded():
    det = np.array([[[1, 0.9, 0, 0, 10, 10]]], "f4")
    lab6 = np.array([[[1, 0, 0, 10, 10, 1.0]]], "f4")  # difficult gt
    m = metric.DetectionMAP(class_num=2, evaluate_difficult=False)
    m.update(pt.to_tensor(det), pt.to_tensor(lab6))
    assert m.accumulate() == 0.0  # no countable gt → no AP


def test_fluid_incubate_fleet_import_paths():
    """The reference's launch-script import paths must resolve
    (reference: fluid/incubate/fleet/{collective,base,parameter_server})."""
    from paddle_tpu.fluid.incubate.fleet.collective import (
        fleet, CollectiveOptimizer, DistributedStrategy, TrainStatus)
    from paddle_tpu.fluid.incubate.fleet.base.role_maker import (
        PaddleCloudRoleMaker, UserDefinedRoleMaker, MPISymetricRoleMaker)
    from paddle_tpu.fluid.incubate.fleet.parameter_server. \
        distribute_transpiler import fleet as ps_fleet
    from paddle_tpu.fluid.incubate.data_generator import (
        MultiSlotDataGenerator)
    assert fleet is ps_fleet  # one singleton, collective-backed
    assert TrainStatus(3) == TrainStatus(3)
    assert callable(CollectiveOptimizer)


def test_top_level_module_tail():
    """compat/sysconfig/common_ops_import exist with the reference
    semantics (python/paddle/{compat,sysconfig,common_ops_import}.py)."""
    import os
    import paddle_tpu
    from paddle_tpu import compat, sysconfig
    from paddle_tpu import common_ops_import as coi

    assert compat.to_text(b"ab") == "ab"
    assert compat.to_text(["a", b"b"]) == ["a", "b"]
    assert compat.to_text(3.5) == 3.5  # non-string passes through (ref)
    assert compat.to_bytes("ab") == b"ab"
    assert compat.to_bytes(b"ab") == b"ab"
    import pytest as _pytest
    with _pytest.raises(TypeError):
        compat.to_bytes(5)  # six.b semantics: no silent NUL-fill
    # py2-style half-away-from-zero rounding, not banker's
    assert compat.round(0.5) == 1.0
    assert compat.round(-0.5) == -1.0
    assert compat.round(1.5) == 2.0
    assert compat.long_type is int
    assert compat.get_exception_message(ValueError("boom")) == "boom"
    assert os.path.isdir(sysconfig.get_include())
    assert isinstance(sysconfig.get_lib(), str)
    for name in ("Variable", "ParamAttr", "Constant",
                 "convert_np_dtype_to_dtype_", "in_dygraph_mode"):
        assert hasattr(coi, name), name
    assert hasattr(paddle_tpu, "compat")
    assert hasattr(paddle_tpu, "sysconfig")
