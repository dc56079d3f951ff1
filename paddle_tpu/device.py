"""paddle_tpu.device — device/place management.

TPU-native rebuild of the reference's Place abstraction
(reference: python/paddle/fluid/framework.py CPUPlace/CUDAPlace +
paddle/fluid/platform/place.h). CUDAPlace becomes TPUPlace; a Place wraps a
jax.Device. `set_device` steers default placement via jax.default_device.
"""
from __future__ import annotations

import os

import jax


class Place:
    def __init__(self, device):
        self.device = device

    def __repr__(self):
        return f"Place({self.device})"

    def __eq__(self, other):
        return isinstance(other, Place) and self.device == other.device


class CPUPlace(Place):
    def __init__(self, idx=0):
        super().__init__(jax.devices("cpu")[idx]
                         if _has_platform("cpu") else jax.devices()[0])


class TPUPlace(Place):
    def __init__(self, idx=0):
        devs = jax.devices()
        super().__init__(devs[idx % len(devs)])


class CUDAPinnedPlace(Place):
    """reference place.h:CUDAPinnedPlace — page-locked host staging
    memory. Host-side staging here is the csrc arena; the place object
    exists so device-placement code ports, and resolves to host CPU."""

    def __init__(self):
        super().__init__(jax.devices("cpu")[0]
                         if _has_platform("cpu") else jax.devices()[0])


# parity alias: code written against the reference uses CUDAPlace for the
# accelerator
CUDAPlace = TPUPlace


def _has_platform(name):
    try:
        jax.devices(name)
        return True
    except RuntimeError:
        return False


_current = None


def set_device(device):
    """paddle.set_device('tpu'/'cpu'/'tpu:0')."""
    global _current
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("tpu", "gpu", "xpu", "cuda"):
        place = TPUPlace(idx)
    else:
        place = CPUPlace(idx)
    _current = place
    jax.config.update("jax_default_device", place.device)
    return place


def get_device():
    if _current is None:
        return f"{jax.devices()[0].platform}:0"
    return f"{_current.device.platform}:{_current.device.id}"


def is_compiled_with_cuda():
    """Parity shim — reports accelerator availability (TPU here)."""
    return any(d.platform != "cpu" for d in jax.devices())


def is_tpu_backend():
    """The one "is this a TPU backend" test: JAX's default backend is
    ``tpu``. Pallas kernels compile exactly when this holds (interpret
    mode is for the CPU tests only, see ``ops.pallas.interpret_mode``)."""
    return jax.default_backend() == "tpu"


def is_compiled_with_tpu():
    return is_tpu_backend()


def device_count():
    return jax.device_count()


# fixed, inside the checkout, ignored by git: the path is part of the
# cache key, so a directory that moves (tmp name, pid, time) never hits
_CACHE_DIR_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compilation_cache(min_compile_time_secs=1.0):
    """Turn on XLA's persistent compilation cache so executables survive
    process restarts, and return the directory in use.

    One rule for the whole repo (benchmark/run.py, chip_smoke.py and
    every script come through here): where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already keeps its cache there and no other directory is
    set in code; where it is not, the cache is ``.jax_cache`` at the
    root of the checkout. Programs that compile faster than
    ``min_compile_time_secs`` are not persisted (tiny shapes would churn
    the cache for no win).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CACHE_DIR_DEFAULT
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    return path
