"""REAL multi-process distributed training (reference:
distributed/launch.py spawning worker processes + NCCL init;
TPU rebuild: jax.distributed over two local processes — the same
coordinator/collective path a multi-host pod uses over DCN, exercised
with CPU devices so it runs anywhere).

The launcher fans out 2 processes x 4 virtual devices = one 8-device
GLOBAL mesh; each process feeds its local batch shard; losses and final
weights must agree bit-exactly across ranks."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest


def test_launch_two_process_global_mesh(tmp_path):
    out_base = str(tmp_path / "result.json")
    env = dict(os.environ)
    # the children are CPU by design and say so, nothing else
    env["JAX_PLATFORMS"] = "cpu"
    env["MULTIPROC_OUT"] = out_base
    worker = os.path.join(os.path.dirname(__file__),
                          "multiproc_worker.py")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", worker],
        env=env, cwd=os.path.dirname(os.path.dirname(worker)),
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]

    results = []
    for rank in range(2):
        with open(out_base + f".{rank}") as f:
            results.append(json.load(f))
    r0, r1 = sorted(results, key=lambda r: r["rank"])
    # both ranks saw the SAME global loss every step (grads psum'd
    # across processes inside the jitted step)
    np.testing.assert_allclose(r0["losses"], r1["losses"], rtol=0)
    # training progressed and the replicated weights stayed in sync
    assert r0["losses"][-1] < r0["losses"][0]
    np.testing.assert_allclose(r0["weight"], r1["weight"], rtol=0)


def test_launch_refuses_fanout_where_jax_would_use_the_tpu():
    """The refusal keys on what is true of the machine — libtpu is
    installed and JAX is not held to the CPU — not on variables nobody
    sets. The parent never touches JAX, so this costs no backend."""
    import importlib.util
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu installed: children could not reach a TPU")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    worker = os.path.join(os.path.dirname(__file__), "multiproc_worker.py")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", worker],
        env=env, cwd=os.path.dirname(os.path.dirname(worker)),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "one process at a time" in proc.stderr
