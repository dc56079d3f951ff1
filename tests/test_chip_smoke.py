"""chip_smoke.py, rehearsed without the chip: every phase is a function
of its sizes and runs here tiny on the CPU (kernels in interpret mode,
virtual devices for the fleet phase); ``main()`` alone checks the device
and fixes the sizes, so the script itself must fail here, at the device
phase, without printing a result."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax

from paddle_tpu.models.bert import BertConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(capsys, phase):
    return [l for l in capsys.readouterr().out.splitlines()
            if l.startswith(f"[{phase}]")]


def test_phase_kernels_tiny_interpret(cs, capsys):
    cs.phase_kernels(rows=64, hidden=128, batch=2, heads=2, seq=128,
                     head_dim=32)
    out = _lines(capsys, "kernels")
    # layer norm f32+bf16, flash x3, at latent attention's head sizes and
    # under the block-diffusion structure and a sliding window, the scan,
    # Mamba-1's selective scan, the convolution, the gated short convolution, the gated norm, the
    # projection-to-heads pair, the latent heads' pair, the experts'
    # scatter-add, the experts' grouped products, a learned selection
    # against a sort and the two kernels under it
    assert len(out) == 19
    assert any("selective_scan[2x128x1024,state16" in l for l in out)
    # (at 1,024 positions: the least the selection kernel packs at chunks
    # of 128 keys)
    assert any("dsa_select[2x1024,4x64,top256]" in l
               and "differ_from_a_sort=0" in l for l in out)
    assert any("sparse_attention[2x2x1024x128,4x64,top256" in l for l in out)
    assert any("qk_heads[2x128x2x128,norm+rotary" in l for l in out)
    assert any("mla_heads[2x128x2x(128+64|128+128)" in l for l in out)
    assert any("moe_grouped[64x128,4x128gated" in l for l in out)
    assert any("gated_short_conv[2x128x3x" in l for l in out)
    assert any("x64,bf16,window32" in l for l in out)
    assert any("moe_scatter_add[64x128" in l for l in out)
    assert any("x96|64,bf16,causal" in l for l in out)
    assert any("x64,bf16,block_diffusion" in l for l in out)
    assert any("padmask" in l for l in out)
    assert all("tpu_custom_calls=0" in l for l in out
               if "dsa_select" not in l)                # interpreted


def test_phase_kernels_fails_past_tolerance(cs):
    with pytest.raises(AssertionError, match="error .* > "):
        cs.phase_kernels(rows=16, hidden=128, batch=1, heads=1, seq=128,
                         head_dim=32, tol_bf16=1e-9)


def test_phase_train_tiny(cs, capsys):
    cs.phase_train(BertConfig.tiny(), batch=4, seq=32, steps=4,
                   flash_batch=2, flash_seq=64, flash_steps=2)
    out = _lines(capsys, "train")
    assert any("jit_compile=2 jit_recompile=1" in l for l in out)
    assert any("tokens_per_s=" in l and "host clock" in l for l in out)
    assert any(l.startswith("[train] state_arrays=") for l in out)


def test_phase_serve_tiny(cs, capsys):
    cs.phase_serve(dim=32, heads=2, layers=2, n_requests=6, slots=4,
                   page=16, max_len=64, prompt_buckets=(8, 16),
                   drain_new_tokens=40)
    out = _lines(capsys, "serve")
    assert any("batched_equals_single=True executables_after_warmup=0 "
               "traces_after_warmup=0" in l for l in out)
    assert any("drained_equals_undrained=True" in l for l in out)
    assert any("the only model the server has" in l for l in out)


def test_phase_fleet_tiny_four_virtual_devices(cs, capsys):
    assert jax.device_count() >= 4
    cs.phase_fleet(BertConfig.tiny(), batch=8, seq=32, steps=3,
                   mesh_shape={"dp": 2, "tp": 2}, tol=2e-2)
    out = _lines(capsys, "fleet")
    sharded = [l for l in out if "group='tp-sharded" in l]
    assert sharded and all("replicated" not in l for l in sharded)
    total = next(l for l in out if "total_param_bytes_per_device" in l)
    per_dev = json.loads(total.split("=", 1)[1])
    assert len(per_dev) == 4 and min(per_dev) > 0


def _fake_device(count):
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": count}


@pytest.mark.parametrize("argv,want", [
    ([], ["device:1", "kernels", "train", "serve"]),
    (["--chips", "4"], ["device:4", "fleet"]),
], ids=["one-chip", "four-chips"])
def test_main_runs_the_phases_of_its_chip_count(cs, monkeypatch, capsys,
                                                argv, want):
    calls = []
    monkeypatch.setattr(cs, "phase_device",
                        lambda n: calls.append(f"device:{n}")
                        or _fake_device(n))
    for name in ("kernels", "train", "serve", "fleet"):
        monkeypatch.setattr(cs, f"phase_{name}",
                            lambda *a, _n=name, **k: calls.append(_n))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"] + argv)
    cs.main()
    assert calls == want
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True,
                                "device": _fake_device(len(argv) and 4
                                                       or 1)}


def test_main_a_phase_that_raises_prints_no_result(cs, monkeypatch,
                                                   capsys):
    monkeypatch.setattr(cs, "phase_device", _fake_device)

    def boom(**k):
        raise AssertionError("kernel off by 1")

    monkeypatch.setattr(cs, "phase_kernels", boom)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(AssertionError, match="off by 1"):
        cs.main()
    assert '"ok"' not in capsys.readouterr().out


def test_main_has_one_option(cs, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--help"])
    with pytest.raises(SystemExit):
        cs.main()
    helptext = capsys.readouterr().out
    opts = {w.rstrip(",") for w in helptext.split() if w.startswith("--")}
    assert opts == {"--help", "--chips"}


def test_script_fails_at_the_device_phase_without_a_chip():
    """The driver's first check: in a sandbox with no accelerator the
    script exits non-zero and prints no ``"ok": true``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, _SCRIPT], env=env, cwd=_ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "[device] platform=cpu" in proc.stdout
    assert "[kernels]" not in proc.stdout
    assert "needs a TPU" in proc.stderr
