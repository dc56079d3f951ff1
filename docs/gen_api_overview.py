"""Regenerate docs/api_overview.md from the live package:
    python docs/gen_api_overview.py > docs/api_overview.md
"""
import contextlib
import importlib
import io
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import warnings  # noqa: E402

warnings.filterwarnings("ignore")
buf = io.StringIO()
with contextlib.redirect_stderr(buf):
    import paddle_tpu  # noqa: F401,E402

SECTIONS = [
    ("Core", ["paddle_tpu", "paddle_tpu.tensor", "paddle_tpu.autograd",
              "paddle_tpu.dispatch", "paddle_tpu.random",
              "paddle_tpu.device", "paddle_tpu.param_attr"]),
    ("Ops", ["paddle_tpu.ops.math", "paddle_tpu.ops.manip",
             "paddle_tpu.ops.creation", "paddle_tpu.ops.nn_ops",
             "paddle_tpu.ops.loss", "paddle_tpu.ops.ssm",
             "paddle_tpu.ops.moe", "paddle_tpu.ops.sequence",
             "paddle_tpu.ops.crf", "paddle_tpu.ops.ctc",
             "paddle_tpu.ops.detection", "paddle_tpu.ops.control_flow",
             "paddle_tpu.ops.imperative_flow"]),
    ("Pallas kernels", ["paddle_tpu.ops.pallas"]),
    ("Layers", ["paddle_tpu.nn", "paddle_tpu.nn.rnn",
                "paddle_tpu.nn.decode"]),
    ("Training", ["paddle_tpu.optimizer", "paddle_tpu.optimizer.lr",
                  "paddle_tpu.initializer", "paddle_tpu.regularizer",
                  "paddle_tpu.clip", "paddle_tpu.metric",
                  "paddle_tpu.amp", "paddle_tpu.jit",
                  "paddle_tpu.static"]),
    ("Data/IO", ["paddle_tpu.io", "paddle_tpu.reader",
                 "paddle_tpu.dataset", "paddle_tpu.inference",
                 "paddle_tpu.quantization"]),
    ("Distributed", ["paddle_tpu.parallel.collective",
                     "paddle_tpu.parallel.fleet",
                     "paddle_tpu.parallel.megatron",
                     "paddle_tpu.parallel.ring_attention",
                     "paddle_tpu.parallel.embedding",
                     "paddle_tpu.distributed"]),
    ("High-level", ["paddle_tpu.hapi", "paddle_tpu.models",
                    "paddle_tpu.distribution",
                    "paddle_tpu.dygraph_to_static"]),
    ("Compat facades", ["paddle_tpu.fluid", "paddle_tpu.fluid.layers",
                        "paddle_tpu.fluid.dygraph",
                        "paddle_tpu.fluid.contrib",
                        "paddle_tpu.framework", "paddle_tpu.imperative",
                        "paddle_tpu.incubate", "paddle_tpu.compat",
                        "paddle_tpu.sysconfig",
                        "paddle_tpu.common_ops_import"]),
]


def main():
    print("""# API overview

Every public module, with the reference surface it rebuilds. Generated
from the live package (`python docs/gen_api_overview.py` regenerates).
""")
    for title, mods in SECTIONS:
        print(f"## {title}\n")
        for name in mods:
            try:
                m = importlib.import_module(name)
            except Exception as e:  # pragma: no cover
                print(f"- `{name}` — IMPORT FAILED: {e}")
                continue
            doc = (m.__doc__ or "").strip().split("\n")[0]
            pub = [n for n in dir(m) if not n.startswith("_")]
            print(f"- **`{name}`** ({len(pub)} public names) — {doc}")
        print()


if __name__ == "__main__":
    main()
