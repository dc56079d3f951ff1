"""Live-telemetry smoke gate (tier-1-safe: tiny MLP, CPU, seconds).

Trains a small hapi.Model with the telemetry plane armed
(``fit(metrics_port=0)``) and scrapes the HTTP endpoints FROM INSIDE
the training loop (a mid-run callback) — the acceptance criterion is
literally "curl /metrics during fit and get live series back":

* ``/metrics`` mid-run parses as OpenMetrics (``# TYPE`` lines, final
  ``# EOF``) and contains executor/dispatch activity counters AND at
  least one sampled ``mem_*`` gauge (``mem.host.rss_bytes`` is
  guaranteed even on CPU, where per-device HBM stats are empty)
* ``/healthz`` answers 200 with watchdog + NaN-guard state mid-run
* ``/snapshot`` answers with the counter snapshot
* ``monitor.disable()`` tears everything down: no paddle_tpu
  threads survive, the port stops answering

Prints one JSON result line; exit code 0 iff every gate passes.
"""
import argparse
import json
import os
import sys
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import numpy as np


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode("utf-8"), \
            r.headers.get("Content-Type", "")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_export_smoke")
    ap.add_argument("--steps", type=int, default=48)
    args = ap.parse_args()

    import paddle_tpu as pt
    from paddle_tpu import hapi, io, monitor, nn, optimizer as opt

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = monitor.enable(os.path.join(args.out_dir,
                                        "export_smoke.jsonl"))
    # fast sampler tick so a ~seconds-long fit gets several samples
    os.environ["PADDLE_TPU_SAMPLER_INTERVAL_S"] = "0.05"

    pt.seed(0)
    rng = np.random.RandomState(0)
    x = rng.rand(args.steps * 8, 16).astype("f4")
    y = rng.randint(0, 4, (args.steps * 8,)).astype("i8")
    ds = io.TensorDataset(x, y)

    m = hapi.Model(nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                                 nn.Linear(32, 4)))
    m.prepare(optimizer=opt.Adam(learning_rate=0.05,
                                 parameters=m.parameters()),
              loss_function=hapi.CrossEntropy())

    scraped = {}

    class MidRunScrape(hapi.Callback):
        """Scrape every endpoint while the step loop is live."""

        def on_train_batch_end(self, step, logs=None):
            if scraped or step < args.steps // 2:
                return
            port = monitor.export.port()
            time.sleep(0.15)  # let the sampler tick at least twice
            scraped["port"] = port
            scraped["metrics"] = _get(port, "/metrics")
            scraped["healthz"] = _get(port, "/healthz")
            scraped["snapshot"] = _get(port, "/snapshot")

    m.fit(ds, batch_size=8, epochs=1, verbose=0, watchdog=True,
          prefetch=2, metrics_port=0, callbacks=[MidRunScrape()])

    port = scraped.get("port")
    status, text, ctype = scraped.get("metrics", (0, "", ""))
    h_status, h_body, _ = scraped.get("healthz", (0, "{}", ""))
    s_status, s_body, _ = scraped.get("snapshot", (0, "{}", ""))
    health = json.loads(h_body or "{}")
    snap = json.loads(s_body or "{}")
    metric_names = {line.split("{")[0].split(" ")[0]
                    for line in text.splitlines()
                    if line and not line.startswith("#")}

    # teardown: disable() must join the server + sampler and free the port
    monitor.disable()
    time.sleep(0.3)
    import threading
    leaked = [t.name for t in threading.enumerate()
              if "paddle_tpu" in t.name]
    port_dead = True
    try:
        _get(port, "/healthz")
        port_dead = False
    except Exception:
        pass

    gates = {
        "metrics_200_openmetrics": (status == 200
                                    and "openmetrics-text" in ctype
                                    and text.rstrip().endswith("# EOF")
                                    and "# TYPE" in text),
        "executor_series_present": any(
            n.startswith(("executor_", "dispatch_", "jit_"))
            for n in metric_names),
        "mem_gauge_present": any(n.startswith("mem_")
                                 for n in metric_names),
        "prefetch_series_present": any(n.startswith("prefetch_")
                                       for n in metric_names),
        "healthz_ok_midrun": (h_status == 200
                              and health.get("status") == "ok"
                              and health.get("watchdogs")
                              and "nan_guard" in health),
        "snapshot_answers": s_status == 200 and "counters" in snap,
        "teardown_clean": port_dead and not leaked,
    }
    result = {
        "port": port,
        "metrics_bytes": len(text),
        "n_series": len(metric_names),
        "watchdogs": health.get("watchdogs"),
        "leaked_threads": leaked,
        "gates": gates,
        "jsonl": jsonl,
        "ok": all(gates.values()),
    }
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
