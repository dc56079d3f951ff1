"""paddle_tpu.distributed.launch — multi-host launch CLI.

TPU-native rebuild of reference python/paddle/distributed/launch.py. The
reference spawns one worker process per GPU and wires NCCL endpoints; on a
TPU pod each HOST runs one process that owns its local chips, so launch
degenerates to: set the coordinator env, call jax.distributed.initialize,
exec the training script. Usage:

    # one invocation per host (pod):
    python -m paddle_tpu.distributed.launch \
        --coordinator 10.0.0.1:8476 --num_hosts 4 --host_id 0 train.py ...

    # or reference-style local spawn (N processes on THIS machine, each a
    # jax.distributed participant — cross-process collectives ride the
    # same code path a pod's DCN does):
    python -m paddle_tpu.distributed.launch --nproc_per_node 2 train.py

Single-host (the common case, incl. this repo's CI): just runs the script.
"""
from __future__ import annotations

import argparse
import os
import runpy
import socket
import subprocess
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--coordinator", default=None,
                   help="coordinator address host:port (multi-host)")
    p.add_argument("--num_hosts", type=int, default=1)
    p.add_argument("--host_id", type=int, default=None)
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="spawn N local worker processes (reference "
                        "launch.py behavior); each becomes one "
                        "jax.distributed process")
    p.add_argument("script", help="training script to run")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_local(args):
    """Reference-style local fan-out: N child processes, auto coordinator,
    failure of any child fails the launch FAST (a dead rank would leave
    the others blocked in the jax.distributed rendezvous forever, so the
    parent polls all children and tears the group down on the first bad
    exit)."""
    import time

    # what is true on a TPU machine: libtpu is installed and JAX is not
    # held to the CPU, so every child would reach for the same chip
    import importlib.util
    if importlib.util.find_spec("libtpu") is not None and \
            os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            "--nproc_per_node > 1 where JAX would use the TPU: a chip "
            "belongs to one process at a time — a TPU pod runs ONE "
            "process per host (use --coordinator/--num_hosts/--host_id, "
            "one launch per host). Set JAX_PLATFORMS=cpu to fan out CPU "
            "worker processes on this machine.")
    port = _free_port()
    procs = []
    for rank in range(args.nproc_per_node):
        env = dict(os.environ)
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["PADDLE_TRAINERS_NUM"] = str(args.nproc_per_node)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_LOCAL_RANK"] = str(rank)
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--coordinator", f"127.0.0.1:{port}",
               "--num_hosts", str(args.nproc_per_node),
               "--host_id", str(rank), args.script] + args.script_args
        procs.append(subprocess.Popen(cmd, env=env))
    try:
        while True:
            codes = [p.poll() for p in procs]
            bad = next((c for c in codes if c not in (None, 0)), None)
            if bad is not None:
                raise SystemExit(bad)
            if all(c == 0 for c in codes):
                return
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()


def main(argv=None):
    args = parse_args(argv)
    if args.nproc_per_node > 1:
        if args.coordinator is not None or args.num_hosts != 1 or \
                args.host_id is not None:
            raise SystemExit(
                "--nproc_per_node cannot combine with --coordinator/"
                "--num_hosts/--host_id: the process model is one "
                "jax.distributed participant per process — either local "
                "fan-out (--nproc_per_node alone) or one launch per host "
                "(--coordinator/--num_hosts/--host_id)")
        _spawn_local(args)
        return
    if args.coordinator and args.num_hosts > 1:
        os.environ["COORDINATOR_ADDRESS"] = args.coordinator
        os.environ["PADDLE_TRAINERS_NUM"] = str(args.num_hosts)
        if args.host_id is not None:
            os.environ["PADDLE_TRAINER_ID"] = str(args.host_id)
        from . import init_parallel_env
        init_parallel_env()
    sys.argv = [args.script] + args.script_args
    runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    main()
