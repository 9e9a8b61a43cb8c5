"""Multi-process training launcher (the torchrun/dask-analog orchestrator).

The reference ships parallel orchestration through its socket machinery plus
external wrappers (Dask in python-package/lightgbm/dask.py, MPI via mpirun);
the TPU-native equivalent is one JAX process per host joined through
`jax.distributed`. This launcher covers the single-machine multi-process
case on the CPU backend (a multi-host cluster simulated as a gang of
`--devices-per-proc` CPU processes):

    python -m lightgbm_tpu.launch -n 4 -- config=train.conf

spawns 4 worker processes with JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID set; each worker runs the normal CLI (lightgbm_tpu.cli), and
parallel/dist.py picks the env vars up in init_distributed. For a REAL
multi-host pod, run the same CLI once per host with those env vars (or a
machine-list conf) instead.

A chip belongs to one process, and every worker spawned here would claim
ALL of its host's chips, so the launcher is not how one host's chips are
used: there the multi-chip path is the in-process mesh — one process,
`tree_learner=data num_machines=k`, which parallel/mesh.py maps onto k
local devices. One process per HOST, never one per chip.

The gang is *supervised* (parallel/elastic.py): the moment one worker exits
nonzero or misses its liveness deadline, every sibling is reaped — a dead
rank must not leave the rest blocked in jax.distributed barriers forever.
With ``--elastic``, the launcher then relaunches the gang up to
``--max-restarts`` times, resuming from the newest valid
``output_model.snapshot_iter_<k>`` (arm ``snapshot_freq`` for that). The
restart keeps the SAME world size by default — the lost rank is respawned,
so the resumed run is bit-identical to an undisturbed one; pass
``--allow-shrink`` to instead continue at the surviving world size (see
docs/ROBUSTNESS.md, "Distributed fault domain", for what that trades away).
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import tempfile
from typing import List

from .parallel.elastic import GangSupervisor, latest_snapshot, worker_env


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _output_model(cli_args: List[str]) -> str:
    # config.py kv2map: first occurrence wins — mirror that here
    for a in cli_args:
        if a.startswith("output_model="):
            return a.split("=", 1)[1]
    return "LightGBM_model.txt"


def main(argv: List[str] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.launch",
        description="Spawn N jax.distributed worker processes running the "
                    "lightgbm_tpu CLI: a CPU gang on one machine "
                    "(--devices-per-proc), or the shape of one process per "
                    "HOST of a pod. Not for the chips of one host: each "
                    "worker would claim every local chip. There, run ONE "
                    "process with tree_learner=data num_machines=k (the "
                    "in-process mesh, parallel/mesh.py).")
    parser.add_argument("-n", "--nproc", type=int, default=2,
                        help="number of worker processes")
    parser.add_argument("--port", type=int, default=0,
                        help="coordinator port (default: pick a free one)")
    parser.add_argument("--devices-per-proc", type=int, default=0,
                        help="force N virtual CPU devices per process "
                             "(local simulation)")
    parser.add_argument("--elastic", action="store_true",
                        help="relaunch the gang after a worker loss, "
                             "resuming from the newest snapshot")
    parser.add_argument("--max-restarts", type=int, default=2,
                        help="elastic relaunch budget (default 2)")
    parser.add_argument("--allow-shrink", action="store_true",
                        help="elastic restarts drop to the surviving world "
                             "size instead of respawning the lost rank")
    parser.add_argument("--liveness-timeout", type=float, default=0.0,
                        help="reap the gang when a worker's liveness file "
                             "goes stale this many seconds (0 = off)")
    parser.add_argument("--gang-dir", default=None,
                        help="directory for per-rank liveness files "
                             "(default: a fresh temp dir)")
    parser.add_argument("cli_args", nargs=argparse.REMAINDER,
                        help="arguments forwarded to lightgbm_tpu.cli "
                             "(prefix with --)")
    args = parser.parse_args(argv)
    cli_args = [a for a in args.cli_args if a != "--"]
    out_model = _output_model(cli_args)
    gang_dir = args.gang_dir or tempfile.mkdtemp(prefix="lgbm_gang_")

    # per-attempt state: each relaunch needs a fresh coordinator port (the
    # old one can sit in TIME_WAIT) and, past attempt 0, a resume arg
    attempt_state = {}

    def _attempt_args(attempt: int) -> tuple:
        if attempt in attempt_state:
            return attempt_state[attempt]
        port = (args.port or _free_port()) if attempt == 0 else _free_port()
        aargs = list(cli_args)
        if attempt > 0:
            snap = latest_snapshot(out_model)
            # kv2map takes the FIRST occurrence: strip any caller-supplied
            # input_model before appending the resume point
            aargs = [a for a in aargs if not a.startswith("input_model=")]
            if snap:
                aargs.append(f"input_model={snap}")
            else:
                print(f"launch: no valid snapshot beside {out_model}; "
                      "elastic restart retrains from scratch",
                      file=sys.stderr)
        attempt_state[attempt] = (port, aargs)
        return attempt_state[attempt]

    def spawn(world: int, rank: int, attempt: int) -> subprocess.Popen:
        port, aargs = _attempt_args(attempt)
        env = worker_env(port=port, world=world, rank=rank, attempt=attempt,
                         gang_dir=gang_dir, elastic=args.elastic,
                         devices_per_proc=args.devices_per_proc)
        return subprocess.Popen(
            [sys.executable, "-m", "lightgbm_tpu.cli", *aargs], env=env)

    sup = GangSupervisor(
        spawn, args.nproc, elastic=args.elastic,
        max_restarts=args.max_restarts, allow_shrink=args.allow_shrink,
        liveness_timeout_s=args.liveness_timeout, gang_dir=gang_dir)
    return sup.run()


if __name__ == "__main__":
    sys.exit(main())
