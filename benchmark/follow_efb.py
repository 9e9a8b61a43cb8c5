#!/usr/bin/env python3
"""The EFB cell's plain reference (`reference/gbdt_efb.py`) in a process of
its own, working while the run does.

    python benchmark/follow_efb.py       (its messages on standard input)

`kinds/train_window_efb.py` starts it first thing. While the program makes
its table, constructs it and compiles, this process makes the same table
from the same configuration and seed (`data_allstate`), fits its own bin
bounds and builds both sets' tables once the program's layout is there.
It follows the program's warm-up trees once the window has closed, on
every core but one (`WORKERS` processes forked from it, which is why it
imports nothing of `lightgbm_tpu` or `jax`): work on the host during the
window would slow the program's host side and move the metric. Messages,
one line each on standard input:

  {"config": {...}, "seed": n, "dir": path, "parent": pid}   the first
  layout     dir/layout.pkl holds what the kind's `layout_of` hands over
  program    dir/program.pkl holds {"model": the text model of the trees
             to follow, "handed": what the program handed over after each,
             "n_follow": how many}; sent when the window has closed

It prints one line of JSON, {"readings": {...}, "seconds": {...}}, and
exits 0; standard error is the run's. It dies with the process that
started it (PR_SET_PDEATHSIG), which kills it if the run ends first.
"""
import ctypes
import json
import os
import pickle
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)  # the benchmark's own modules

import data_allstate  # noqa: E402
import modeltext  # noqa: E402
from reference import gbdt_efb as reference  # noqa: E402

# processes routing the trees and counting a tree's histograms: every
# core but the one the run's own process needs
WORKERS = max(1, (os.cpu_count() or 2) - 1)


def _die_with_parent(parent: int) -> None:
    try:
        ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGKILL)
    except (OSError, AttributeError):  # not Linux: the pipe's end is all
        pass
    if os.getppid() != parent:  # the parent was gone before the prctl
        sys.exit(1)


def _wait_for(name: str, where: str):
    """The next message, which has to be `name`; the object it names."""
    line = sys.stdin.readline().strip()
    if line != name:  # the run ended (EOF) or went another way
        sys.exit(1)
    with open(os.path.join(where, name + ".pkl"), "rb") as fh:
        return pickle.load(fh)


def main() -> None:
    t0 = time.perf_counter()
    first = json.loads(sys.stdin.readline())
    _die_with_parent(int(first["parent"]))
    cfg, params = first["config"], first["config"]["params"]
    seconds = {}
    sets = data_allstate.make_allstate_data(cfg, int(first["seed"]))
    seconds["data"] = time.perf_counter() - t0
    t = time.perf_counter()
    bounds = reference.own_bounds(sets["train"][0], params)
    seconds["bounds"] = time.perf_counter() - t
    layout = _wait_for("layout", first["dir"])
    t = time.perf_counter()
    follower = reference.Follower(sets, layout, params, bounds)
    del sets, layout
    seconds["tables"] = time.perf_counter() - t
    program = _wait_for("program", first["dir"])
    t = time.perf_counter()
    trees = modeltext.parse_model(program["model"])
    readings = follower.follow(trees, program["handed"],
                               int(program["n_follow"]), WORKERS)
    seconds["trees"] = time.perf_counter() - t
    seconds["all"] = time.perf_counter() - t0
    print(json.dumps({"readings": readings, "seconds": seconds}), flush=True)


if __name__ == "__main__":
    main()
