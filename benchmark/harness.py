"""The harness: one process, one cell, one result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
`BENCHMARK.json` gives it (README.md says how to add each). This module
knows none of them by name.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
EXIT_NO_DEVICE = 3
EXIT_NOT_DEVICE_PATH = 4


class Refused(SystemExit):
    """The run ends non-zero and prints no result line."""

    def __init__(self, code: int, why: str) -> None:
        print(f"benchmark: REFUSED: {why}", file=sys.stderr, flush=True)
        super().__init__(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(roots: list, kind_dir: str, filename: str) -> str:
    """`<root>/<kind_dir>/<filename>` in the first root that has it."""
    for root in roots:
        path = os.path.join(root, kind_dir, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {kind_dir}/{filename} under {' or '.join(roots)}")


def load_module(kind_dir: str, name: str, roots: list):
    path = find_file(roots, kind_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind_dir}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Context:
    """What a traffic kind and the metric readers are handed."""

    def __init__(self, args, root: str, t_start: float) -> None:
        self.t_start = t_start
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.root = root
        self.index = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.index["paths"][0])
        self.roots = [self.bench_dir] + ([HERE] if self.bench_dir != HERE
                                         else [])
        cells = {w["name"]: w for w in self.index["workloads"]}
        if args.workload not in cells:
            raise Refused(2, f"no workload {args.workload!r} in "
                             f"BENCHMARK.json: {sorted(cells)}")
        self.cell = cells[args.workload]
        configs = {c["name"]: c for c in self.index["configs"]}
        self.config = load_json(os.path.join(
            root, configs[self.cell["config"]]["file"]))
        self.traffic = load_json(self.find("traffic",
                                           self.cell["traffic"] + ".json"))
        # only a fixture index (tests, rehearsal) may name another platform
        rehearsal = self.index.get("rehearsal") if root != REPO else None
        self.platform = (rehearsal or {}).get("platform", "tpu")
        self.rehearsal = self.platform != "tpu"
        self.devices = []
        self.device = {}
        self.counts = {}        # counters and host-clock spans, by name
        self.e2e = {}           # end-to-end metric values, by name
        self.compared = {}      # name -> {"value", "limit"}
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.memory_peak_bytes = None
        self.device_extra = {}  # further keys of the line's `device`
        self.least_s = None     # (seconds, bound) for the window's work
        self.trace_dir = os.path.join(root, ".bench_out", "trace",
                                      self.cell["name"])
        self._summary = None
        self._window_span = None
        self.window_open_at = None

    def find(self, kind_dir: str, filename: str) -> str:
        return find_file(self.roots, kind_dir, filename)

    # ------------------------------------------------------------ device

    def claim_devices(self) -> None:
        import jax

        devices = jax.devices()  # a backend that cannot start raises here
        if devices[0].platform != self.platform:
            raise Refused(EXIT_NO_DEVICE,
                          f"JAX's default device is {devices[0].platform!r}"
                          f", the cell needs {self.platform!r}")
        if len(devices) < int(self.cell["chips"]):
            raise Refused(EXIT_NO_DEVICE,
                          f"the cell needs {self.cell['chips']} chips, JAX "
                          f"sees {len(devices)}")
        self.devices = devices[:int(self.cell["chips"])]
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(self.devices)}

    def read_memory(self) -> None:
        """The peak on the fullest chip, read once the window has closed and
        before any reference runs: the allocator's peak of live buffers
        plus its peak reservation. A running program's temp is reserved, not
        "in use" (PERF.md, Findings PR 25: 0.12 GB in use beside 4.96 GB
        reserved while a 5.49 GB-temp program ran), and both come out of
        the same `bytes_limit`."""
        peak, fullest = -1, {}
        for d in self.devices:
            stats = d.memory_stats() or {}
            total = (int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
            if total > peak:
                peak, fullest = total, stats
        self.memory_peak_bytes = max(peak, 0)
        self.device_extra.update(
            {"allocator_" + k: int(fullest[k]) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
             if k in fullest})

    # ------------------------------------------------------------ window

    def window_limit(self) -> float:
        """Seconds after which no new work is begun: --seconds, or the
        traffic's shorter `trace_seconds` in a traced run."""
        if self.trace:
            return min(self.seconds, float(self.traffic["trace_seconds"]))
        return self.seconds

    def open_window(self) -> float:
        """Set-up ends here. In a traced run the profiler starts first, so
        its start-up is set-up too. Returns the window's first instant."""
        if self.trace:
            import jax
            from jax.profiler import ProfileOptions

            import trace as trace_mod

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation(
                trace_mod.WINDOW_SPAN)
            self._window_span.__enter__()
        self.window_open_at = time.perf_counter()
        self.setup_s = self.window_open_at - self.t_start
        return self.window_open_at

    def close_window(self) -> None:
        if self._window_span is not None:
            import jax

            self._window_span.__exit__(None, None, None)
            self._window_span = None
            jax.profiler.stop_trace()
        self.read_memory()

    def span(self, name: str):
        """A host span in the profiler's own trace (a no-op context outside
        a traced run)."""
        import contextlib

        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_summary(self):
        """The reduced trace of a traced run, read once; None otherwise."""
        if not self.trace:
            return None
        if self._summary is None:
            import trace as trace_mod

            self._summary = trace_mod.reduce(
                trace_mod.find_xplane(self.trace_dir))
        return self._summary

    # ----------------------------------------------------------- correct

    def compare(self, name: str, value: float, limit: float) -> None:
        self.compared[name] = {"value": float(value), "limit": float(limit)}

    def correct(self) -> bool:
        return bool(self.compared) and all(
            v["value"] == v["value"] and v["value"] <= v["limit"]
            for v in self.compared.values())


def per_layer_metrics(ctx: Context) -> dict:
    out = {}
    cell = ctx.cell["name"]
    reported = set(ctx.e2e)
    for metric in ctx.index["per_layer"]:
        cells = metric.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if cells is None and metric["moves"] not in reported:
            continue
        spec = load_json(ctx.find("metrics", metric["name"] + ".json"))
        reader = load_module("readers", spec["reader"], ctx.roots)
        value = reader.read(ctx, spec)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(ctx: Context) -> dict:
    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak_bytes)
    device.update(ctx.device_extra)
    line = {"correct": ctx.correct(), "attempted": int(ctx.attempted),
            "failed": int(ctx.failed)}
    if ctx.trace:
        import trace as trace_mod

        summary = ctx.trace_summary()
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["metrics"] = per_layer_metrics(ctx)
        line["device"] = device
        line["breakdown"] = trace_mod.breakdown(summary)
    else:
        units = {m["name"]: m["unit"] for m in ctx.index["end_to_end"]}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in ctx.e2e.items()}
        line["device"] = device
    line["cell"] = ctx.cell["name"]
    line["seed"] = ctx.seed
    line["compared"] = ctx.compared
    return line


def run(argv=None, t_start: float = None, check_device: bool = True) -> dict:
    """One run of one cell; returns the result line (and prints it).
    `check_device=False` is for the tests that break the timed path on the
    CPU: it skips the look for a chip and nothing else."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=REPO,
                    help="directory that holds BENCHMARK.json (tests point "
                         "this at a fixture directory)")
    args = ap.parse_args(argv)
    ctx = Context(args, os.path.abspath(args.root), t_start)
    # the configuration's own switches of the program (its stated precision)
    os.environ.update({k: str(v) for k, v in
                       ctx.config.get("env", {}).items()})
    if ctx.rehearsal:
        # the CPU rehearsal: Pallas kernels interpreted (the program's own
        # switch for its CPU tests)
        os.environ.setdefault("LGBM_TPU_PALLAS_INTERPRET", "1")
        from lightgbm_tpu.treelearner import serial

        serial.on_tpu = lambda: True  # the device learner, on the CPU
    if ctx.trace:
        # the program's own scopes become host spans of the trace; they are
        # perf_counter reads and TraceAnnotations, no device sync
        os.environ.setdefault("LGBM_TPU_TIMETAG", "1")
    if check_device:
        ctx.claim_devices()
    else:
        import jax

        ctx.devices = jax.devices()[:1]
        ctx.device = {"platform": ctx.devices[0].platform,
                      "kind": ctx.devices[0].device_kind, "count": 1}
    kind = load_module("kinds", ctx.traffic["kind"], ctx.roots)
    kind.run(ctx)
    ctx.e2e["setup_s"] = ctx.setup_s
    line = result_line(ctx)
    if ctx.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    for name, v in ctx.compared.items():
        print(f"compared {name}: value {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return line
