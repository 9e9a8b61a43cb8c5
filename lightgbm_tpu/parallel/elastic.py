"""Elastic multi-process training: gang supervision, collective heartbeats,
and watchdog conversion of indefinite collective blocks into typed errors.

The reference's network layer is built around failure — socket linkers retry
connects with timeouts and the collective algorithms assume a machine can
drop (src/network/linkers_socket.cpp:188-215). The jax.distributed analog
has the opposite default: a worker that dies mid-wave leaves every sibling
blocked in a `psum_scatter` forever. This module supplies the three missing
pieces (docs/ROBUSTNESS.md, "Distributed fault domain"):

* **CollectiveWatchdog** — a daemon thread fed one cheap ``beat()`` per
  iteration. When no beat lands for ``LGBM_TPU_COLLECTIVE_TIMEOUT_S`` the
  block is converted into a typed :class:`WorkerLostError` carrying this
  rank and the last-good iteration, dumped through the PR 11 flight
  recorder. Escalation is staged: cooperative raise at the next injection
  point, then a best-effort async raise into the blocked thread, then — only
  under gang supervision — a hard ``os._exit`` so the launcher can reap the
  gang instead of hanging with it.
* **collective heartbeat** — a tiny ``psum`` token over the ``data`` mesh.
  It rides the HealthMonitor's existing per-``check_every`` sync slot
  (health.py ``admit``), NOT a new hot-path host sync; without a monitor it
  self-windows at ``LGBM_TPU_HEARTBEAT_EVERY``. A completed-but-short token
  means the mesh lost cardinality mid-run and raises WorkerLostError; a
  dead sibling usually manifests as the psum blocking, which the watchdog
  owns.
* **GangSupervisor** — the launcher-side policy: watch the worker gang,
  reap every sibling the moment one exits nonzero or misses its liveness
  deadline (no orphaned hangs), and under ``--elastic`` relaunch the gang —
  at the same world size by default (the lost rank is respawned, keeping
  resume bit-identical), or at the surviving world size with
  ``--allow-shrink`` (shrink-to-fit; see the checkpoint world fingerprint).

Module import stays jax-free: launch.py and bench.py drive GangSupervisor
without paying a backend init; jax loads lazily on the first heartbeat.
"""
from __future__ import annotations

import os
import subprocess
import threading
import time
from typing import Any, Callable, List, Optional, Tuple

from .. import telemetry, tracing
from ..utils import sanitize
from ..utils.log import Log
from ..utils.timer import global_timer

# exit code a worker uses when its watchdog hard-exits out of a dead
# collective (distinct from crash codes so the supervisor log names it)
EXIT_WORKER_LOST = 117

ENV_TIMEOUT = "LGBM_TPU_COLLECTIVE_TIMEOUT_S"
ENV_HEARTBEAT_EVERY = "LGBM_TPU_HEARTBEAT_EVERY"
ENV_ELASTIC = "LGBM_TPU_ELASTIC"
ENV_GANG = "LGBM_TPU_GANG"          # set by the launcher: under supervision
ENV_GANG_DIR = "LGBM_TPU_GANG_DIR"  # per-rank liveness files live here
ENV_GANG_ATTEMPT = "LGBM_TPU_GANG_ATTEMPT"

_DEF_HEARTBEAT_EVERY = 10
_LIVENESS_MIN_INTERVAL_S = 0.5


class WorkerLostError(RuntimeError):
    """A collective peer stopped participating: the watchdog expired (the
    collective blocked past the deadline) or the heartbeat token came back
    short. Carries the observing rank and its last-good iteration count
    (finished iterations — the checkpoint a restart resumes from)."""

    def __init__(self, message: str = "a gang peer stopped participating",
                 rank: int = -1, last_good_iteration: int = -1) -> None:
        # message MUST default: the watchdog's async-raise escalation can
        # only deliver the bare class, which Python instantiates with no
        # arguments at the interrupt point
        super().__init__(message)
        self.rank = int(rank)
        self.last_good_iteration = int(last_good_iteration)


def _rank() -> int:
    try:
        return int(os.environ.get("JAX_PROCESS_ID", "0") or 0)
    except ValueError:
        return 0


class CollectiveWatchdog:
    """Deadline watchdog over the training thread's iteration beats.

    ``beat()`` is O(1) attribute stores — no lock, no syscall — so the hot
    loop pays nothing. The daemon thread fires when the gap since the last
    beat exceeds ``timeout_s``, records a fully-populated WorkerLostError,
    dumps a flight postmortem, and escalates (async raise, then gang hard
    exit) until the error is consumed by a cooperative checkpoint."""

    def __init__(self, timeout_s: float, rank: Optional[int] = None) -> None:
        self.timeout_s = float(timeout_s)
        self.rank = _rank() if rank is None else int(rank)
        self.error: Optional[WorkerLostError] = None
        self._last: Optional[Tuple[float, int, int]] = None  # (t, iters, tid)
        self._armed = False
        self._fired_at: Optional[float] = None
        self._async_raised = False
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._poll_s = max(0.02, min(self.timeout_s / 4.0, 0.25))

    # ------------------------------------------------------------ hot path

    def beat(self, finished_iterations: int) -> None:
        """One call per iteration from the training thread: records 'alive
        at N finished iterations' plus the thread to interrupt on expiry."""
        self._last = (time.monotonic(), int(finished_iterations),
                      threading.get_ident())
        self._armed = True
        if self._thread is None:
            self._start()

    def raise_if_expired(self) -> None:
        """Cooperative checkpoint: surface the watchdog's verdict in the
        training thread with the full typed error (the async-raise fallback
        can only deliver a bare class)."""
        err = self.error
        if err is not None:
            self.error = None
            self._armed = False
            self._fired_at = None
            raise err

    def disarm(self) -> None:
        """Training finished (or aborted): beats stop legitimately."""
        self._armed = False
        self.error = None
        self._fired_at = None
        self._async_raised = False

    def stop(self) -> None:
        self.disarm()
        self._stop = True

    # ------------------------------------------------------------- thread

    def _start(self) -> None:
        t = threading.Thread(target=self._run, name="lgbm-collective-watchdog",
                             daemon=True)
        self._thread = t
        t.start()

    def _run(self) -> None:
        while not self._stop:
            time.sleep(self._poll_s)
            last = self._last
            if not self._armed or last is None:
                continue
            now = time.monotonic()
            if self._fired_at is None:
                if now - last[0] > self.timeout_s:
                    self._fire(last)
                continue
            self._escalate(now, last)

    def _fire(self, last: Tuple[float, int, int]) -> None:
        t_beat, iters, _tid = last
        err = WorkerLostError(
            f"collective blocked for more than {self.timeout_s:.1f}s on "
            f"rank {self.rank} (last good iteration: {iters}) — a gang "
            "peer stopped participating", rank=self.rank,
            last_good_iteration=iters)
        self.error = err
        self._fired_at = time.monotonic()
        self._async_raised = False
        Log.warning("%s", err)
        tracing.note("worker_lost", rank=self.rank, last_good_iteration=iters,
                     timeout_s=self.timeout_s)
        if telemetry.enabled():
            telemetry.emit("worker_lost", rank=self.rank,
                           last_good_iteration=iters,
                           timeout_s=self.timeout_s)
        global_timer.add_count("elastic_worker_lost", 1)
        tracing.dump_flight("worker_lost", extra={
            "rank": self.rank, "last_good_iteration": iters,
            "timeout_s": self.timeout_s}, force=True)

    def _escalate(self, now: float, last: Tuple[float, int, int]) -> None:
        """After firing: if no cooperative checkpoint consumed the error,
        try an async raise into the training thread (lands at its next
        bytecode — enough for Python-level blocks); if the block is at the
        C level and we run under a gang, hard-exit so the supervisor reaps
        the gang instead of inheriting the hang."""
        assert self._fired_at is not None
        if not self._async_raised and now - self._fired_at > 2 * self._poll_s:
            self._async_raised = True
            try:
                import ctypes

                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_ulong(last[2]), ctypes.py_object(WorkerLostError))
            except Exception:  # noqa: BLE001 - escalation is best-effort
                pass
        grace = max(1.0, self.timeout_s)
        if os.environ.get(ENV_GANG) and now - self._fired_at > grace:
            Log.warning("watchdog: rank %d still blocked %.1fs after the "
                        "deadline; exiting %d for the gang supervisor",
                        self.rank, now - self._fired_at, EXIT_WORKER_LOST)
            os._exit(EXIT_WORKER_LOST)


class ElasticRuntime:
    """Per-process elastic state: the watchdog, the heartbeat collective,
    and the liveness file the gang supervisor reads. Obtained via
    :func:`active` (env-configured) or :func:`install` (tests/bench)."""

    def __init__(self, timeout_s: Optional[float] = None,
                 heartbeat_every: int = _DEF_HEARTBEAT_EVERY,
                 rank: Optional[int] = None,
                 gang_dir: Optional[str] = None) -> None:
        self.rank = _rank() if rank is None else int(rank)
        self.heartbeat_every = max(1, int(heartbeat_every))
        self.watchdog = (CollectiveWatchdog(timeout_s, rank=self.rank)
                         if timeout_s else None)
        self.gang_dir = gang_dir
        self._since_hb = 0
        self._hb: Optional[tuple] = None  # lazily built (fn, token_in, world)
        self._liveness_t = 0.0

    # ------------------------------------------------------------ hot path

    def on_iteration_start(self, finished_iterations: int,
                           piggyback: bool = False) -> None:
        """Called at the top of every training iteration. Beats the
        watchdog, surfaces a pending expiry, touches the liveness file, and
        — only when no HealthMonitor window exists to piggyback on
        (``piggyback=False``) — runs the self-windowed heartbeat."""
        if self.watchdog is not None:
            self.watchdog.raise_if_expired()
            self.watchdog.beat(finished_iterations)
        if self.gang_dir:
            self._touch_liveness(finished_iterations)
        if not piggyback:
            self._since_hb += 1
            if self._since_hb >= self.heartbeat_every:
                self._since_hb = 0
                self.heartbeat_sync(finished_iterations)

    def poll_raise(self) -> None:
        """Cooperative poll for code that blocks on purpose (the injected
        worker_hang loop): raises the watchdog's typed error when set."""
        if self.watchdog is not None:
            self.watchdog.raise_if_expired()

    # ----------------------------------------------------------- heartbeat

    def heartbeat_sync(self, iteration: int) -> bool:
        """All-reduce one health token over the data mesh and verify its
        cardinality. This is the method health.py calls inside its existing
        per-``check_every`` sync window — the token pull rides a slot that
        is already serialized, so no new hot-path host sync is introduced.
        Returns True when the full world answered; a short token raises."""
        hb = self._ensure_collective()
        if hb is None:
            return True
        fn, token_in, world = hb
        token = fn(token_in)
        from .dist import host_value

        # graftlint: disable=R1 -- the windowed heartbeat pull: rides the health.py check_every sync slot (or self-windows at LGBM_TPU_HEARTBEAT_EVERY), never per-iteration
        got = int(host_value(token))
        global_timer.add_count("elastic_heartbeats", 1)
        if telemetry.enabled():
            telemetry.emit("heartbeat", iteration=int(iteration),
                           token=got, world=world, rank=self.rank)
        if got == world:
            # the heartbeat slot doubles as the sanitizer's collective-
            # order sync point: every rank is here in lockstep, so the
            # allgathered fingerprints compare like-for-like
            if sanitize.enabled():
                sanitize.check_collective_order()
            return True
        last_good = int(iteration) if self.watchdog is None else max(
            0, int(iteration))
        err = WorkerLostError(
            f"heartbeat token came back {got}/{world} at iteration "
            f"{iteration}: the mesh lost cardinality mid-run",
            rank=self.rank, last_good_iteration=last_good)
        tracing.note("heartbeat_mismatch", token=got, world=world,
                     iteration=int(iteration), rank=self.rank)
        tracing.dump_flight("heartbeat_mismatch", extra={
            "token": got, "world": world, "iteration": int(iteration),
            "rank": self.rank}, force=True)
        raise err

    def _ensure_collective(self) -> Optional[tuple]:
        """Build (once) the jitted psum token over the data mesh. A
        single-device world has nobody to hear from — the heartbeat
        degrades to the watchdog beat alone."""
        if self._hb is not None:
            return self._hb or None
        import jax

        # graftlint: disable=collective-order -- the windowed heartbeat pull, the one sanctioned rank-dependent gate: process_count()/device count are uniform across the gang, so every rank takes the same arm — single-process runs skip the psum by construction, multi-process gangs all build it
        if len(jax.devices()) <= 1 and jax.process_count() <= 1:
            self._hb = ()
            return None
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from .dist import put_global
        from .mesh import data_mesh

        mesh = data_mesh(0)
        world = int(mesh.devices.size)
        token_in = put_global(np.ones((world,), np.float32), mesh, P("data"))

        def _token_sum(x):
            return jax.lax.psum(jnp.sum(x), "data")

        fn = jax.jit(shard_map(_token_sum, mesh=mesh,
                               in_specs=P("data"), out_specs=P()))
        self._hb = (fn, token_in, world)
        return self._hb

    # ------------------------------------------------------------ liveness

    def _touch_liveness(self, finished_iterations: int) -> None:
        now = time.monotonic()
        if now - self._liveness_t < _LIVENESS_MIN_INTERVAL_S:
            return
        self._liveness_t = now
        try:
            os.makedirs(self.gang_dir, exist_ok=True)
            with open(os.path.join(self.gang_dir, f"hb_{self.rank}"),
                      "w") as fh:
                fh.write(f"{int(finished_iterations)}\n")
        except OSError:
            pass  # liveness is advisory; the heartbeat/watchdog still cover

    def notify_train_end(self) -> None:
        if self.watchdog is not None:
            self.watchdog.disarm()


# -------------------------------------------------------- runtime registry

_runtime: Optional[ElasticRuntime] = None
_runtime_key: Optional[tuple] = None
_installed = False


def active() -> Optional[ElasticRuntime]:
    """The process's elastic runtime, or None when elastic mode is off.
    Env-configured (LGBM_TPU_ELASTIC / LGBM_TPU_COLLECTIVE_TIMEOUT_S) unless
    a runtime was installed programmatically; the off-path costs two dict
    lookups, matching the faults-hook budget."""
    global _runtime, _runtime_key
    if _installed:
        return _runtime
    timeout = os.environ.get(ENV_TIMEOUT, "")
    elastic_on = os.environ.get(ENV_ELASTIC, "") not in ("", "0", "false")
    if not timeout and not elastic_on:
        return None
    key = (timeout, elastic_on, os.environ.get(ENV_HEARTBEAT_EVERY, ""),
           os.environ.get(ENV_GANG_DIR, ""))
    if _runtime is None or _runtime_key != key:
        try:
            timeout_s = float(timeout) if timeout else None
        except ValueError:
            Log.warning("Ignoring unparseable %s=%r", ENV_TIMEOUT, timeout)
            timeout_s = None
        every = os.environ.get(ENV_HEARTBEAT_EVERY, "")
        _runtime = ElasticRuntime(
            timeout_s=timeout_s,
            heartbeat_every=int(every) if every else _DEF_HEARTBEAT_EVERY,
            gang_dir=os.environ.get(ENV_GANG_DIR) or None)
        _runtime_key = key
    return _runtime


def install(timeout_s: Optional[float] = None,
            heartbeat_every: int = _DEF_HEARTBEAT_EVERY,
            rank: Optional[int] = None,
            gang_dir: Optional[str] = None) -> ElasticRuntime:
    """Arm an elastic runtime programmatically (tests, bench)."""
    global _runtime, _runtime_key, _installed
    clear()
    _runtime = ElasticRuntime(timeout_s=timeout_s,
                              heartbeat_every=heartbeat_every,
                              rank=rank, gang_dir=gang_dir)
    _runtime_key = None
    _installed = True
    return _runtime


def clear() -> None:
    """Disarm; the next active() re-reads the environment."""
    global _runtime, _runtime_key, _installed
    if _runtime is not None and _runtime.watchdog is not None:
        _runtime.watchdog.stop()
    _runtime = None
    _runtime_key = None
    _installed = False


def notify_train_end() -> None:
    """engine.train's finally hook: legitimate end of beats — the watchdog
    must not convert post-training silence into a worker loss."""
    if _runtime is not None:
        _runtime.notify_train_end()


# ------------------------------------------------------- gang supervision

def latest_snapshot(output_model: str) -> Optional[str]:
    """Newest ``<output_model>.snapshot_iter_<k>`` with a VALID sidecar —
    what a relaunched gang resumes from. Validation runs the sidecar
    checksum (checkpoint.read_sidecar_manifest); a snapshot whose write was
    torn by the dying worker is skipped, not resumed."""
    import glob

    best: Optional[Tuple[int, str]] = None
    for path in glob.glob(output_model + ".snapshot_iter_*"):
        if path.endswith(".ckpt"):
            continue
        try:
            it = int(path.rsplit("_", 1)[1])
        except ValueError:
            continue
        if best is not None and it <= best[0]:
            continue
        try:
            from ..checkpoint import read_sidecar_manifest

            if read_sidecar_manifest(path) is None:
                continue
        except Exception:  # noqa: BLE001 - damaged snapshot: skip it
            continue
        best = (it, path)
    return best[1] if best else None


class GangSupervisor:
    """Watch a gang of worker processes; reap on first loss; optionally
    relaunch. ``spawn(world_size, rank, attempt)`` -> subprocess.Popen is
    supplied by the caller (launch.py builds CLI workers; bench.py drives
    stub commands to measure detect->reap->respawn latency in isolation).

    Loss detection: any nonzero exit, or — when ``liveness_timeout_s`` is
    set — a rank whose liveness file under ``gang_dir`` goes stale (the
    hung-not-dead case). Either way every sibling is torn down before the
    supervisor returns or relaunches: no orphaned hangs (the launch.py
    pre-elastic bug, where one dead worker left the rest blocked in
    jax.distributed barriers forever)."""

    def __init__(self, spawn: Callable[[int, int, int], subprocess.Popen],
                 nproc: int, *, elastic: bool = False, max_restarts: int = 2,
                 allow_shrink: bool = False, liveness_timeout_s: float = 0.0,
                 gang_dir: Optional[str] = None, poll_s: float = 0.1,
                 reap_grace_s: float = 5.0) -> None:
        self.spawn = spawn
        self.nproc = int(nproc)
        self.elastic = bool(elastic)
        self.max_restarts = int(max_restarts)
        self.allow_shrink = bool(allow_shrink)
        self.liveness_timeout_s = float(liveness_timeout_s)
        self.gang_dir = gang_dir
        self.poll_s = float(poll_s)
        self.reap_grace_s = float(reap_grace_s)
        self.attempts_used = 0
        self.last_recovery_ms: Optional[float] = None
        self._loss_t: Optional[float] = None

    def run(self) -> int:
        world, attempt = self.nproc, 0
        while True:
            self._clear_liveness()
            procs = [self.spawn(world, rank, attempt) for rank in range(world)]
            if self._loss_t is not None:
                # detect -> reap -> respawn latency of THIS recovery
                self.last_recovery_ms = (time.monotonic()
                                         - self._loss_t) * 1e3
                global_timer.set_count("gang_recovery_ms",
                                       int(self.last_recovery_ms))
            lost = self._watch(procs)
            if lost is None:
                return 0
            rank, rc, why = lost
            reaped = self._reap(procs)
            Log.warning("gang: worker %d lost (%s, rc=%s) at attempt %d; "
                        "reaped %d sibling(s)", rank, why, rc, attempt,
                        reaped)
            tracing.note("gang_worker_lost", rank=rank, exit_code=rc,
                         attempt=attempt, why=why, world_size=world)
            if telemetry.enabled():
                telemetry.emit("gang_worker_lost", rank=rank, exit_code=rc,
                               attempt=attempt, why=why, world_size=world)
            global_timer.add_count("gang_workers_lost", 1)
            tracing.dump_flight("gang_worker_lost", extra={
                "rank": rank, "exit_code": rc, "attempt": attempt,
                "why": why, "world_size": world}, force=True)
            if not self.elastic or attempt >= self.max_restarts:
                return rc if rc else 1
            attempt += 1
            self.attempts_used = attempt
            if self.allow_shrink and world > 1:
                world -= 1
            Log.warning("gang: elastic restart %d/%d at world size %d",
                        attempt, self.max_restarts, world)

    # ------------------------------------------------------------ watching

    def _watch(self, procs: List[subprocess.Popen]
               ) -> Optional[Tuple[int, Optional[int], str]]:
        """Block until the gang finishes cleanly (None) or a worker is
        lost: (rank, exit_code_or_None, "exit"|"liveness")."""
        while True:
            running = 0
            for rank, p in enumerate(procs):
                rc = p.poll()
                if rc is None:
                    running += 1
                elif rc != 0:
                    self._loss_t = time.monotonic()
                    return (rank, rc, "exit")
            if running == 0:
                return None
            stale = self._stale_liveness(procs)
            if stale is not None:
                self._loss_t = time.monotonic()
                return (stale, None, "liveness")
            time.sleep(self.poll_s)

    def _stale_liveness(self, procs: List[subprocess.Popen]
                        ) -> Optional[int]:
        if not self.liveness_timeout_s or not self.gang_dir:
            return None
        now = time.time()
        for rank, p in enumerate(procs):
            if p.poll() is not None:
                continue
            path = os.path.join(self.gang_dir, f"hb_{rank}")
            try:
                age = now - os.stat(path).st_mtime
            except OSError:
                continue  # never beat yet: still in startup, not stale
            if age > self.liveness_timeout_s:
                return rank
        return None

    def _clear_liveness(self) -> None:
        if not self.gang_dir:
            return
        for rank in range(self.nproc):
            try:
                os.unlink(os.path.join(self.gang_dir, f"hb_{rank}"))
            except OSError:
                pass

    def _reap(self, procs: List[subprocess.Popen]) -> int:
        """terminate -> bounded wait -> kill every survivor. Returns the
        number of processes that had to be reaped."""
        alive = [p for p in procs if p.poll() is None]
        for p in alive:
            try:
                p.terminate()
            except OSError:
                pass
        deadline = time.monotonic() + self.reap_grace_s
        for p in alive:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()
        return len(alive)


def worker_env(base: Optional[dict] = None, *, port: int, world: int,
               rank: int, attempt: int, gang_dir: Optional[str] = None,
               elastic: bool = False, devices_per_proc: int = 0) -> dict:
    """Environment block for one gang worker: the jax.distributed triple
    plus the gang markers faults.py / the watchdog key off."""
    env = dict(os.environ if base is None else base)
    env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    env["JAX_NUM_PROCESSES"] = str(world)
    env["JAX_PROCESS_ID"] = str(rank)
    env[ENV_GANG] = "1"
    env[ENV_GANG_ATTEMPT] = str(attempt)
    if gang_dir:
        env[ENV_GANG_DIR] = gang_dir
    if elastic:
        env[ENV_ELASTIC] = "1"
    if devices_per_proc:
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{devices_per_proc}").strip()
    return env
