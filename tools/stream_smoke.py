"""CI streaming smoke: out-of-core training + continuous-refit flywheel.

    python tools/stream_smoke.py [telemetry_dir]

Drives the full docs/STREAMING.md story end to end and exits nonzero on
any violated invariant:

  1. chunked-iterator ingest through RowBlockStore (no raw matrix ever
     materialized in one piece);
  2. out-of-core training under an HBM budget 4x smaller than the bin
     plane, asserted BIT-IDENTICAL to the resident train;
  3. a mid-refit injected kill, resumed bit-identically from the
     generation checkpoint while fresh pushes keep landing;
  4. a refit -> hot-swap loop against a live PredictionService under
     concurrent predict load, with zero failed predicts;
  5. a planted drift_shift fault tripping the PSI alarm (flight dump on
     disk), a sketch-driven bin-mapper refresh that measurably restores
     bin resolution while the published model stays byte-identical, and
     a poisoned generation rejected by the holdout quality gate before
     a clean retry publishes;
  6. a REAL multi-process gang (2 jax.distributed workers over gloo)
     running the gang-sharded streamed path: sketch-merged bin fit +
     budgeted tree_learner=data train asserted BIT-identical to a
     world=1 run, then a planted kill mid-generation and a surviving
     single rank resuming the partial snapshot to the same bytes.
     Set LGBM_TPU_SMOKE_NO_POD=1 to skip (e.g. sandboxes without
     loopback sockets).

When a telemetry dir is given the run records a full event stream there
(validate with `python tools/teldiff.py --self-check <dir>`).
"""
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# Phase-6 worker, written to the workdir at run time. Modes:
#   solo   -- world=1 reference: generation 0 + generation 1, no faults;
#   gang   -- one rank of the 2-process gloo gang: generation 0, then a
#             planted kill@3 that fells every rank at the same iteration
#             of generation 1, leaving the gen-1 snapshot at iteration 2;
#   resume -- the surviving rank continuing ALONE (world=1): its fresh
#             checkpoint dir holds ONLY the gang's partial gen-1
#             snapshot, so generation 0 retrains fresh and generation 1
#             resumes mid-generation from the copied checkpoint.
_POD_WORKER_SRC = '''\
import os
import sys


def main() -> int:
    mode, ckpt_dir, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
    import numpy as np

    from lightgbm_tpu.parallel.dist import init_distributed
    init_distributed()  # picks up the JAX_* triple; gloo on the CPU gang
    import jax

    from lightgbm_tpu.streaming import ContinuousTrainer, \\
        ShardedRowBlockStore
    from lightgbm_tpu.utils import faults
    from lightgbm_tpu.utils.faults import InjectedFault
    from lightgbm_tpu.utils.timer import global_timer

    world = jax.process_count()
    params = {"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1, "verbosity": -1, "min_data_in_leaf": 5,
              "tree_learner": "data", "use_quantized_grad": True}
    rng = np.random.RandomState(17)
    n, f = 2048, 8
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.3 > 0
         ).astype(np.float64)

    # gang contract: every rank receives the full push stream and owns
    # the blocks that land on its shard; the bin fit merges per-rank
    # sketches over a real cross-process allgather
    store = ShardedRowBlockStore(params=params, bin_sample_rows=1024)
    for lo in range(0, 1024, 256):
        store.push_rows(X[lo:lo + 256], label=y[lo:lo + 256])
    assert store.num_shards == world, (store.num_shards, world)
    if world > 1:
        assert global_timer.counters.get("stream_sketch_merges", 0) >= 1, \\
            "gang fit never merged sketches across ranks"

    # starved budget: 2 resident blocks of 8 -> the streamed learner
    groups = len(store._group_lists)
    os.environ["LGBM_TPU_STREAM_BLOCK_ROWS"] = "256"
    os.environ["LGBM_TPU_HBM_BUDGET"] = str(2 * groups * 256)

    tr = ContinuousTrainer(params, store, num_boost_round=5,
                           checkpoint_dir=ckpt_dir)
    b0 = tr.refit()
    with open(out_path + ".gen0", "w") as fh:
        fh.write(b0.model_to_string())
    for lo in range(1024, 2048, 256):
        store.push_rows(X[lo:lo + 256], label=y[lo:lo + 256])
    if mode == "gang":
        faults.install("kill@3")
        try:
            tr.step()
            raise AssertionError("planted kill@3 did not fire")
        except InjectedFault:
            return 0  # generation 1 died; its snapshot holds iteration 2
        finally:
            faults.clear()
    b1 = tr.step()
    with open(out_path, "w") as fh:
        fh.write(b1.model_to_string())
    return 0


if __name__ == "__main__":
    sys.exit(main())
'''


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _read(path: str) -> str:
    with open(path) as fh:
        return fh.read()


def _pod_phase() -> None:
    """Phase 6: spawn a REAL 2-process jax.distributed gang (gloo on CPU)
    through the phase-6 worker, prove the gang-sharded streamed train is
    bit-identical to a world=1 run, then resume the gang's killed
    generation on a single surviving rank and prove the SAME bytes."""
    import glob
    import shutil

    from lightgbm_tpu.parallel.elastic import worker_env

    workdir = tempfile.mkdtemp(prefix="stream-smoke-pod-")
    worker = os.path.join(workdir, "pod_worker.py")
    with open(worker, "w") as fh:
        fh.write(_POD_WORKER_SRC)

    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = _REPO
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env.pop("XLA_FLAGS", None)
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID", "LGBM_TPU_HBM_BUDGET",
              "LGBM_TPU_STREAM_BLOCK_ROWS"):
        base_env.pop(k, None)

    def run_solo(mode: str, ckpt_dir: str, out: str) -> None:
        r = subprocess.run(
            [sys.executable, worker, mode, ckpt_dir, out], env=base_env,
            cwd=_REPO, capture_output=True, text=True, timeout=480)
        assert r.returncode == 0, (
            f"pod {mode} worker rc={r.returncode}\n"
            + (r.stdout + r.stderr)[-2000:])

    solo_out = os.path.join(workdir, "solo.txt")
    run_solo("solo", os.path.join(workdir, "ckpt_solo"), solo_out)

    # the gang: 2 jax.distributed processes, 1 virtual CPU device each;
    # per-rank checkpoint dirs (identical bytes, but no shared tmp races)
    port = _free_port()
    t0 = time.monotonic()
    procs = []
    for rank in range(2):
        env = worker_env(base_env, port=port, world=2, rank=rank,
                         attempt=0, elastic=False, devices_per_proc=1)
        procs.append(subprocess.Popen(
            [sys.executable, worker, "gang",
             os.path.join(workdir, f"ckpt_gang_r{rank}"),
             os.path.join(workdir, f"gang_r{rank}.txt")],
            env=env, cwd=_REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    deadline = time.monotonic() + 480
    for p in procs:
        rc = p.wait(timeout=max(1.0, deadline - time.monotonic()))
        out = p.stdout.read()
        assert rc == 0, f"pod gang worker rc={rc}\n{out[-2000:]}"
    gang_s = time.monotonic() - t0

    solo_gen0 = _read(solo_out + ".gen0")
    gang_gen0 = _read(os.path.join(workdir, "gang_r0.txt.gen0"))
    assert gang_gen0 == _read(os.path.join(workdir, "gang_r1.txt.gen0")), \
        "gang ranks published different generation-0 models"
    assert gang_gen0 == solo_gen0, \
        "2-process sharded train diverged from the world=1 run"

    # surviving-rank resume: a fresh world=1 worker whose checkpoint dir
    # holds ONLY the gang's partial generation-1 snapshot
    partial = glob.glob(
        os.path.join(workdir, "ckpt_gang_r0", "refit_gen0001.txt*"))
    assert partial, "gang kill left no partial generation-1 snapshot"
    resume_ckpt = os.path.join(workdir, "ckpt_resume")
    os.makedirs(resume_ckpt)
    for p in partial:
        shutil.copy(p, resume_ckpt)
    resume_out = os.path.join(workdir, "resume.txt")
    run_solo("resume", resume_ckpt, resume_out)
    assert _read(resume_out + ".gen0") == solo_gen0
    assert _read(resume_out) == _read(solo_out), \
        "surviving-rank resume diverged from the undisturbed run"
    shutil.rmtree(workdir, ignore_errors=True)
    print(f"# pod: 2-process gloo gang bit-identical to world=1 and a "
          f"surviving rank resumed the killed generation to the same "
          f"bytes ({gang_s:.1f}s gang wall)")


def main() -> int:
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.engine import train
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving import PredictionService
    from lightgbm_tpu.streaming import ContinuousTrainer, RowBlockStore
    from lightgbm_tpu.utils import faults
    from lightgbm_tpu.utils.faults import InjectedFault
    from lightgbm_tpu.utils.timer import global_timer

    tel_dir = sys.argv[1] if len(sys.argv) > 1 else None
    if tel_dir:
        telemetry.start(tel_dir, label="stream_smoke")

    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
              "verbosity": -1, "min_data_in_leaf": 5}
    rng = np.random.RandomState(11)
    n, f = 4096, 10
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.3 > 0
         ).astype(np.float64)

    try:
        # -- 1. chunked-iterator ingest ---------------------------------
        def block_source(lo_hi_step=512):
            for lo in range(0, n, lo_hi_step):
                hi = min(n, lo + lo_hi_step)
                yield X[lo:hi], y[lo:hi]

        store = RowBlockStore(params=params)
        store.push_from_iterator(block_source())
        assert store.total_rows == n, store.total_rows
        ingest_ds = store.to_basic_dataset(params=params)
        print(f"# ingest: {n} rows in {n // 512} iterator blocks, "
              f"{int(global_timer.counters.get('stream_ingest_bytes', 0))} "
              "raw bytes binned")

        # -- 2. out-of-core train, bit-identical ------------------------
        resident = train(dict(params), lgb.Dataset(X, label=y),
                         num_boost_round=6)
        core = CoreDataset.from_matrix(X, label=y, config=Config(dict(params)))
        plane_bytes = core.bins.size * core.bins.dtype.itemsize
        block_bytes = core.bins.shape[0] * 256
        budget = 2 * block_bytes
        assert plane_bytes >= 4 * budget, (plane_bytes, budget)
        os.environ["LGBM_TPU_STREAM_BLOCK_ROWS"] = "256"
        os.environ["LGBM_TPU_HBM_BUDGET"] = str(budget)
        try:
            streamed = train(dict(params), ingest_ds, num_boost_round=6)
        finally:
            os.environ.pop("LGBM_TPU_HBM_BUDGET", None)
            os.environ.pop("LGBM_TPU_STREAM_BLOCK_ROWS", None)
        assert streamed.model_to_string() == resident.model_to_string(), \
            "streamed model diverged from resident"
        c = global_timer.counters
        frac = c["stream_resident_blocks"] / c["stream_blocks_total"]
        print(f"# out-of-core: bit-identical under budget={budget}B "
              f"(resident fraction {frac:.2f}, "
              f"{int(c.get('stream_h2d_blocks', 0))} block uploads)")

        # -- 3. kill mid-refit, resume bit-identically -------------------
        with tempfile.TemporaryDirectory() as ckpt_dir:
            def filled():
                s = RowBlockStore(params=params)
                for lo in range(0, 2048, 512):
                    s.push_rows(X[lo:lo + 512], label=y[lo:lo + 512])
                return s

            straight = ContinuousTrainer(
                params, filled(), num_boost_round=5,
                checkpoint_dir=os.path.join(ckpt_dir, "a")).refit()
            crashy_store = filled()
            crashy = ContinuousTrainer(
                params, crashy_store, num_boost_round=5,
                checkpoint_dir=os.path.join(ckpt_dir, "b"))
            faults.install("kill@3")
            try:
                crashy.step()
                raise AssertionError("injected kill did not fire")
            except InjectedFault:
                pass
            faults.clear()
            # fresh rows land while the refit is down; the watermark must
            # keep the retried generation pinned to the pre-crash range
            crashy_store.push_rows(X[2048:2560], label=y[2048:2560])
            resumed = crashy.step()
            assert resumed.model_to_string() == straight.model_to_string(), \
                "resumed refit diverged from uninterrupted refit"
            print("# crash-resume: generation checkpoint replayed "
                  "bit-identically with pushes landing mid-outage")

        # -- 4. refit -> hot-swap under concurrent predicts --------------
        live_store = RowBlockStore(params=params)
        live_store.push_rows(X[:1024], label=y[:1024])
        svc = PredictionService(max_batch_rows=512, batch_window_s=0.0005)
        flywheel = ContinuousTrainer(params, live_store, num_boost_round=3,
                                     service=svc, model_name="live")
        failures = []
        try:
            flywheel.refit()
            done = threading.Event()

            def hammer():
                while not done.is_set():
                    try:
                        out = svc.predict("live", X[:16], raw_score=True)
                        assert out.shape[0] == 16
                    except Exception as e:  # noqa: BLE001 - the invariant
                        failures.append(repr(e))

            threads = [threading.Thread(target=hammer) for _ in range(3)]
            for t in threads:
                t.start()
            for lo in (1024, 2048):
                live_store.push_rows(X[lo:lo + 1024], label=y[lo:lo + 1024])
                flywheel.step()
            done.set()
            for t in threads:
                t.join()
        finally:
            svc.close()
        assert failures == [], failures[:3]
        assert flywheel.generation == 3, flywheel.generation
        assert svc.registry.get("live").version == 3
        print("# flywheel: 3 generations hot-swapped, 0 failed predicts")

        # -- 5. drift alarm -> bin refresh -> quality-gated publish ------
        d_saved = {k: os.environ.get(k) for k in
                   ("LGBM_TPU_DRIFT", "LGBM_TPU_DRIFT_CHECK_ROWS",
                    "LGBM_TPU_FLIGHT_DIR")}
        # flight dumps land next to the event stream when a telemetry dir
        # is given, so the CI artifact ships the drift postmortems too
        flight_dir = tel_dir or tempfile.mkdtemp(prefix="stream-smoke-flight-")
        os.environ["LGBM_TPU_DRIFT"] = "1"
        os.environ["LGBM_TPU_DRIFT_CHECK_ROWS"] = "512"
        os.environ["LGBM_TPU_FLIGHT_DIR"] = flight_dir
        faults.install("drift_shift@1024:0")
        try:
            dstore = RowBlockStore(params=params, bin_sample_rows=1024)
            dtr = ContinuousTrainer(params, dstore, num_boost_round=3,
                                    holdout_rows=512)
            dstore.push_rows(X[:1024], label=y[:1024])
            old_text = dtr.step().model_to_string()
            for lo in range(1024, 3072, 512):
                dstore.push_rows(X[lo:lo + 512], label=y[lo:lo + 512])
            mon = dstore._drift
            assert mon is not None and mon.alarmed, "drift alarm missing"
            assert mon.alarm_feature == 0, mon.alarm_feature
            assert os.path.exists(
                os.path.join(flight_dir, "flight-drift_alarm.json")), \
                "drift alarm fired without a flight dump"
            shifted = X[1024:2048, 0] * 3.0 + 10.0  # the fault's transform
            mapper0 = dstore._layout.mappers[0]
            bins_before = len(np.unique(mapper0.values_to_bins(shifted)))
            assert dstore.maybe_refresh_bins() is True, "refresh was a no-op"
            assert dstore.layout_generation == 1
            mapper0 = dstore._layout.mappers[0]
            bins_after = len(np.unique(mapper0.values_to_bins(shifted)))
            assert bins_after > bins_before, (bins_before, bins_after)
            assert dtr.booster.model_to_string() == old_text, \
                "bin refresh mutated the published model"
            faults.clear()
            # gate: a poisoned candidate never publishes, serving untouched
            faults.install("bad_generation@1")
            assert dtr.step() is None, "poisoned generation passed the gate"
            assert dtr.generation == 1, dtr.generation
            assert dtr.booster.model_to_string() == old_text
            faults.clear()
            assert dtr.step() is not None, "clean retry failed to publish"
            assert dtr.generation == 2, dtr.generation
            print(f"# drift: alarm on feature 0, refresh restored "
                  f"{bins_before}->{bins_after} distinct bins, 1 poisoned "
                  "generation rejected, published model byte-identical")
        finally:
            faults.clear()
            for k, v in d_saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

        # -- 6. multi-process gang: sharded fit + surviving-rank resume --
        if os.environ.get("LGBM_TPU_SMOKE_NO_POD", "") not in ("1", "true"):
            _pod_phase()
        else:
            print("# pod: skipped (LGBM_TPU_SMOKE_NO_POD)")
    finally:
        if tel_dir:
            telemetry.stop()
    print("# stream smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
