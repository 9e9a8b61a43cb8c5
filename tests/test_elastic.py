"""Elastic multi-process training suite: gang supervision (reap, elastic
restart, liveness deadlines), the collective watchdog / heartbeat runtime,
shrink-to-fit resume bit-identity, and the continuous-training flywheel's
worker-loss rollback.

Gang tests run on STUB subprocess workers (no JAX startup) so detection,
reaping and relaunch policy are tested in milliseconds; the end-to-end
4-process launcher chaos scenario lives in tools/chaos_smoke.py and the
slow-marked test that drives it.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.checkpoint import (checkpoint_callback, load_checkpoint,
                                     read_sidecar_manifest, save_checkpoint)
from lightgbm_tpu.engine import train
from lightgbm_tpu.parallel import elastic
from lightgbm_tpu.parallel.elastic import (EXIT_WORKER_LOST, GangSupervisor,
                                           WorkerLostError, latest_snapshot,
                                           worker_env)
from lightgbm_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
        "verbosity": -1, "min_data_in_leaf": 5}

# the shrink-to-fit contract holds for quantized histograms (integer
# collectives are order-exact); these are the params the chain test uses
QUANT = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "tree_learner": "data", "device_type": "cpu",
         "use_quantized_grad": True, "quant_train_renew_leaf": False,
         "seed": 7}


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.clear()
    elastic.clear()


def _data(seed=7, n=500, f=10):
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.5 > 0)
    return X, y.astype(np.float64)


# ------------------------------------------------------ fault-token parsing

def test_distributed_fault_tokens_parse():
    p = faults.FaultPlan("worker_kill@1:3")
    assert p.worker_kill == (1, 3)
    p = faults.FaultPlan("worker_hang@0:2")
    assert p.worker_hang == (0, 2)
    p = faults.FaultPlan("coord_loss@4")  # sugar for worker_kill@0:4
    assert p.worker_kill == (0, 4)
    p = faults.FaultPlan("slow_worker@2:5")
    assert p.slow_worker == (2, 0.005)
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError):
        faults.FaultPlan("worker_kill@1")  # malformed rank:iter stays fatal


def test_slow_worker_fires_every_attempt(monkeypatch):
    faults.install("slow_worker@0:30")
    monkeypatch.setenv("LGBM_TPU_GANG_ATTEMPT", "1")  # not attempt 0
    t0 = time.perf_counter()
    faults.check_distributed(3)
    assert time.perf_counter() - t0 >= 0.03


# -------------------------------------------------- checkpoint world fields

def test_sidecar_carries_world_fingerprint(tmp_path):
    X, y = _data(n=300)
    bst = train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=2)
    p = str(tmp_path / "m.txt")
    save_checkpoint(bst, p)
    world = read_sidecar_manifest(p)["world"]
    assert world["process_count"] == 1
    assert world["mesh_shape"] == [1]  # serial learner: no mesh cap
    assert world["device_kinds"] == ["cpu"]
    assert world["jax_version"] not in ("", "unknown")


def test_world_mismatch_restore_warns_not_fatal(tmp_path, monkeypatch, capfd):
    """A checkpoint written under a different world restores fine but names
    both shapes in a structured warning (the named-invariant contract)."""
    import lightgbm_tpu.checkpoint as ckpt_mod

    X, y = _data(n=300)
    bst = train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=2)
    p = str(tmp_path / "m.txt")
    monkeypatch.setattr(
        ckpt_mod, "world_fingerprint",
        lambda: {"process_count": 8, "mesh_shape": [8],
                 "device_kinds": ["TPU v4"], "jax_version": "x",
                 "jaxlib_version": "x"})
    save_checkpoint(bst, p)  # sidecar now claims an 8-process TPU world
    monkeypatch.undo()
    resumed = train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=4,
                    init_model=p)
    cap = capfd.readouterr()
    txt = cap.out + cap.err
    assert "written under world" in txt
    # both shapes are NAMED in the warning (the save-side mesh_shape is
    # always the learner's actual shard count, so the fake world shows
    # through its process/device fields)
    assert "'process_count': 8" in txt
    assert "'device_kinds': ['TPU v4']" in txt
    assert "restored under {'process_count': 1" in txt
    assert resumed.current_iteration() == 4


# ------------------------------------------------------- gang supervision

_STUB = ("import sys, time\n"
         "rank, attempt, mode = sys.argv[1:4]\n"
         "rank, attempt = int(rank), int(attempt)\n"
         "if mode == 'rank1_dies' and rank == 1 and attempt == 0:\n"
         "    sys.exit(7)\n"
         "if mode == 'rank0_sleeps' and rank == 0:\n"
         "    time.sleep(60)\n"
         "if mode == 'beat_then_hang':\n"
         "    import os\n"
         "    d = sys.argv[4]\n"
         "    open(os.path.join(d, f'hb_{rank}'), 'w').write('0')\n"
         "    time.sleep(60)\n"
         "time.sleep(0.05)\n")


def _stub_spawn(mode, gang_dir=""):
    def spawn(world, rank, attempt):
        return subprocess.Popen(
            [sys.executable, "-c", _STUB, str(rank), str(attempt), mode,
             gang_dir])
    return spawn


def test_gang_reaps_siblings_on_first_loss():
    """The pre-elastic launcher bug: one dead worker must not leave the
    rest running (blocked in jax.distributed barriers) while the launcher
    waits forever. rank 1 dies instantly, rank 0 'hangs' for 60s — the
    supervisor must return the failure in well under that, with rank 0
    reaped."""
    procs_seen = []

    def spawn(world, rank, attempt):
        mode = "rank1_dies" if rank == 1 else "rank0_sleeps"
        p = _stub_spawn(mode)(world, rank, attempt)
        procs_seen.append(p)
        return p

    sup = GangSupervisor(spawn, 2, elastic=False, poll_s=0.02,
                         reap_grace_s=2.0)
    t0 = time.perf_counter()
    rc = sup.run()
    took = time.perf_counter() - t0
    assert rc == 7
    assert took < 30.0  # nowhere near rank 0's 60s sleep
    for p in procs_seen:
        assert p.poll() is not None  # nobody left behind


def test_gang_elastic_restart_recovers():
    sup = GangSupervisor(_stub_spawn("rank1_dies"), 4, elastic=True,
                         max_restarts=2, poll_s=0.02)
    assert sup.run() == 0
    assert sup.attempts_used == 1
    assert sup.last_recovery_ms is not None and sup.last_recovery_ms > 0


def test_gang_restart_budget_exhausts():
    # every attempt kills rank 1 -> budget burns down, failure surfaces
    def spawn(world, rank, attempt):
        return subprocess.Popen(
            [sys.executable, "-c",
             "import sys; sys.exit(7 if int(sys.argv[1]) == 1 else 0)",
             str(rank)])
    sup = GangSupervisor(spawn, 2, elastic=True, max_restarts=1, poll_s=0.02)
    assert sup.run() == 7
    assert sup.attempts_used == 1


def test_gang_shrink_drops_world_size():
    worlds = []

    def spawn(world, rank, attempt):
        if rank == 0:
            worlds.append(world)
        return _stub_spawn("rank1_dies")(world, rank, attempt)

    sup = GangSupervisor(spawn, 4, elastic=True, max_restarts=1,
                         allow_shrink=True, poll_s=0.02)
    assert sup.run() == 0
    assert worlds == [4, 3]


def test_gang_liveness_deadline_reaps_hung_worker(tmp_path):
    """A worker that beats once then stops (hung, not dead: exit code never
    arrives) is detected through its stale liveness file and the gang is
    reaped — the hung-not-crashed half of the fault domain."""
    gd = str(tmp_path)
    sup = GangSupervisor(_stub_spawn("beat_then_hang", gd), 2, elastic=False,
                         liveness_timeout_s=0.6, gang_dir=gd, poll_s=0.05,
                         reap_grace_s=2.0)
    t0 = time.perf_counter()
    rc = sup.run()
    assert rc == 1  # liveness loss has no exit code; the supervisor's own
    assert time.perf_counter() - t0 < 30.0


def test_worker_env_builds_gang_block(tmp_path):
    env = worker_env({}, port=12345, world=4, rank=2, attempt=1,
                     gang_dir=str(tmp_path), elastic=True,
                     devices_per_proc=2)
    assert env["JAX_COORDINATOR_ADDRESS"] == "127.0.0.1:12345"
    assert env["JAX_NUM_PROCESSES"] == "4"
    assert env["JAX_PROCESS_ID"] == "2"
    assert env["LGBM_TPU_GANG"] == "1"
    assert env["LGBM_TPU_GANG_ATTEMPT"] == "1"
    assert env["LGBM_TPU_ELASTIC"] == "1"
    assert "host_platform_device_count=2" in env["XLA_FLAGS"]


def test_latest_snapshot_skips_torn_sidecar(tmp_path):
    X, y = _data(n=300)
    out = str(tmp_path / "model.txt")
    train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=4,
          callbacks=[checkpoint_callback(
              lambda it: f"{out}.snapshot_iter_{it}", period=2)])
    assert latest_snapshot(out).endswith(".snapshot_iter_4")
    # tear the newest snapshot's sidecar: resume must fall back to iter 2
    os.unlink(f"{out}.snapshot_iter_4.ckpt")
    assert latest_snapshot(out).endswith(".snapshot_iter_2")


# --------------------------------------------- watchdog / heartbeat runtime

def test_watchdog_converts_hang_to_worker_lost(tmp_path, monkeypatch):
    """A planted worker_hang blocks the training loop; the collective
    watchdog converts the block into a typed WorkerLostError — rank +
    last-good iteration — within the timeout, and dumps a flight
    postmortem."""
    monkeypatch.setenv("LGBM_TPU_FLIGHT_DIR", str(tmp_path))
    X, y = _data(n=300)
    # compile every program the run dispatches before the 2 s watchdog is
    # armed: on a loaded machine a first iteration's compiles outlast it,
    # and it fires at iteration 0 instead of at the planted hang
    train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=3)
    elastic.install(timeout_s=2.0)
    faults.install("worker_hang@0:2")
    t0 = time.perf_counter()
    with pytest.raises(WorkerLostError) as ei:
        train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=6)
    took = time.perf_counter() - t0
    assert ei.value.rank == 0
    assert ei.value.last_good_iteration == 2
    assert took < 20.0  # detection bounded by the timeout, not the hang
    dumps = [f for f in os.listdir(str(tmp_path)) if "worker_lost" in f]
    assert dumps, os.listdir(str(tmp_path))
    payload = json.loads(open(os.path.join(str(tmp_path), dumps[0])).read())
    assert payload["extra"]["rank"] == 0
    assert payload["extra"]["last_good_iteration"] == 2


def test_watchdog_disarms_at_train_end():
    X, y = _data(n=300)
    rt = elastic.install(timeout_s=2.0)
    train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=2)
    # post-training silence is legitimate: the watchdog must not fire even
    # after the deadline (plus a poll cycle) has long passed
    assert not rt.watchdog._armed
    time.sleep(2.5)
    assert rt.watchdog.error is None


def test_heartbeat_rides_health_window():
    """With a HealthMonitor armed, the heartbeat token piggybacks on its
    sync slot; the self-windowed path stays quiet (no double sync)."""
    from lightgbm_tpu.utils.timer import global_timer

    X, y = _data(n=300)
    base = int(global_timer.counters.get("elastic_heartbeats", 0))
    elastic.install(timeout_s=None, heartbeat_every=1)
    train({**BASE, "health_check_policy": "warn", "health_check_every": 2},
          lgb.Dataset(X, label=y), num_boost_round=4)
    rode = int(global_timer.counters.get("elastic_heartbeats", 0)) - base
    assert rode == 2  # one per health window (4 iters / check_every 2)


def test_heartbeat_detects_short_token(monkeypatch):
    rt = elastic.install(timeout_s=None, heartbeat_every=1)
    # a completed-but-short psum means the mesh lost cardinality: fake the
    # collective to answer with fewer participants than the world
    rt._hb = (lambda x: x, 6.0, 8)
    monkeypatch.setattr("lightgbm_tpu.parallel.dist.host_value",
                        lambda x: x)
    with pytest.raises(WorkerLostError) as ei:
        rt.heartbeat_sync(iteration=5)
    assert "6/8" in str(ei.value)
    assert ei.value.last_good_iteration == 5


def test_exit_codes_are_distinct():
    # the supervisor's log keys off these; collisions would mislabel losses
    from lightgbm_tpu.utils.faults import EXIT_INJECTED_KILL

    assert EXIT_WORKER_LOST != EXIT_INJECTED_KILL
    assert EXIT_WORKER_LOST not in (0, 1, 2)


# ---------------------------------------------- shrink-to-fit bit-identity

def test_shrink_resume_8_4_1_bit_identical(tmp_path, monkeypatch):
    """THE shrink-to-fit contract: a quantized data-parallel run
    checkpointed on the 8-device mesh, resumed on 4, then resumed again on
    1, produces byte-identical model text to the undisturbed 8-device run.
    Mesh shrinkage is forced via LGBM_TPU_FORCE_MESH_DEVICES (num_machines
    cannot express the 1-device leg and echoes into the model text)."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    X, y = _data(seed=42, n=1600, f=10)
    ck = str(tmp_path / "chain.txt")

    undisturbed = train(dict(QUANT), lgb.Dataset(X, label=y),
                        num_boost_round=6)

    def leg(boost_to, devices, resume):
        if devices:
            monkeypatch.setenv("LGBM_TPU_FORCE_MESH_DEVICES", str(devices))
        else:
            monkeypatch.delenv("LGBM_TPU_FORCE_MESH_DEVICES", raising=False)
        bst = train(dict(QUANT), lgb.Dataset(X, label=y),
                    num_boost_round=boost_to,
                    init_model=ck if resume else None,
                    callbacks=[checkpoint_callback(ck, period=2)])
        monkeypatch.delenv("LGBM_TPU_FORCE_MESH_DEVICES", raising=False)
        return bst

    leg(2, devices=0, resume=False)   # 8-device leg writes iter-2 state
    assert load_checkpoint(ck).iteration == 2
    assert read_sidecar_manifest(ck)["world"]["mesh_shape"] == [8]
    leg(4, devices=4, resume=True)    # shrink to 4
    assert read_sidecar_manifest(ck)["world"]["mesh_shape"] == [4]
    chained = leg(6, devices=1, resume=True)  # shrink to 1

    assert (chained.model_to_string(num_iteration=-1)
            == undisturbed.model_to_string(num_iteration=-1))


# -------------------------------------------------- flywheel worker loss

def test_flywheel_worker_loss_rolls_back_and_keeps_serving(tmp_path):
    """A gang peer lost mid-refit: the generation rolls back to its pinned
    checkpoint (no publish, watermark stays pinned), the serving front
    keeps answering from the last published model, and the NEXT refit
    resumes the same row range and publishes."""
    from lightgbm_tpu.serving import ModelRegistry
    from lightgbm_tpu.streaming import ContinuousTrainer, RowBlockStore

    X, y = _data(n=600)
    params = dict(BASE)
    store = RowBlockStore(params=params)
    store.push_rows(X[:400], label=y[:400])
    reg = ModelRegistry()
    tr = ContinuousTrainer(params, store, num_boost_round=4,
                           checkpoint_dir=str(tmp_path), registry=reg,
                           model_name="live")
    first = tr.step()  # generation 0 publishes cleanly
    assert first is not None and tr.generation == 1
    baseline = np.asarray(reg.get("live").predict(X[:32], raw_score=True))

    store.push_rows(X[400:], label=y[400:])
    elastic.install(timeout_s=2.0)
    faults.install("worker_hang@0:2")
    assert tr.step() is None          # worker lost mid-refit: no publish
    faults.clear()
    elastic.clear()
    assert tr.generation == 1         # generation did NOT advance
    assert tr._inflight_rows == 600   # watermark stays pinned
    # serving kept the last published model the whole time
    np.testing.assert_array_equal(
        np.asarray(reg.get("live").predict(X[:32], raw_score=True)),
        baseline)

    second = tr.step()                # resumes the SAME pinned row range
    assert second is not None
    assert tr.generation == 2
    assert tr._inflight_rows is None
    # the new generation is now live
    assert not np.array_equal(
        np.asarray(reg.get("live").predict(X[:32], raw_score=True)),
        baseline)


# ----------------------------------------------------- end-to-end chaos

@pytest.mark.slow
def test_chaos_smoke_end_to_end(tmp_path):
    """Drive tools/chaos_smoke.py: a 4-process --elastic launcher gang with
    a planted worker_kill@1:3 must produce a byte-identical model to the
    undisturbed gang, plus a gang_worker_lost flight dump naming rank 1."""
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_smoke.py"),
         str(tmp_path / "chaos")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": _REPO})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["byte_equal"] is True
    assert report["flight_rank"] == 1
