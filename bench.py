"""Training-throughput benchmark vs the reference's HIGGS baseline.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "platform",
"device", ...} and exits 0 — or raises and exits non-zero. It needs a TPU:
no chip, a kernel that will not lower, or an out-of-memory error ends the
run; nothing is retried on the CPU, through the XLA histogram or at fewer
rows. BENCH_PLATFORM=cpu is the one explicit way to run it on the CPU (the
smoke test does), and the record then says platform "cpu". A record that
carries any `*_error` field from a secondary capture is still printed, and
the exit code is 1.

Reference anchor (BASELINE.md): LightGBM CPU trains HIGGS — 10.5M rows x 28
features, 500 iterations, 255 leaves — in 130.094 s (docs/Experiments.rst:113),
i.e. 10.5e6 * 500 / 130.094 = 40.36M row-iterations/second. HIGGS itself
cannot be downloaded in this sandbox (zero egress), so the bench trains on a
synthetic dataset with the HIGGS shape profile (28 dense numerical features,
binary labels, max_bin=255, num_leaves=255) and reports the same
row-iterations/second measure; vs_baseline = ours / 40.36e6 (>1 is faster).

BENCH_ROWS defaults to HIGGS's own 10.5M, which the whole-tree program does
not fit on a 16 GB chip today (CHANGES.md PR 22: the [N, k] row-payload
arrays pad to 128 lanes); pass the row count the chip holds.

One process: the backend is initialised here and nowhere else — a chip
belongs to one process, so nothing probes it from a child.
"""
import json
import os
import sys
import time

N_ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))  # true HIGGS rows
N_FEATURES = 28
N_ITERS = int(os.environ.get("BENCH_ITERS", 5))
WARMUP_ITERS = 2
BASELINE_ROW_ITERS_PER_SEC = 10_500_000 * 500 / 130.094


def emit(record: dict) -> None:
    sys.stdout.flush()
    print(json.dumps(record), flush=True)


def make_data(n_rows: int, seed: int = 42):
    import numpy as np

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_rows, N_FEATURES), dtype=np.float32)
    w = rng.standard_normal(N_FEATURES, dtype=np.float32)
    logit = X[:5_000_000] @ w  # cap the label-gen matmul cost
    if n_rows > logit.shape[0]:
        logit = np.concatenate([logit, X[5_000_000:] @ w])
    noise = rng.standard_normal(n_rows, dtype=np.float32)
    y = (logit + noise > 0).astype(np.float64)
    return X, y


def _auc(y, score) -> float:
    """Mann-Whitney AUC with midranks for ties (tree scores tie often;
    ordinal ranks would make the number order-dependent)."""
    import numpy as np

    score = np.asarray(score)
    order = np.argsort(score, kind="stable")
    sorted_s = score[order]
    ranks = np.empty(len(score))
    # average rank within each tied group
    uniq, start, counts = np.unique(sorted_s, return_index=True,
                                    return_counts=True)
    del uniq
    group_mid = start + (counts + 1) / 2.0  # 1-based midrank per group
    grp = np.repeat(np.arange(len(start)), counts)
    ranks[order] = group_mid[grp]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return 0.5
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def _wave_traffic_fields(ds) -> dict:
    """HBM-traffic instrumentation for the bandwidth model in
    docs/PERF_NOTES.md: rows actually histogrammed (a counter the device
    learner publishes) and the bytes of loop carry each wave drags through
    HBM. Both fields are ALWAYS present — when the run never dispatched the
    device learner (BENCH_PLATFORM=cpu runs use the serial learner), the
    carry estimate is recomputed from the dataset shape with the same
    formula DeviceTreeLearner._record_carry_bytes uses, and the row
    counter reports 0.
    """
    from lightgbm_tpu.utils.timer import global_timer

    fields = {"device_hist_rows":
              int(global_timer.counters.get("device_hist_rows", 0))}
    carry = global_timer.counters.get("device_carry_bytes_per_wave")
    if carry is None:
        from lightgbm_tpu import perfmodel
        from lightgbm_tpu.ops.compact_pallas import COMPACT_TILE
        from lightgbm_tpu.ops.hist_pallas import DEFAULT_TILE_ROWS

        core = ds._handle
        unit = max(DEFAULT_TILE_ROWS, COMPACT_TILE)
        plane_b = 1 if core.bins.dtype.itemsize == 1 else 4
        carry = perfmodel.carry_bytes_per_wave(
            core.num_data, core.bins.shape[0], plane_b, unit)
    fields["est_carried_bytes_per_wave"] = int(carry)
    return fields


def _kernel_micro_fields(ds, n_rows: int) -> dict:
    """Per-dispatch microlatency of the round-8 kernels, measured with the
    session's real dataset shapes on this backend:

    * scan_kernel_ms: one `find_best_split` dispatch (the same call the
      serial learner's per-leaf scan makes: the XLA scan of ops/split.py);
    * goss_device_gather_ms: one jitted GOSS select (score + stable
      argsort + top-rate mask + small-gradient rescale) at the training
      row count — the work the device bag keeps off the host.
    """
    import numpy as np

    out = {}
    rng = np.random.default_rng(7)
    try:
        import jax.numpy as jnp

        from lightgbm_tpu.ops.histogram import build_histogram
        from lightgbm_tpu.ops.split import find_best_split, make_feature_meta

        core = ds._handle
        s = min(core.num_data, 100_000)
        g = rng.standard_normal(s, dtype=np.float32)
        h = np.abs(rng.standard_normal(s, dtype=np.float32)) + 0.1
        gh = jnp.asarray(np.stack([g, h, np.ones(s, np.float32)], axis=1))
        B = int(core.group_bin_counts().max())
        hist = build_histogram(jnp.asarray(core.bins[:, :s]), gh, B)
        meta = make_feature_meta(core, B)
        pvec = jnp.asarray([0, 0, 20, 1e-3, 0, 0], dtype=jnp.float32)
        totals = hist[0].sum(axis=0).astype(jnp.float32)
        find_best_split(hist, totals, meta, pvec).block_until_ready()
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            rec = find_best_split(hist, totals, meta, pvec)
        rec.block_until_ready()
        out["scan_kernel_ms"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3)
    except Exception as e:  # noqa: BLE001 - secondary must not kill primary
        out["scan_kernel_error"] = repr(e)[:200]
    try:
        import jax
        import jax.numpy as jnp

        from lightgbm_tpu.models import sample_strategy as ss

        n = min(n_rows, 1_000_000)
        gd = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        hd = jnp.asarray(
            np.abs(rng.standard_normal(n, dtype=np.float32)) + 0.1)
        top_k = max(int(np.ceil(n * 0.2)), 1)
        n_sampled = min(int(np.ceil(n * 0.1)), n - top_k)
        pos = jnp.asarray(rng.choice(
            n - top_k, n_sampled, replace=False).astype(np.int32))
        from functools import partial

        select = jax.jit(partial(ss._goss_select, top_k=top_k))
        mult = jnp.float32(8.0)
        select(gd, hd, pos, mult)[1].block_until_ready()
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            _, gr, _ = select(gd, hd, pos, mult)
        gr.block_until_ready()
        out["goss_device_gather_ms"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3)
    except Exception as e:  # noqa: BLE001 - secondary must not kill primary
        out["goss_kernel_error"] = repr(e)[:200]
    return out


def _bench_gang_recovery() -> dict:
    """Measure one detect -> reap -> respawn cycle of the elastic gang
    supervisor on stub workers (rank 1 exits nonzero on attempt 0; the
    relaunched gang exits clean). Stubs keep the number a pure supervisor
    latency — no JAX startup, no coordinator barrier — so regressions in
    the watch/reap loop itself are visible under the ledger gate."""
    import subprocess as sp

    from lightgbm_tpu.parallel.elastic import GangSupervisor

    code = ("import sys, time\n"
            "rank, attempt = int(sys.argv[1]), int(sys.argv[2])\n"
            "if attempt == 0 and rank == 1:\n"
            "    sys.exit(7)\n"
            "time.sleep(0.05)\n")

    def spawn(world, rank, attempt):
        return sp.Popen([sys.executable, "-c", code, str(rank), str(attempt)])

    try:
        sup = GangSupervisor(spawn, 4, elastic=True, max_restarts=1,
                             poll_s=0.02)
        rc = sup.run()
        if rc == 0 and sup.last_recovery_ms is not None:
            return {"gang_recovery_ms": round(sup.last_recovery_ms, 2)}
        return {"gang_error": f"supervisor rc={rc}, "
                              f"recovery_ms={sup.last_recovery_ms}"}
    except Exception as e:  # noqa: BLE001 - secondary must not kill primary
        return {"gang_error": repr(e)[:200]}


def _bench_voting_fields() -> dict:
    """Pod-scale learner comm capture (docs/PERF_NOTES.md round-9): grow
    trees over the same wide dataset (F=256 — the regime the PV-Tree
    voting scheme is priced for) with the data-parallel, voting-parallel
    and feature-parallel device learners plus the single-device baseline,
    and record

    * the per-wave ICI gauges each learner publishes — the three-way comm
      model: full-histogram psum_scatter (data) vs nomination gather +
      elected-slice psum (voting) vs best-record all_gather (feature);
    * device_ici_overlap_pct — the share of the elected-slice reduction
      the double-buffered dispatch hides behind partition/commit;
    * voting_miss_total under LGBM_TPU_VOTING_EXACT_CHECK=1: elections
      where the full reduction disagreed with the committed split (0 on a
      single shard, where the local argmax is always nominated);
    * scaling_efficiency_{data,voting,feature}: measured rows/s against
      D x the single-device learner's.

    Smoke-asserted on the spot: voting must move strictly fewer bytes per
    wave than data-parallel, and feature-parallel fewer than voting — the
    ordering the round-9 model predicts at F=256, top_k=20.
    """
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    from lightgbm_tpu.parallel.learners import (
        DeviceDataParallelTreeLearner, DeviceFeatureParallelTreeLearner,
        VotingDataParallelTreeLearner)
    from lightgbm_tpu.treelearner.device import DeviceTreeLearner
    from lightgbm_tpu.utils.timer import global_timer

    n, f = 4096, 256
    rng = np.random.default_rng(11)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.25 * X[:, 2] > 0).astype(np.float32)
    g = (0.5 - y + 0.1 * rng.standard_normal(n)).astype(np.float32)
    gh = np.stack([g, np.full(n, 0.25, np.float32),
                   np.ones(n, np.float32)], axis=1)
    gh_ext = jnp.asarray(
        np.concatenate([gh, np.zeros((1, 3), np.float32)]))
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 64,
              "min_data_in_leaf": 20, "top_k": 20, "verbosity": -1}

    def _train(cls):
        cfg = Config(params)
        ds = CoreDataset.from_matrix(X, label=y, config=cfg)
        learner = cls(cfg, ds)
        learner.finalize(learner.train_async(gh_ext))  # compile warmup
        t0 = time.perf_counter()
        learner.finalize(learner.train_async(gh_ext))
        return learner, time.perf_counter() - t0

    _, single_s = _train(DeviceTreeLearner)
    _GAUGES = ("device_ici_bytes_per_wave", "voting_ici_bytes_per_wave",
               "feature_ici_bytes_per_wave", "device_ici_overlap_pct",
               "voting_miss_total")
    out, ici = {}, {}
    for key, cls in (("data", DeviceDataParallelTreeLearner),
                     ("voting", VotingDataParallelTreeLearner),
                     ("feature", DeviceFeatureParallelTreeLearner)):
        for c in _GAUGES:
            global_timer.counters.pop(c, None)
        saved = os.environ.get("LGBM_TPU_VOTING_EXACT_CHECK")
        if key == "voting":
            os.environ["LGBM_TPU_VOTING_EXACT_CHECK"] = "1"
        try:
            learner, el = _train(cls)
        finally:
            if key == "voting":
                if saved is None:
                    os.environ.pop("LGBM_TPU_VOTING_EXACT_CHECK", None)
                else:
                    os.environ["LGBM_TPU_VOTING_EXACT_CHECK"] = saved
        ici[key] = int(global_timer.counters["device_ici_bytes_per_wave"])
        out[f"scaling_efficiency_{key}"] = round(
            single_s / (learner.D * el), 4) if el > 0 else 0.0
        if key == "voting":
            out["voting_ici_bytes_per_wave"] = int(
                global_timer.counters["voting_ici_bytes_per_wave"])
            out["device_ici_overlap_pct"] = int(
                global_timer.counters["device_ici_overlap_pct"])
            out["voting_miss_total"] = int(
                global_timer.counters.get("voting_miss_total", 0))
        elif key == "feature":
            out["feature_ici_bytes_per_wave"] = int(
                global_timer.counters["feature_ici_bytes_per_wave"])
    assert out["voting_ici_bytes_per_wave"] < ici["data"], (
        "voting moved more ICI bytes than the full reduction", out, ici)
    assert out["feature_ici_bytes_per_wave"] < out[
        "voting_ici_bytes_per_wave"], (
        "feature-parallel should be the cheapest wire", out)
    return out


def run_bench(n_rows: int) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry

    holdout = min(200_000, n_rows // 5)
    Xall, yall = make_data(n_rows + holdout)
    # true holdout: rows NEVER seen by training
    Xh, yh = Xall[:holdout], yall[:holdout]
    X, y = Xall[holdout:], yall[holdout:]
    params = {
        "objective": "binary",
        "num_leaves": 255,
        "learning_rate": 0.1,
        "max_bin": 255,
        "min_data_in_leaf": 100,
        "verbosity": -1,
    }
    # aggregate-only telemetry session (no files): counts jit compiles and
    # samples HBM high-water so the capture record attributes regressions
    # (recompile churn vs memory pressure) instead of just restating them
    telemetry.start(None, label="bench")
    try:
        ds = lgb.Dataset(X, label=y)
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(WARMUP_ITERS):  # compile + cache warmup, not timed
            bst.update()
        t0 = time.perf_counter()
        for _ in range(N_ITERS):
            bst.update()
        elapsed = time.perf_counter() - t0
        rips = n_rows * N_ITERS / elapsed
        out = {"row_iters_per_sec": rips, "elapsed_s": elapsed,
               "rows": n_rows, "iters": N_ITERS,
               "auc": round(_auc(yh, bst.predict(Xh)), 4)}
        out.update(_wave_traffic_fields(ds))

        # cost-model attribution (perfmodel.py): measured per-stage walls
        # from the timer, the analytic byte model from the published
        # gauges, and XLA's own cost_analysis() for each captured dispatch
        # — taken NOW, before the guardrail/telemetry short trains below
        # pollute the timer totals with their own boosting scopes
        from lightgbm_tpu import perfmodel
        from lightgbm_tpu.utils.timer import global_timer

        # wave accounting: the observed commit rate and the wave width
        # (the record keeps its old field name; both 0 when the run never
        # dispatched the device learner), plus the per-dispatch
        # microlatency of the split scan and the device GOSS select at
        # this session's shapes
        spec = int(global_timer.counters.get("wave_splits_speculated", 0))
        out["wave_commit_rate"] = round(
            int(global_timer.counters.get("wave_splits_committed", 0))
            / spec, 4) if spec else 0.0
        out["adaptive_k_final"] = int(
            global_timer.counters.get("wave_k", 0))
        out.update(_kernel_micro_fields(ds, n_rows))

        try:
            import jax

            devs = jax.devices()
            kind = str(devs[0].device_kind) if devs else ""
        except Exception:  # noqa: BLE001 - attribution is best-effort
            kind = ""
        out["attribution"] = perfmodel.attribution(
            dict(global_timer.totals), dict(global_timer.counters),
            device_kind=kind, include_static=True)

        # inference throughput: chunked streaming predict over the train
        # matrix (the serving configuration — double-buffered
        # H2D/compute/D2H overlap)
        from lightgbm_tpu.ops.partition import bucket_size

        pred_chunk = min(1 << 20, bucket_size(max(n_rows // 4, 1), 1024))
        bst.predict(X, raw_score=True, pred_chunk_rows=pred_chunk)  # warmup
        t0 = time.perf_counter()
        bst.predict(X, raw_score=True, pred_chunk_rows=pred_chunk)
        pe = time.perf_counter() - t0
        out["predict_rows_per_sec"] = round(n_rows / pe, 1)
        out["predict_chunk_rows"] = pred_chunk

        # serving-layer throughput: an open-loop generator firing fixed-size
        # requests over HTTP at the hardened prediction service
        # (docs/SERVING.md) — the full request path, so the tracing stage
        # histograms decompose the serve-vs-direct gap into named numbers
        # (parse / queue_wait / assembly / device / d2h / serialize)
        import json as json_mod
        import threading
        import urllib.request

        import numpy as np

        from lightgbm_tpu import tracing
        from lightgbm_tpu.serving import PredictionService
        from lightgbm_tpu.serving.http import serve as serve_http

        serve_rows = 64
        serve_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", 300))
        tracing.reset_stats()  # this section owns the stage quantiles
        # min_bucket matches the 64-row request size: the coalescing beat
        # only helps when a batch is still below one bucket, and a 256-row
        # floor made every ~3-request batch pay the full window
        svc = PredictionService(max_batch_rows=4096, min_bucket=64,
                                batch_window_s=0.001)
        server = None
        try:
            svc.load_model("bench", booster=bst)
            server, _ = serve_http(svc, port=0)
            url = f"http://127.0.0.1:{server.port}/predict"
            span = max(X.shape[0] - serve_rows, 1)
            # request bodies built outside the timed loop: client-side
            # encoding is the generator's cost, not the service's
            bodies = [json_mod.dumps(
                {"model": "bench", "raw_score": True,
                 "rows": X[(i * serve_rows) % span:
                           (i * serve_rows) % span + serve_rows].tolist()}
            ).encode() for i in range(serve_requests)]
            served = []

            def fire(i):
                req = urllib.request.Request(
                    url, data=bodies[i],
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                served.append(i)

            t0 = time.perf_counter()
            threads = []
            for i in range(serve_requests):
                th = threading.Thread(target=fire, args=(i,))
                th.start()
                threads.append(th)
                time.sleep(0.0005)  # open loop: fixed arrival rate
            for th in threads:
                th.join()
            serve_s = time.perf_counter() - t0
            sstats = svc.batcher.stats()
            out["serve_rows_per_sec"] = round(
                len(served) * serve_rows / serve_s, 1)
            out["serve_p50_ms"] = round(sstats.get("p50_ms", 0.0), 3)
            out["serve_p99_ms"] = round(sstats.get("p99_ms", 0.0), 3)
            out["serve_batches"] = int(sstats["batches"])
            stages = svc.stats().get("stages", {})
            for stage, field in (("parse", "serve_parse_ms_p99"),
                                 ("queue_wait", "serve_queue_ms_p99"),
                                 ("assembly", "serve_assembly_ms_p99"),
                                 ("device", "serve_device_ms_p99"),
                                 ("d2h", "serve_d2h_ms_p99"),
                                 ("serialize", "serve_serialize_ms_p99")):
                out[field] = round(
                    stages.get(stage, {}).get("p99_ms", 0.0), 3)

            # binary wire format (serving/wire.py): the SAME rows as raw
            # f32 frames, zero-copy decoded server-side. Open-loop like
            # the JSON drive above: each persistent connection pipelines
            # its requests (send all frames, then drain the responses) so
            # the wire cost — not per-round-trip latency — is what's
            # measured; the JSON scenario is untouched for cross-PR
            # comparability
            import socket

            from lightgbm_tpu.serving import wire as wire_mod

            wire_workers = 16
            per_worker = max(1, serve_requests // wire_workers)

            def _wire_http(frame):
                return (b"POST /predict HTTP/1.1\r\nHost: bench\r\n"
                        b"Content-Type: " + wire_mod.CONTENT_TYPE.encode()
                        + b"\r\nContent-Length: " + str(len(frame)).encode()
                        + b"\r\n\r\n" + frame)

            frames = [_wire_http(wire_mod.encode_request(
                "bench",
                np.ascontiguousarray(
                    X[(i * serve_rows) % span:
                      (i * serve_rows) % span + serve_rows],
                    dtype=np.float32),
                raw_score=True)) for i in range(wire_workers)]
            wire_rows = [0] * wire_workers

            def fire_wire(w):
                sock = socket.create_connection(
                    ("127.0.0.1", server.port), timeout=60)
                sock.setsockopt(  # no Nagle stall between frames
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                payload = frames[w] * per_worker
                sender = threading.Thread(
                    target=lambda: sock.sendall(payload))
                sender.start()
                fh = sock.makefile("rb")
                try:
                    for _ in range(per_worker):
                        status = fh.readline()
                        clen = 0
                        while True:
                            line = fh.readline()
                            if not line or line == b"\r\n":
                                break
                            if line.lower().startswith(b"content-length:"):
                                clen = int(line.split(b":")[1])
                        fh.read(clen)
                        if b" 200 " in status:
                            wire_rows[w] += serve_rows
                finally:
                    sender.join()
                    fh.close()
                    sock.close()

            t0 = time.perf_counter()
            threads = [threading.Thread(target=fire_wire, args=(w,))
                       for w in range(wire_workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wire_s = time.perf_counter() - t0
            out["serve_wire_binary_rows_per_sec"] = round(
                sum(wire_rows) / wire_s, 1)
        finally:
            if server is not None:
                server.shutdown()
            svc.close()

        # replica cold start: persist the model + its AOT executable
        # bundle, drop every compile cache (a fresh process stand-in), and
        # time load -> first bucket-shaped answer, with and without the
        # bundle — the serve_cold_start_ms vs *_compile_ms gap is what the
        # warm-start tentpole buys a scale-out event
        import tempfile as _tmp

        import jax as _jax

        from lightgbm_tpu.checkpoint import save_checkpoint as _save_ckpt

        with _tmp.TemporaryDirectory() as td:
            mpath = os.path.join(td, "bench_model.txt")
            _save_ckpt(bst, mpath)
            svc_w = PredictionService(max_batch_rows=1024,
                                      batch_window_s=0.0)
            try:
                svc_w.load_model("warm", path=mpath)
                svc_w.export_aot("warm")
            finally:
                svc_w.close()
            probe = np.ascontiguousarray(X[:256], dtype=np.float32)

            def _cold_ms(drop_aot):
                if drop_aot:
                    os.remove(mpath + ".aot")
                _jax.clear_caches()
                svc_c = PredictionService(max_batch_rows=1024,
                                          batch_window_s=0.0)
                try:
                    t0 = time.perf_counter()
                    svc_c.load_model("cold", path=mpath)
                    svc_c.predict("cold", probe, raw_score=True)
                    return (time.perf_counter() - t0) * 1e3
                finally:
                    svc_c.close()

            out["serve_cold_start_ms"] = round(_cold_ms(False), 1)
            out["serve_cold_start_compile_ms"] = round(_cold_ms(True), 1)

        # fleet dispatch: throughput of one hot model on one replica vs
        # two hot models pinned to two replicas, closed-loop in-process
        # callers — perfect scaling is 1.0, contention shows below it
        from lightgbm_tpu import perfmodel as _perfmodel

        def _fleet_rows_per_sec(n_entries, replicas):
            svc_f = PredictionService(max_batch_rows=4096,
                                      batch_window_s=0.0,
                                      replicas=replicas)
            block = np.ascontiguousarray(X[:serve_rows], dtype=np.float32)
            reqs = max(50, serve_requests // 2)
            try:
                for i in range(n_entries):
                    svc_f.load_model(f"rep{i}", booster=bst)

                def drive(name):
                    for _ in range(reqs):
                        svc_f.predict(name, block, raw_score=True)

                drivers = [threading.Thread(target=drive, args=(f"rep{i}",))
                           for i in range(n_entries) for _ in range(2)]
                t0 = time.perf_counter()
                for th in drivers:
                    th.start()
                for th in drivers:
                    th.join()
                dt = time.perf_counter() - t0
                return 2 * n_entries * reqs * serve_rows / dt
            finally:
                svc_f.close()

        fleet_t1 = _fleet_rows_per_sec(1, 1)
        fleet_t2 = _fleet_rows_per_sec(2, 2)
        out["serve_replica_scaling_efficiency"] = \
            _perfmodel.serve_replica_scaling_efficiency(fleet_t1, fleet_t2, 2)

        # robustness-layer cost: one full-state checkpoint write of the
        # trained model (model text + sidecar, atomic + fsync) ...
        import tempfile

        from lightgbm_tpu.checkpoint import save_checkpoint

        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            save_checkpoint(bst, os.path.join(td, "bench_model.txt"))
            out["checkpoint_write_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
    finally:
        tel_summary = telemetry.stop()
    out["compile_count"] = int(tel_summary["compile_count"])
    out["hbm_high_water_bytes"] = int(tel_summary["hbm_high_water_bytes"])

    # ... and the numerical-health guardrail at its most expensive setting
    # (policy=warn, sync every iteration) vs the same short train without it
    g_rows = min(n_rows, 100_000)
    Xg, yg = X[:g_rows], y[:g_rows]

    def _short_train(extra: dict) -> float:
        dg = lgb.Dataset(Xg, label=yg)
        bg = lgb.Booster(params={**params, **extra}, train_set=dg)
        for _ in range(WARMUP_ITERS):
            bg.update()
        t0 = time.perf_counter()
        for _ in range(N_ITERS):
            bg.update()
        return time.perf_counter() - t0

    base_s = _short_train({})
    guard_s = _short_train({"health_check_policy": "warn",
                            "health_check_every": 1})
    out["guardrail_overhead_pct"] = round((guard_s / base_s - 1.0) * 100.0, 2)

    # ... and the elastic collective heartbeat at its most aggressive
    # cadence (the psum health token EVERY iteration; the production
    # default is every 10th, riding the health monitor's existing sync
    # slot) vs the same short train with elastic mode off
    from lightgbm_tpu.parallel import elastic

    elastic.install(timeout_s=None, heartbeat_every=1)
    try:
        hb_s = _short_train({})
    finally:
        elastic.clear()
    out["heartbeat_overhead_pct"] = round((hb_s / base_s - 1.0) * 100.0, 2)

    # ... and one elastic gang recovery (detect -> reap -> respawn) on stub
    # workers, isolating the supervisor's loop latency from JAX startup
    out.update(_bench_gang_recovery())

    # ... and the telemetry stack at full tilt (file sinks + watchers + span
    # capture) vs the same short train with it off — the <1% overhead claim,
    # measured on every capture (can be negative on noisy hosts)
    with tempfile.TemporaryDirectory() as tel_td:
        from lightgbm_tpu import telemetry as _tel

        with _tel.capture(tel_td, label="bench-overhead"):
            tel_s = _short_train({})
    out["telemetry_overhead_pct"] = round((tel_s / base_s - 1.0) * 100.0, 2)

    # secondary quantized capture defaults ON only at moderate sizes — at
    # full HIGGS scale it would double the compile + train time
    quant_default = "1" if n_rows <= 4_000_000 else "0"
    if os.environ.get("BENCH_QUANTIZED", quant_default) not in ("0", "false"):
        # secondary metric: the int8 quantized-gradient path
        # (use_quantized_grad, the reference's gradient_discretizer feature)
        try:
            dq = lgb.Dataset(X, label=y)
            bq = lgb.Booster(params={**params, "use_quantized_grad": True},
                             train_set=dq)
            for _ in range(WARMUP_ITERS):
                bq.update()
            t0 = time.perf_counter()
            for _ in range(N_ITERS):
                bq.update()
            eq = time.perf_counter() - t0
            out["quantized_row_iters_per_sec"] = round(
                n_rows * N_ITERS / eq, 1)
            out["quantized_auc"] = round(_auc(yh, bq.predict(Xh)), 4)
        except Exception as e:  # noqa: BLE001 - secondary must not kill primary
            out["quantized_error"] = repr(e)[:200]

    # out-of-core streaming capture (docs/STREAMING.md): chunked ingest
    # through RowBlockStore, then training under a deliberately starved
    # HBM budget (2 of ~8 blocks resident) so the numbers reflect real
    # evictions and prefetch overlap, never the pin-everything fast path
    if os.environ.get("BENCH_STREAMING", "1") not in ("0", "false"):
        try:
            from lightgbm_tpu.streaming import RowBlockStore, wrap_dataset

            s_rows = min(n_rows, 200_000)
            push_chunk = 16_384
            store = RowBlockStore(params=params)
            t0 = time.perf_counter()
            for lo in range(0, s_rows, push_chunk):
                hi = min(s_rows, lo + push_chunk)
                store.push_rows(X[lo:hi], label=y[lo:hi])
            core = store.finalize()
            ingest_s = time.perf_counter() - t0
            out["stream_ingest_rows_per_sec"] = round(s_rows / ingest_s, 1)

            block_rows = max(256, -(-s_rows // 8))
            budget = 2 * perfmodel.stream_block_bytes(
                block_rows, core.bins.shape[0], core.bins.dtype.itemsize)
            saved = {k: os.environ.get(k) for k in
                     ("LGBM_TPU_HBM_BUDGET", "LGBM_TPU_STREAM_BLOCK_ROWS")}
            os.environ["LGBM_TPU_HBM_BUDGET"] = str(int(budget))
            os.environ["LGBM_TPU_STREAM_BLOCK_ROWS"] = str(block_rows)
            base = {k: int(global_timer.counters.get(k, 0)) for k in
                    ("stream_h2d_prefetched", "stream_h2d_cold")}
            try:
                bs = lgb.Booster(params=params,
                                 train_set=wrap_dataset(core, params=params))
                bs.update()  # compile warmup, not timed
                t0 = time.perf_counter()
                for _ in range(N_ITERS):
                    bs.update()
                stream_s = time.perf_counter() - t0
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            out["stream_train_rows_per_sec"] = round(
                s_rows * N_ITERS / stream_s, 1)
            c = global_timer.counters
            out["hbm_resident_fraction"] = round(
                c["stream_resident_blocks"] / c["stream_blocks_total"], 4)
            pre = int(c.get("stream_h2d_prefetched", 0)
                      ) - base["stream_h2d_prefetched"]
            cold = int(c.get("stream_h2d_cold", 0)) - base["stream_h2d_cold"]
            out["stream_h2d_overlap_pct"] = round(
                100.0 * pre / max(pre + cold, 1), 2)

            # drift capture (docs/STREAMING.md "Drift and generation
            # safety"): the sketch+occupancy tax on ingest, one forced
            # bin-mapper refresh, and one holdout gate evaluation — the
            # three costs the <2% overhead contract is priced against
            d_saved = os.environ.get("LGBM_TPU_DRIFT")
            os.environ["LGBM_TPU_DRIFT"] = "1"
            try:
                dstore = RowBlockStore(params=params)
                t0 = time.perf_counter()
                for lo in range(0, s_rows, push_chunk):
                    hi = min(s_rows, lo + push_chunk)
                    dstore.push_rows(X[lo:hi], label=y[lo:hi])
                dstore.finalize()
                drift_s = time.perf_counter() - t0
                out["drift_check_overhead_pct"] = round(
                    (drift_s / ingest_s - 1.0) * 100.0, 2)
                t0 = time.perf_counter()
                dstore.maybe_refresh_bins(force=True)
                out["bin_refresh_ms"] = round(
                    (time.perf_counter() - t0) * 1000.0, 3)
            finally:
                if d_saved is None:
                    os.environ.pop("LGBM_TPU_DRIFT", None)
                else:
                    os.environ["LGBM_TPU_DRIFT"] = d_saved

            from lightgbm_tpu import health as _health

            g_rows = min(4096, s_rows)
            Xg, yg = X[:g_rows], y[:g_rows]
            obj = str(params.get("objective", ""))
            t0 = time.perf_counter()
            _health.prediction_loss(bs.predict(Xg), yg, obj)
            _health.prediction_loss(bs.predict(Xg), yg, obj)
            out["gate_eval_ms"] = round(
                (time.perf_counter() - t0) * 1000.0, 3)
        except Exception as e:  # noqa: BLE001 - secondary must not kill primary
            out["stream_error"] = repr(e)[:200]

    # gang-sharded streaming capture (docs/STREAMING.md "Pod-scale
    # streaming"): chunked ingest through ShardedRowBlockStore (the rank-
    # merged sketch fit wall lands in stream_sketch_merge_ms), then
    # training through the gang-sharded learner — tree_learner=data +
    # quantized histograms, the psum-merged path — under the same starved
    # budget. The overlap ratio is re-measured on the gang run (the
    # per-gang stream_h2d_overlap_pct). On a single-device host the gang
    # degenerates to one shard; the code path and merge timing still
    # capture.
    if os.environ.get("BENCH_STREAMING", "1") not in ("0", "false"):
        try:
            from lightgbm_tpu.streaming import (ShardedRowBlockStore,
                                                wrap_dataset)

            s_rows = min(n_rows, 200_000)
            push_chunk = 16_384
            sh_store = ShardedRowBlockStore(params=params)
            for lo in range(0, s_rows, push_chunk):
                hi = min(s_rows, lo + push_chunk)
                sh_store.push_rows(X[lo:hi], label=y[lo:hi])
            sh_core = sh_store.finalize()
            out["stream_sketch_merge_ms"] = round(
                global_timer.counters.get("stream_sketch_merge_us", 0)
                / 1000.0, 3)

            block_rows = max(256, -(-s_rows // 8))
            budget = 2 * perfmodel.stream_block_bytes(
                block_rows, sh_core.bins.shape[0],
                sh_core.bins.dtype.itemsize)
            sh_params = {**params, "tree_learner": "data",
                         "use_quantized_grad": True}
            saved = {k: os.environ.get(k) for k in
                     ("LGBM_TPU_HBM_BUDGET", "LGBM_TPU_STREAM_BLOCK_ROWS")}
            os.environ["LGBM_TPU_HBM_BUDGET"] = str(int(budget))
            os.environ["LGBM_TPU_STREAM_BLOCK_ROWS"] = str(block_rows)
            base = {k: int(global_timer.counters.get(k, 0)) for k in
                    ("stream_h2d_prefetched", "stream_h2d_cold")}
            try:
                bsh = lgb.Booster(
                    params=sh_params,
                    train_set=wrap_dataset(sh_core, params=sh_params))
                bsh.update()  # compile warmup, not timed
                t0 = time.perf_counter()
                for _ in range(N_ITERS):
                    bsh.update()
                sh_s = time.perf_counter() - t0
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            out["stream_sharded_rows_per_sec"] = round(
                s_rows * N_ITERS / sh_s, 1)
            c = global_timer.counters
            out["stream_gang_shards"] = int(c.get("stream_shards", 1))
            pre = int(c.get("stream_h2d_prefetched", 0)
                      ) - base["stream_h2d_prefetched"]
            cold = int(c.get("stream_h2d_cold", 0)) - base["stream_h2d_cold"]
            out["stream_h2d_overlap_pct"] = round(
                100.0 * pre / max(pre + cold, 1), 2)
        except Exception as e:  # noqa: BLE001 - secondary must not kill primary
            out["stream_sharded_error"] = repr(e)[:200]

    # pod-scale learner comm capture (docs/PERF_NOTES.md round-9): the
    # three-way ICI model (data vs voting vs feature) on a fixed wide
    # dataset — cost is independent of n_rows, so it always runs
    if os.environ.get("BENCH_VOTING", "1") not in ("0", "false"):
        try:
            out.update(_bench_voting_fields())
        except Exception as e:  # noqa: BLE001 - secondary must not kill primary
            out["voting_error"] = repr(e)[:200]
    return out


def _append_ledger(record: dict) -> None:
    """Append the finished capture to BENCH_LEDGER.jsonl (atomic writer;
    $BENCH_LEDGER overrides the path or disables with 0/off). Only clean
    records enter the trail benchdiff gates on — and an append failure
    must never eat the capture itself."""
    try:
        from lightgbm_tpu.fingerprint import append_ledger

        path = append_ledger(record)
        if path:
            print(f"# ledger: appended to {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - capture output comes first
        print(f"# ledger: append failed: {e!r}", file=sys.stderr)


def main() -> None:
    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        os.environ["JAX_PLATFORMS"] = forced
    import jax

    dev = jax.devices()[0]  # a backend that cannot start raises here
    if not forced and dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU and found {dev.platform!r}; "
                 "BENCH_PLATFORM=cpu runs it on the CPU, labelled as such")

    from lightgbm_tpu.fingerprint import fingerprint

    record = {
        "metric": "train_row_iters_per_sec",
        "value": 0.0,
        "unit": "row_iters/s",
        "vs_baseline": 0.0,
        "platform": dev.platform,
        "device": str(dev.device_kind),
        "device_count": len(jax.devices()),
    }
    # environment fingerprint: git sha, jax/jaxlib versions, device
    # kind/count, active LGBM_TPU_* flags + the ledger schema_version —
    # the provenance benchdiff keys its comparability checks on
    record["fingerprint"] = fingerprint()
    record["schema_version"] = record["fingerprint"]["schema_version"]

    res = run_bench(N_ROWS)
    record["value"] = round(res.pop("row_iters_per_sec"), 1)
    record["vs_baseline"] = round(
        record["value"] / BASELINE_ROW_ITERS_PER_SEC, 4)
    record["elapsed_s"] = round(res.pop("elapsed_s"), 3)
    record.update(res)
    errors = sorted(k for k in record if k.endswith("_error"))
    if not errors:
        _append_ledger(record)  # only clean records enter the trail
    emit(record)
    if errors:
        sys.exit(f"bench.py: secondary captures failed: {errors}")


if __name__ == "__main__":
    main()
