#!/usr/bin/env python
"""Benchmark: hist-GBT boosting rounds/sec/chip (BASELINE config 1 proxy).

Runs on the TPU that ``jax.devices()`` reports and exits non-zero when
there is none: a CPU run is not a benchmark (``BENCH_FORCE_CPU=N`` asks
for N virtual CPU devices — the self-tests' hook; such a record says
``platform: "cpu"`` and carries no device metric).  HIGGS-scale synthetic
data — BENCH_ROWS×28 dense features, binary labels — quantile-binned once
on the device, then ``BENCH_ROUNDS`` boosting rounds of depth
``BENCH_DEPTH`` after ``BENCH_WARMUP`` discarded warmup rounds (compile +
cache), per BASELINE.md's measurement plan.

Multi-chip mode (ISSUE 7): ``BENCH_CHIPS=N`` pins the data-mesh width
(default: every local device).  Rows stage through the sharded per-chip
ingest, the per-level histogram psum is the only cross-chip traffic
(``psum_probe`` measures its bytes/latency standalone), and when the
budget allows, a 1-chip re-measure on the same rows+cuts yields
``scaling.scaling_efficiency`` = per-chip rate at N chips / 1-chip rate
(``BENCH_SCALING=0`` skips).  The headline metric stays per-chip.

Output protocol (driver parses the LAST stdout line as JSON): this script
emits a *provisional* JSON line at every phase transition and at every
timed-chunk arrival, then one final line.  Whatever kills the process —
driver timeout (SIGTERM), our own wall-clock budget, SIGKILL — the last
line on stdout is always a valid record carrying the evidence gathered so
far, so survivability is part of the bench's spec, not polish.

Robustness machinery:
  * ``BENCH_TIME_BUDGET`` (s, default 480): an internal deadline enforced
    by a watchdog *thread* (signal handlers can't run while the main
    thread is blocked inside a C-land device fetch; a thread can).  On
    expiry the evidence-so-far is flushed as the final line and the
    process exits 0.
  * SIGTERM/SIGINT handlers flush the same way (the driver's `timeout`
    sends SIGTERM first).
  * The configuration is exactly what ``BENCH_ROWS``/``BENCH_FEATURES``/
    ``BENCH_ROUNDS`` say; a run that does not fit the budget is truncated
    by the watchdog and says so, it is never shrunk to fit.
  * The anomaly re-measure (one dispatch far slower than its siblings:
    worst/best chunk ratio > 3) reuses the device-resident binned matrix
    via ``HistGBT.fit_device`` — zero re-upload — and is skipped entirely
    when the budget can't fit it.
  * Official-run selection prefers the NON-anomalous run; if every run
    is anomalous the median-chunk rate is reported (``value_basis`` says
    which), never a corrupted wall number and never best-of-2.

vs_baseline: the reference publishes no numbers (SURVEY.md §6); the target
is the BASELINE.json north star — XGBoost+NCCL on one 8×A100 node at
HIGGS-10M.  Comparator derivation (BASELINE.md "comparator" section for
the full provenance and uncertainty band): public single-GPU
``gpu_hist``/``hist`` HIGGS benchmarks cluster around 10-17 rounds/s at
this config, and public multi-GPU scaling on a 10M-row dataset is poor
(allreduce-bound; dask-xgboost benchmarks show ≤2× aggregate on 8 GPUs),
giving an aggregate ≈ 16-34 rounds/s → **2.0 rounds/s per chip** as the
mid-band per-GPU effective rate.  vs_baseline = value / 2.0.  This
environment has no network and no xgboost wheel, so the comparator is
pinned from cited public figures, not re-measured here.
"""

import json
import os
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

_COMPARATOR = 2.0          # rounds/s/chip, BASELINE.md mid-band
# RLock: a SIGTERM handler runs ON the main thread and re-enters emit()
# if the signal lands mid-print; a plain Lock would self-deadlock there
_EMIT_LOCK = threading.RLock()

#: bf16 peak FLOP/s by ``jax.devices()[0].device_kind``, for the MFU
#: line.  "TPU v5 lite" is the v5e: 197 TFLOP/s bf16 (Google Cloud
#: documentation, "TPU v5e").  A TPU that is not in the table is an
#: error, not a default — another chip's peak makes a wrong MFU.
_PEAK_BF16 = {"TPU v5 lite": 197e12}


def peak_bf16(device):
    """Peak bf16 FLOP/s of ``device`` (a ``jax.Device``), None off-TPU
    (no device metric is computed from a CPU run)."""
    if device.platform != "tpu":
        return None
    if device.device_kind not in _PEAK_BF16:
        raise KeyError(
            f"no bf16 peak recorded for device_kind "
            f"{device.device_kind!r} — add it to bench._PEAK_BF16 with "
            f"its source")
    return _PEAK_BF16[device.device_kind]

#: single shared evidence store; emit() renders it as one JSON line.
#: Written only by the main thread; read by the watchdog thread and
#: signal handlers.  Cross-thread safety contract: container VALUES are
#: only ever REBOUND wholesale (never mutated in place, except list
#: .append which cannot raise mid-iteration in CPython) — a concurrent
#: emit() therefore never sees a dict change size under iteration.
EV = {
    "phase": "start",
    "t0": None,              # process start (time.time())
    "config": {},            # rows/feats/rounds/... once chosen
    "platform": None,
    "device_kind": None,
    "chunk_times": [],       # (rounds_done, elapsed_s) of the LIVE run
    "runs": [],              # completed run evidence dicts
    "official": None,        # final selection
    "value_basis": None,
    "notes": [],
}


def _elapsed():
    return time.time() - EV["t0"] if EV["t0"] else 0.0


def _live_estimate():
    """Best per-CHIP rate estimate from the in-flight run's chunk
    arrivals (the metric is per chip: divide the mesh rate out, exactly
    as the official paths do)."""
    ct = EV["chunk_times"]
    if not ct:
        return None
    done, t = ct[-1]
    if t <= 0:
        return None
    return done / t / EV["config"].get("chips", 1)


def _metrics_out_path():
    """--metrics-out PATH / --metrics-out=PATH / BENCH_METRICS_OUT env —
    where to archive the full metrics snapshot (None = don't)."""
    for i, a in enumerate(sys.argv):
        if a == "--metrics-out" and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if a.startswith("--metrics-out="):
            return a.split("=", 1)[1]
    return os.environ.get("BENCH_METRICS_OUT")


def _slo_path():
    """--slo PATH / --slo=PATH / DMLC_SLO_SPEC env — committed SLO spec
    to score the final record against (None = skip)."""
    for i, a in enumerate(sys.argv):
        if a == "--slo" and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if a.startswith("--slo="):
            return a.split("=", 1)[1]
    return os.environ.get("DMLC_SLO_SPEC") or None


def _attach_metrics(out):
    """Final-record metrics: archive the full registry snapshot when
    --metrics-out/BENCH_METRICS_OUT names a path, and inline a compact
    phase breakdown (the BENCH_* artifact now says where the time went,
    not only how much there was).  Never fatal — the headline record
    must survive a metrics failure."""
    try:
        from dmlc_core_tpu.base.metrics import default_registry

        reg = default_registry()
        path = _metrics_out_path()
        if path:
            out["metrics_path"] = reg.save_json(path)
        snap = reg.snapshot()["metrics"]
        summary = {}
        ph = snap.get("dmlc_gbt_phase_seconds")
        if ph:
            for se in ph["series"]:
                lab = se["labels"]
                key = f"{lab['engine']}_{lab['phase']}"
                summary[f"{key}_p50_s"] = se["quantiles"]["p50"]
                summary[f"{key}_count"] = se["count"]
        for name, field in (("dmlc_gbt_rounds_total", "rounds_total"),
                            ("dmlc_collective_bytes_total",
                             "collective_bytes_total"),
                            ("dmlc_histogram_psum_bytes_total",
                             "histogram_psum_bytes_total")):
            m = snap.get(name)
            if m and m["series"]:
                summary[field] = sum(s["value"] for s in m["series"])
        # resilience evidence rides every final record (zeros included):
        # a perf run that silently degraded into a retry storm — or a
        # chaos run that injected nothing — must be visible in the
        # artifact, not only in a live scrape
        for name, field in (("dmlc_retries_total", "retries_total"),
                            ("dmlc_faults_injected_total",
                             "faults_injected")):
            m = snap.get(name)
            summary[field] = (sum(s["value"] for s in m["series"])
                              if m and m["series"] else 0.0)
        # fleet-wide view: when this process spools (DMLC_METRICS_SPOOL),
        # say how many processes the merged snapshot covers — a fleet
        # bench whose children never spooled reads 1, not silence
        from dmlc_core_tpu.base import metrics_agg
        sw = metrics_agg.installed_spool()
        if sw is not None:
            sw.flush()
            _, nprocs = metrics_agg.merge_spool(os.path.dirname(sw.path))
            summary["spool_processes_merged"] = nprocs
        # under DMLC_JITCHECK=1 the record carries the steady-state
        # compile count across every steady window this process opened
        # (0 = the PR 18 warmup fix holds under the dynamic gate)
        from dmlc_core_tpu.base import jitcheck
        if jitcheck.installed():
            summary["recompiles_steady_state"] = len(
                jitcheck.compiles("steady"))
        out["metrics_summary"] = summary
    except Exception as e:  # noqa: BLE001
        out["metrics_error"] = f"{type(e).__name__}: {e}"[:200]


def _attach_slo(out):
    """Score the final record against a committed SLO spec (--slo PATH /
    DMLC_SLO_SPEC).  The snapshot is the fleet-merged spool view when a
    spool is installed, else this process's registry; the record itself
    is the evidence dict, so objectives can reference headline fields
    (``{"evidence": "dropped"}``).  Never fatal — the headline record
    must survive a scorecard failure."""
    path = _slo_path()
    if not path:
        return
    try:
        from dmlc_core_tpu.base import metrics_agg, slo
        from dmlc_core_tpu.base.metrics import default_registry

        sw = metrics_agg.installed_spool()
        if sw is not None:
            sw.flush()
            snapshot, _ = metrics_agg.merge_spool(os.path.dirname(sw.path))
        else:
            snapshot = default_registry().snapshot()
        out["slo"] = slo.evaluate(slo.SLOSpec.load(path), snapshot,
                                  evidence=out)
    except Exception as e:  # noqa: BLE001
        out["slo_error"] = f"{type(e).__name__}: {e}"[:200]


def emit(final=False, **extra):
    """Print one JSON evidence line (the driver reads the LAST line)."""
    cfg = EV["config"]
    value = 0.0
    basis = None
    if EV["official"] is not None:
        value = EV["official"]["value"]
        basis = EV["value_basis"]
    else:
        live = _live_estimate()
        if live is not None:
            value = live
            basis = "wall_so_far"
    out = {
        "metric": "histgbt_rounds_per_sec_per_chip",
        "value": round(value, 4),
        "unit": "rounds/s/chip",
        "vs_baseline": round(value / _COMPARATOR, 4),
        "provisional": not final,
        "phase": EV["phase"],
        "elapsed_s": round(_elapsed(), 1),
        "platform": EV["platform"],
        "device_kind": EV["device_kind"],
    }
    if basis:
        out["value_basis"] = basis
    out.update(cfg)
    if EV["chunk_times"] and EV["official"] is None:
        out["chunks_so_far"] = [[d, round(t, 3)] for d, t in
                                EV["chunk_times"]]
    if EV["official"] is not None:
        out.update(EV["official"])
        out["value"] = round(value, 4)          # official dict also has it
        out["vs_baseline"] = round(value / _COMPARATOR, 4)
        out["vs_baseline_band"] = [round(value / 4.0, 4),
                                   round(value / 2.0, 4)]
        out["runs"] = EV["runs"]
    if EV["notes"]:
        out["notes"] = EV["notes"]
    if final:
        _attach_metrics(out)
        _attach_slo(out)
    out.update(extra)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(out) + "\n")
        sys.stdout.flush()


def _flush_and_exit(reason):
    try:
        emit(final=True, terminated=reason)
    except Exception as e:  # noqa: BLE001 — the record must still exist
        with _EMIT_LOCK:
            sys.stdout.write(json.dumps({
                "metric": "histgbt_rounds_per_sec_per_chip",
                "value": 0.0, "unit": "rounds/s/chip", "vs_baseline": 0.0,
                "terminated": reason, "provisional": False,
                "emit_error": f"{type(e).__name__}: {e}"[:200]}) + "\n")
            sys.stdout.flush()
    os._exit(0)


def _install_guards(deadline):
    """SIGTERM/SIGINT flush + watchdog thread enforcing the deadline.

    The watchdog is a thread, not SIGALRM: a Python signal handler only
    runs between bytecodes on the main thread, and the main thread can sit
    blocked inside a C-land device fetch — exactly when the budget is
    most likely to expire."""
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda s, f: _flush_and_exit(
            signal.Signals(s).name))

    def watch():
        while True:
            left = deadline - time.time()
            if left <= 0:
                _flush_and_exit("budget_exhausted")
            time.sleep(min(5.0, max(0.5, left)))

    threading.Thread(target=watch, daemon=True).start()


def _derived_metrics(rows, feats, depth, n_bins, seconds_per_round, peak,
                     n_chips=1, layout=None, grow_policy="depthwise",
                     max_leaves=0):
    """Auditable per-round cost model of the sibling-subtracted round.

    MXU flops: per level ℓ the Pallas histogram dot is [A, T]·[T, lo]
    over all rows with A = 2·n_build·ceil(B/lo); sibling subtraction
    makes n_build = 1, 1, 2, 4, ... and ops._lo_factor picks lo.  HBM
    bytes: the bin matrix is read once by each level's histogram
    pass and once by each level's descend pass — at the PHYSICAL row
    width, so an int4-packed/bundled :class:`BinLayout` shrinks the bill
    — plus the f32 row vectors (g, h, preds, margin update).  psum
    bytes: the per-level left-child histogram [2, n_build, S, Bs] f32 —
    what each chip contributes to the in-step histogram-sync allreduce
    (the rabit-allreduce replacement).  The ``kernel`` block is the
    ISSUE 12 lever evidence: bin-matrix bytes one round's passes pull
    from HBM, and how many node histograms the round actually builds
    (loss-guide builds ``max_leaves`` instead of ``2^(depth-1)``)."""
    from dmlc_core_tpu.ops.histogram import (_lo_factor,
                                             bins_bytes_per_round,
                                             hist_psum_bytes_per_round,
                                             leaves_built_per_round)

    rows = rows // n_chips    # per-chip row share: metrics are per chip,
    mxu_flops = 0             # matching rounds_per_sec_per_chip
    # shared analytic traffic model (ops.histogram): also feeds the live
    # dmlc_histogram_psum_bytes_total counter the engine increments
    psum_bytes = hist_psum_bytes_per_round(
        depth, feats, n_bins, layout=layout, grow_policy=grow_policy,
        max_leaves=max_leaves)
    sync_bins = layout.sync_bins if layout is not None else n_bins
    for level in range(depth):
        n_build = 1 if level == 0 else 1 << (level - 1)
        lo = _lo_factor(n_build, sync_bins)
        hi = -(-sync_bins // lo)
        mxu_flops += 2 * (2 * n_build * hi) * lo * rows * feats
    # bin-matrix bytes per data row: F uint8 rows plain, fewer physical
    # rows when the layout packs int4 pairs / fuses bundles
    row_bytes = (layout.phys_bytes_per_row() if layout is not None
                 else feats)
    leaves_built = leaves_built_per_round(depth, grow_policy, max_leaves)
    bins_bytes = bins_bytes_per_round(
        depth, rows, row_bytes, grow_policy=grow_policy,
        max_leaves=max_leaves)
    hbm = bins_bytes + 6 * rows * 4       # + g/h/preds/update f32 vectors
    mfu = (mxu_flops / seconds_per_round / peak) if peak else None
    return {
        "mxu_flops_per_round": mxu_flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "hbm_bytes_per_round": hbm,
        "hbm_gbps": round(hbm / seconds_per_round / 1e9, 1),
        "hist_psum_bytes_per_round": psum_bytes,
        "kernel": {
            "bins_bytes_per_round": bins_bytes,
            "bin_bytes_per_data_row": row_bytes,
            "leaves_built_per_round": leaves_built,
            "grow_policy": grow_policy,
            "bin_layout": (None if layout is None else
                           f"{layout.n_features}F->{layout.phys_rows}rows"
                           f"/{len(layout.pairs)}pairs"),
        },
    }


def chunk_stats(chunk_times, total_rounds, total_seconds):
    """Per-chunk rate evidence from (rounds_done, t) arrival timestamps.

    Returns best/median/worst seconds-per-round and the anomaly flag
    (worst/best > 3 AND worst > 50 ms/round — one dispatch sitting for
    hundreds of ms to minutes corrupts the wall number with no trace;
    the absolute floor stops a near-zero timer delta on a fast fit from
    flagging its sibling chunks as "slow").  Deltas are also
    clamped to 1 µs so a coarse timer can never divide-by-zero.  Pure
    so the anomaly machinery itself is unit-testable
    (tests/test_bench_stats)."""
    eps = 1e-6
    spr = []
    prev_done, prev_t = 0, 0.0
    for done_i, t_i in chunk_times:
        spr.append(max(t_i - prev_t, eps) / (done_i - prev_done))
        prev_done, prev_t = done_i, t_i
    # wall fallback only when there is no chunk evidence at all
    spr_sorted = sorted(spr) or [total_seconds / total_rounds]
    med = spr_sorted[len(spr_sorted) // 2]
    return {
        "chunk_seconds_per_round": [round(s, 5) for s in spr],
        "rounds_per_sec_best_chunk": round(1.0 / spr_sorted[0], 4),
        "rounds_per_sec_median_chunk": round(1.0 / med, 4),
        "anomaly": (len(spr) >= 2
                    and spr_sorted[-1] / spr_sorted[0] > 3.0
                    and spr_sorted[-1] > 0.05),
    }


def scaling_summary(n_chips, per_chip_rate, baseline_rate):
    """Multi-chip scaling evidence vs the 1-chip oracle run.

    ``scaling_efficiency`` = per-chip rate at N chips / 1-chip rate
    (1.0 = perfect linear scaling; the ISSUE 7 acceptance bar is 0.7 at
    the 10M x 28 config).  Pure so the math is unit-testable
    (tests/test_bench_stats) independent of the measurement harness."""
    if not baseline_rate or baseline_rate <= 0 or n_chips < 1:
        return None
    return {
        "chips": n_chips,
        "baseline_chips": 1,
        "baseline_rounds_per_sec_per_chip": round(baseline_rate, 4),
        "aggregate_rounds_per_sec": round(per_chip_rate * n_chips, 4),
        "scaling_efficiency": round(per_chip_rate / baseline_rate, 4),
    }


def _psum_probe(mesh, depth, feats, n_bins, reps=3):
    """Measured latency of one round's histogram-sync allreduce: a
    standalone device_allreduce of the per-round psum payload (the
    [2, n_build, F, B] per-level histograms, flattened) over the bench
    mesh.  An upper-bound probe — inside the real round program XLA
    overlaps the per-level psums with compute — but it pins the
    bytes/latency scale of the only cross-chip traffic the multi-chip
    flagship pays."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dmlc_core_tpu.ops.histogram import hist_psum_bytes_per_round
    from dmlc_core_tpu.parallel.collectives import device_allreduce

    nbytes = hist_psum_bytes_per_round(depth, feats, n_bins)
    W = mesh.devices.size
    x = jax.device_put(
        np.ones((W, nbytes // 4), np.float32),
        NamedSharding(mesh, P("data")))
    out = device_allreduce(x, mesh)            # warm the program
    np.asarray(out[:1])
    t0 = time.perf_counter()
    for _ in range(reps):
        out = device_allreduce(x, mesh)
    np.asarray(out[:1])                        # real fetch: sync
    ms = (time.perf_counter() - t0) / reps * 1e3
    return {
        "bytes_per_round": nbytes,
        "allreduce_ms": round(ms, 3),
        "effective_gbps": round(nbytes / (ms / 1e3) / 1e9, 2)
        if ms > 0 else None,
    }


def latency_summary(lats_s):
    """p50/p95/p99/mean (ms) of a latency sample list — pure, so the
    serve-bench percentile math is unit-testable (tests/test_serve.py)."""
    if not lats_s:
        return {"latency_p50_ms": None, "latency_p95_ms": None,
                "latency_p99_ms": None, "latency_mean_ms": None}
    s = sorted(lats_s)

    def q(p):
        return s[min(len(s) - 1, max(0, int(round(p * (len(s) - 1)))))]

    return {
        "latency_p50_ms": round(q(0.50) * 1e3, 3),
        "latency_p95_ms": round(q(0.95) * 1e3, 3),
        "latency_p99_ms": round(q(0.99) * 1e3, 3),
        "latency_mean_ms": round(sum(s) / len(s) * 1e3, 3),
    }


def _serve_emit(rec, final=False):
    rec = {"metric": "serve_requests_per_sec", "unit": "req/s",
           "provisional": not final, **rec}
    if final:
        _attach_metrics(rec)
        _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


def _serve_bench() -> None:
    """``--serve``: open-loop load over the serve batcher+runner.

    Trains a small GBT, publishes it to a ModelRegistry, then drives the
    DynamicBatcher directly (no HTTP — the socket layer has its own soak
    test) with Poisson arrivals at ``SERVE_QPS`` for ``SERVE_SECONDS``,
    request sizes drawn from ``SERVE_REQ_SIZES`` (comma list, sampled
    uniformly — repeat a size to weight it).  Emits the same JSON shape
    as the GBT bench: one provisional line per phase, a final line with
    throughput, latency percentiles, reject counts and a batch-size
    histogram summary; ``--metrics-out`` archives the full registry
    snapshot.  All buckets are warmed before the timed window so jit
    compiles don't pollute the latency sample."""
    t0 = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))
    qps = float(os.environ.get("SERVE_QPS", 300))
    duration = min(float(os.environ.get("SERVE_SECONDS", 10)),
                   max(budget - 120, 2.0))
    max_batch = int(os.environ.get("SERVE_MAX_BATCH", 256))
    max_delay = float(os.environ.get("SERVE_MAX_DELAY_MS", 2.0)) / 1e3
    sizes = [int(s) for s in
             os.environ.get("SERVE_REQ_SIZES", "1,1,1,1,2,4,8,16").split(",")]
    train_rows = int(os.environ.get("SERVE_TRAIN_ROWS", 50_000))
    n_trees = int(os.environ.get("SERVE_TREES", 20))
    feats = int(os.environ.get("BENCH_FEATURES", 28))

    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    cfg = {"qps": qps, "duration_s": duration, "max_batch": max_batch,
           "max_delay_ms": max_delay * 1e3, "req_sizes": sizes,
           "train_rows": train_rows, "n_trees": n_trees}
    _serve_emit({"value": 0.0, "phase": "train", **cfg})

    import jax  # noqa: F401 — device init before timing anything

    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.serve import DynamicBatcher, ModelRegistry

    rng = np.random.default_rng(11)
    X = rng.normal(size=(train_rows, feats)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    model = HistGBT(n_trees=n_trees, max_depth=4, n_bins=64,
                    learning_rate=0.3)
    model.fit(X, y)

    registry = ModelRegistry(max_batch=max_batch, min_bucket=8)
    registry.publish(model, source="serve-bench")
    _, runner = registry.current()

    def execute(batch):
        version, r = registry.current()
        return r.predict(batch), version

    _serve_emit({"value": 0.0, "phase": "warmup", **cfg})
    # compile every ladder bucket (persistent-cache aware: a warm
    # restart deserializes instead of compiling — see doc/performance.md)
    warm_wall = runner.warmup(feats)

    batcher = DynamicBatcher(execute, max_batch=max_batch,
                             max_delay=max_delay, max_queue=512,
                             name="serve-bench")
    lats = []
    errors = [0]
    lock = threading.Lock()

    def record(fut, t_sub):
        try:
            fut.result()
        except Exception:  # noqa: BLE001
            with lock:
                errors[0] += 1
            return
        with lock:
            lats.append(time.perf_counter() - t_sub)

    from dmlc_core_tpu.serve import QueueFullError

    _serve_emit({"value": 0.0, "phase": "load", **cfg})
    submitted = rejected = 0
    start = time.perf_counter()
    next_t = start
    end = start + duration
    while (now := time.perf_counter()) < end:
        if now < next_t:
            time.sleep(min(next_t - now, 0.005))
            continue
        next_t += rng.exponential(1.0 / qps)
        k = int(rng.choice(sizes))
        lo = int(rng.integers(0, train_rows - k))
        t_sub = time.perf_counter()
        try:
            fut = batcher.submit(X[lo:lo + k], timeout=5.0)
        except QueueFullError:
            rejected += 1
            continue
        fut.add_done_callback(lambda f, t=t_sub: record(f, t))
        submitted += 1
    batcher.close(drain=True)
    wall = time.perf_counter() - start

    # batch-size evidence straight from the serve instruments
    batch_summary = {}
    try:
        from dmlc_core_tpu.base.metrics import default_registry
        snap = default_registry().snapshot()["metrics"]
        hs = snap.get("dmlc_serve_batch_rows", {}).get("series", [])
        se = next((s for s in hs
                   if s["labels"].get("batcher") == "serve-bench"), None)
        if se:
            batch_summary = {
                "batches": se["count"],
                "batch_rows_p50": se["quantiles"]["p50"],
                "batch_rows_p99": se["quantiles"]["p99"],
                "batch_rows_max": se["max"],
            }
    except Exception:  # noqa: BLE001 — evidence, not the headline
        pass

    done = len(lats)
    _serve_emit({
        "value": round(done / wall, 2) if wall > 0 else 0.0,
        "phase": "done",
        "elapsed_s": round(time.time() - t0, 1),
        "platform": jax.devices()[0].platform,
        "submitted": submitted,
        "completed": done,
        "rejected": rejected,
        "errors": errors[0],
        "warmup_seconds": round(warm_wall, 3),
        **latency_summary(lats),
        **batch_summary,
        "compiled_shapes": sorted(runner.compiled_shapes),
        "shape_bound": runner.shape_bound,
        **cfg,
    }, final=True)


def _fleet_emit(rec, final=False):
    rec = {"metric": "fleet_requests_per_sec", "unit": "req/s",
           "provisional": not final, **rec}
    if final:
        _attach_metrics(rec)
        _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


def _fleet_bench() -> None:
    """``--fleet``: closed-loop load over a replica fleet behind the
    consistent-hash router, with a staged v1->v2 rollout mid-run.

    Trains two GBT versions, checkpoints both, then stands up the full
    fleet topology — FleetTracker + ``FLEET_REPLICAS`` replicas spawned
    through the launch subsystem (a :class:`LauncherScaler`-backed
    JobSet) + in-process FleetRouter — and drives it with the
    multi-process closed-loop load generator (heavy-tail request sizes,
    diurnal QPS ramp).  One third into the run a staged rollout
    (wave size 1) hot-swaps the fleet to v2 under load.  Every response
    is verified bit-exactly against the version it claims, so the final
    line's ``dropped``/``wrong`` counters ARE the zero-drop hot-swap
    acceptance evidence; per-replica balance comes from the router's
    ``fleet_routed_total`` series, and the supervisor's view lands in
    the final line's ``launch`` block (backend, respawns,
    spawn_ms_p95)."""
    t0 = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))
    n_replicas = int(os.environ.get("FLEET_REPLICAS", 3))
    duration = min(float(os.environ.get("FLEET_SECONDS", 8)),
                   max(budget - 180, 3.0))
    qps = float(os.environ.get("FLEET_QPS", 120))
    procs = int(os.environ.get("FLEET_PROCS", 2))
    threads = int(os.environ.get("FLEET_THREADS", 3))
    train_rows = int(os.environ.get("FLEET_TRAIN_ROWS", 20_000))
    serve_rows = int(os.environ.get("FLEET_SERVE_ROWS", 512))
    feats = int(os.environ.get("BENCH_FEATURES", 28))

    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    cfg = {"replicas": n_replicas, "qps": qps, "duration_s": duration,
           "procs": procs, "threads": threads, "train_rows": train_rows}
    _fleet_emit({"value": 0.0, "phase": "train", **cfg})

    import tempfile

    import jax  # noqa: F401 — device init before timing anything

    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.serve import checkpoint_model
    from dmlc_core_tpu.serve.fleet import (FleetRouter, FleetTracker,
                                           HttpFleetAdmin, LauncherScaler,
                                           Rollout, run_loadgen)

    rng = np.random.default_rng(11)
    Xt = rng.normal(size=(train_rows, feats)).astype(np.float32)
    yt = (Xt[:, 0] * Xt[:, 1] + 0.5 * Xt[:, 2] > 0).astype(np.float32)
    m1 = HistGBT(n_trees=5, max_depth=4, n_bins=32,
                 learning_rate=0.3).fit(Xt, yt)
    m2 = HistGBT(n_trees=10, max_depth=4, n_bins=32,
                 learning_rate=0.3).fit(Xt, yt)
    X = Xt[:serve_rows]

    workdir = tempfile.mkdtemp(prefix="fleet_bench_")
    v1_uri = f"file://{workdir}/v1.ckpt"
    v2_uri = f"file://{workdir}/v2.ckpt"
    checkpoint_model(v1_uri, m1, version=1)
    checkpoint_model(v2_uri, m2, version=2)
    expected_npz = os.path.join(workdir, "expected.npz")
    np.savez(expected_npz, X=X, v1=m1.predict(X), v2=m2.predict(X))

    _fleet_emit({"value": 0.0, "phase": "spawn", **cfg})
    child_env = {"JAX_PLATFORMS": "cpu"} if os.environ.get(
        "BENCH_FORCE_CPU") else None
    tracker = FleetTracker(nworker=max(8, n_replicas + 2))
    tracker.start()
    scaler = LauncherScaler(tracker, v1_uri, initial=n_replicas,
                            spawn_env=child_env)
    router = None
    rollout_report = {}
    try:
        deadline = time.time() + 180
        while len(tracker.serve_endpoints()) < n_replicas:
            if time.time() > deadline:
                raise RuntimeError("fleet replicas never registered")
            time.sleep(0.2)
        router = FleetRouter(tracker, probe_s=0.2).start()

        def _rollout():
            time.sleep(duration / 3.0)
            admin = HttpFleetAdmin(tracker.serve_endpoints())
            rollout_report.update(
                Rollout(admin, wave_size=1, settle_s=0.3).run(v2_uri))

        _fleet_emit({"value": 0.0, "phase": "load", **cfg})
        roller = threading.Thread(target=_rollout, daemon=True)
        roller.start()
        merged = run_loadgen(
            router.url, expected_npz, duration_s=duration, procs=procs,
            threads=threads, base_qps=qps, amplitude=0.5,
            period_s=max(duration / 2.0, 2.0),
            timeout_ms=10_000, workdir=workdir)
        roller.join(timeout=120)

        balance = {}
        try:
            from dmlc_core_tpu.base.metrics import default_registry
            snap = default_registry().snapshot()["metrics"]
            for s in snap.get("dmlc_fleet_routed_total",
                              {}).get("series", []):
                balance[s["labels"]["replica"]] = s["value"]
        except Exception:  # noqa: BLE001 — evidence, not the headline
            pass

        _fleet_emit({
            "value": merged["throughput_rps"],
            "phase": "done",
            "elapsed_s": round(time.time() - t0, 1),
            "platform": jax.devices()[0].platform,
            "requests": merged["count"],
            "ok": merged["ok"],
            "dropped": merged["dropped"],
            "wrong": merged["wrong"],
            "by_version": merged["by_version"],
            "latency_p50_ms": merged["latency_p50_ms"],
            "latency_p95_ms": merged["latency_p95_ms"],
            "latency_p99_ms": merged["latency_p99_ms"],
            "per_replica_routed": balance,
            "rollout": {k: rollout_report.get(k) for k in
                        ("version", "outcome", "waves")},
            "launch": {k: scaler.jobset.stats()[k] for k in
                       ("backend", "respawns", "spawn_ms_p95")},
            **cfg,
        }, final=True)
    finally:
        if router is not None:
            router.close()
        scaler.reap(timeout=15)
        tracker.stop()


def _tenants_emit(rec, final=False):
    rec = {"metric": "tenant_requests_per_sec", "unit": "req/s",
           "provisional": not final, **rec}
    if final:
        _attach_metrics(rec)
        _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


def _tenants_bench() -> None:
    """``--tenants``: multi-tenant registry under a Zipf tenant mix.

    Publishes ``TENANTS_N`` distinct HistGBT models into one
    :class:`TenantRegistry` capped at ``TENANTS_RESIDENT_CAP`` resident
    runners, then drives it closed-loop from ``TENANTS_THREADS`` threads
    sampling tenants from the same bounded-Zipf law the tenancy drill
    uses — the hot head stays warm, the long tail churns through
    eviction and compile-cache-backed warm restore.  Every response is
    verified bit-exactly against the publishing model, so ``wrong`` is
    paging-correctness evidence, not just a counter; the final line
    carries per-tenant p50/p99 plus the eviction/restore totals the
    scorecard gates."""
    t0 = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))
    n_tenants = int(os.environ.get("TENANTS_N", 12))
    cap = int(os.environ.get("TENANTS_RESIDENT_CAP", 4))
    duration = min(float(os.environ.get("TENANTS_SECONDS", 6)),
                   max(budget - 120, 2.0))
    n_threads = int(os.environ.get("TENANTS_THREADS", 4))
    zipf_a = float(os.environ.get("TENANTS_ZIPF_A", 1.1))
    train_rows = int(os.environ.get("TENANTS_TRAIN_ROWS", 4000))
    serve_rows = int(os.environ.get("TENANTS_SERVE_ROWS", 256))
    feats = int(os.environ.get("BENCH_FEATURES", 28))

    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    cfg = {"tenants": n_tenants, "resident_cap": cap, "zipf_a": zipf_a,
           "duration_s": duration, "threads": n_threads}
    _tenants_emit({"value": 0.0, "phase": "train", **cfg})

    import jax

    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.serve.fleet.loadgen import (sample_tenant,
                                                   zipf_weights)
    from dmlc_core_tpu.serve.tenancy import TenantRegistry

    rng = np.random.default_rng(17)
    Xt = rng.normal(size=(train_rows, feats)).astype(np.float32)
    X = Xt[:serve_rows]
    reg = TenantRegistry(resident_cap=cap, max_batch=64)
    names = [f"t{i:02d}" for i in range(n_tenants)]
    expected = {}
    for i, name in enumerate(names):
        yt = (Xt[:, i % feats] + 0.5 * Xt[:, (i + 1) % feats]
              > 0).astype(np.float32)
        m = HistGBT(n_trees=3 + i % 3, max_depth=3, n_bins=32).fit(Xt, yt)
        reg.publish(name, m)
        # HistGBT is bit-exact across batch shapes, so any prefix of
        # this full-batch oracle is THE expected answer for a request
        expected[name] = np.asarray(m.predict(X))

    cum = zipf_weights(n_tenants, zipf_a)
    lat = {name: [] for name in names}   # list.append is GIL-atomic
    wrongs = [0] * n_threads
    stop = threading.Event()

    def worker(idx):
        r = np.random.default_rng(1000 + idx)
        while not stop.is_set():
            tenant = sample_tenant(r, names, cum)
            n = int(r.integers(1, serve_rows + 1))
            t1 = time.perf_counter()
            _, runner = reg.current(tenant)
            out = np.asarray(runner.predict(X[:n]))
            lat[tenant].append(time.perf_counter() - t1)
            if not np.array_equal(out, expected[tenant][:n]):
                wrongs[idx] += 1

    _tenants_emit({"value": 0.0, "phase": "load", **cfg})
    workers = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_threads)]
    t_load = time.perf_counter()
    for w in workers:
        w.start()
    time.sleep(duration)
    stop.set()
    for w in workers:
        w.join(timeout=60)
    wall = time.perf_counter() - t_load

    count = sum(len(v) for v in lat.values())
    by_tenant = {}
    for name in names:
        ms = np.sort(np.asarray(lat[name], dtype=np.float64)) * 1000.0
        by_tenant[name] = {"count": int(ms.size)}
        if ms.size:
            by_tenant[name].update(
                p50_ms=round(float(np.percentile(ms, 50)), 3),
                p99_ms=round(float(np.percentile(ms, 99)), 3))
    _tenants_emit({
        "value": round(count / max(wall, 1e-9), 2),
        "phase": "done",
        "elapsed_s": round(time.time() - t0, 1),
        "platform": jax.devices()[0].platform,
        "requests": count,
        "wrong": sum(wrongs),
        "evictions": reg.evictions,
        "warm_restores": reg.restores,
        "resident": reg.resident(),
        "by_tenant": by_tenant,
        **cfg,
    }, final=True)


def _stream_emit(rec, final=False):
    rec = {"metric": "stream_staleness_seconds", "unit": "s",
           "provisional": not final, **rec}
    if final:
        _attach_metrics(rec)
        _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


def _stream_bench() -> None:
    """``--stream``: closed-loop online-learning benchmark.

    A generator thread appends synthetic events (dense-event codec,
    slight concept drift) to a growing RecordIO shard set at
    ``STREAM_EVENTS_PER_SEC``; the main loop runs the full train→serve
    path — tail → warm-start boost → eval-gate publish → registry
    hot-swap — for ``STREAM_SECONDS``.  The headline is **staleness**:
    the latency from an event being appended to an *activated* model
    version having trained on it (p50/p95/p99 over all served events),
    reported alongside refresh throughput.  ``--metrics-out`` archives
    the full registry snapshot (tailer/trainer/publisher counters plus
    the staleness histogram)."""
    t0 = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))
    duration = min(float(os.environ.get("STREAM_SECONDS", 10)),
                   max(budget - 120, 2.0))
    rate = float(os.environ.get("STREAM_EVENTS_PER_SEC", 1500))
    chunk_rows = int(os.environ.get("STREAM_CHUNK_ROWS", 1024))
    window_chunks = int(os.environ.get("STREAM_WINDOW_CHUNKS", 2))
    trees = int(os.environ.get("STREAM_TREES", 5))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    shard_events = int(os.environ.get("STREAM_SHARD_EVENTS",
                                      8 * chunk_rows))

    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    cfg = {"duration_s": duration, "events_per_sec": rate,
           "chunk_rows": chunk_rows, "window_chunks": window_chunks,
           "trees_per_refresh": trees, "features": feats}
    _stream_emit({"value": 0.0, "phase": "setup", **cfg})

    import shutil
    import tempfile

    import jax  # noqa: F401 — device init before timing anything

    from dmlc_core_tpu.base import jitcheck
    from dmlc_core_tpu.base.metrics import default_registry
    from dmlc_core_tpu.io.recordio import encode_records
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.serve import ModelRegistry
    from dmlc_core_tpu.stream import (ModelPublisher, OnlineTrainer,
                                      RecordIOTailer, encode_dense_events)

    stale_hist = default_registry().histogram(
        "stream_staleness_seconds",
        "event appended → servable prediction (an activated version "
        "has trained on it)",
        buckets=(0.25, 0.5, 1, 2, 4, 8, 16, 32, 64))

    rng = np.random.default_rng(13)

    def make_events(n, drift):
        X = rng.normal(size=(n, feats)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + (0.5 + drift) * X[:, 2]
             - drift * X[:, 3] > 0).astype(np.float32)
        return X, y

    root = tempfile.mkdtemp(prefix="bench_stream_")
    shard_dir = os.path.join(root, "events")
    os.makedirs(shard_dir)
    append_ts = []                    # wall clock per appended event seq
    stop_gen = threading.Event()

    def generator():
        """Paced appender: bursts every tick, fsync-free flush so the
        tailer sees bytes promptly; rotates shards so the tailer's
        growing-file-set path is exercised."""
        written = 0
        shard_idx = 0
        f = open(os.path.join(shard_dir, f"part-{shard_idx:04d}.rec"), "ab")
        start = time.perf_counter()
        try:
            while not stop_gen.is_set():
                target = int((time.perf_counter() - start) * rate)
                burst = min(target - written, 4096)
                if burst <= 0:
                    time.sleep(0.01)
                    continue
                drift = 0.2 * ((written // shard_events) % 3)
                X, y = make_events(burst, drift)
                blob = encode_records(encode_dense_events(X, y))
                f.write(blob)
                f.flush()
                now = time.time()
                append_ts.extend([now] * burst)
                written += burst
                if written // shard_events > shard_idx:
                    f.close()
                    shard_idx = written // shard_events
                    f = open(os.path.join(
                        shard_dir, f"part-{shard_idx:04d}.rec"), "ab")
        finally:
            f.close()

    Xh, yh = make_events(4096, drift=0.0)
    registry = ModelRegistry(max_batch=256, min_bucket=8)
    publisher = ModelPublisher(registry, holdout=(Xh, yh),
                               name="stream-bench")
    model = HistGBT(n_trees=trees, max_depth=4, n_bins=32,
                    learning_rate=0.3)
    tailer = RecordIOTailer(shard_dir,
                            cursor_uri=os.path.join(root, "cursor.ckpt"),
                            name="stream-bench")
    trainer = OnlineTrainer(model, tailer, n_features=feats,
                            chunk_rows=chunk_rows,
                            window_chunks=window_chunks, decay=1.0,
                            publisher=publisher, name="stream-bench")

    gen = threading.Thread(target=generator, daemon=True)
    gen.start()
    _stream_emit({"value": 0.0, "phase": "loop", **cfg})

    staleness = []
    served_floor = 0                  # events covered by an activation
    refreshes = []
    steady_marked = False
    end = time.perf_counter() + duration
    try:
        while time.perf_counter() < end:
            left = end - time.perf_counter()
            r = trainer.refresh(timeout=max(min(left, 5.0), 0.1))
            if r is None:
                continue
            refreshes.append(r)
            if (not steady_marked and jitcheck.installed()
                    and r["window_rows"] >= chunk_rows * window_chunks):
                # the sliding window just reached its final shape, so
                # every refresh program is compiled — from here on a
                # refresh that compiles is a steady-state stall
                jitcheck.steady()
                steady_marked = True
            if r.get("activated"):
                now = time.time()
                covered = min(r["records_total"], len(append_ts))
                for seq in range(served_floor, covered):
                    s = now - append_ts[seq]
                    staleness.append(s)
                    stale_hist.observe(s)
                served_floor = covered
    finally:
        stop_gen.set()
        gen.join(timeout=5.0)
        tailer.close()

    wall = time.time() - t0
    activated = sum(1 for r in refreshes if r.get("activated"))
    stale_sorted = sorted(staleness)

    def q(p):
        if not stale_sorted:
            return None
        return round(stale_sorted[min(len(stale_sorted) - 1,
                                      int(round(p * (len(stale_sorted)
                                                     - 1))))], 3)

    fit_s = [r["fit_seconds"] for r in refreshes]
    final = {
        "value": q(0.95) or 0.0,
        "phase": "done",
        "elapsed_s": round(wall, 1),
        "platform": jax.devices()[0].platform,
        "staleness_seconds": {"p50": q(0.50), "p95": q(0.95),
                              "p99": q(0.99)},
        "refreshes_published": activated,
        "refreshes_total": len(refreshes),
        "rollbacks": publisher.rollbacks,
        "refreshes_per_sec": round(len(refreshes) / max(duration, 1e-9), 3),
        "refresh_rows_per_sec": round(
            sum(r["rows"] for r in refreshes) / max(duration, 1e-9), 1),
        "fit_seconds_mean": (round(sum(fit_s) / len(fit_s), 3)
                             if fit_s else None),
        "events_appended": len(append_ts),
        "events_consumed": tailer.records_seen,
        "events_served": served_floor,
        "trees_total": len(model.trees),
        "registry_versions": len(registry.versions()),
        "recompiles_steady_state": (len(jitcheck.compiles("steady"))
                                    if steady_marked else None),
        **cfg,
    }
    _stream_emit(final, final=True)
    shutil.rmtree(root, ignore_errors=True)
    if steady_marked:
        # DMLC_JITCHECK=1 turns the record into a gate: any compile
        # after the window filled fails the bench outright
        jitcheck.check()


def _ps_bench() -> None:
    """``--ps``: web-scale sparse CTR over the sharded parameter server.

    In-process fleet (scheduler + ``PS_SERVERS`` server threads) with
    ``PS_WORKERS`` worker threads each running :meth:`GBLinear.fit_ps`
    over its own synthetic hashing-space CTR stream —
    ``PS_FEATURES`` (default 10M) feature cardinality, so the weight
    vector exists only range-sharded on the fleet and each minibatch
    moves only its touched ids.  Headlines: **keys_per_sec** (sparse
    ids crossing the wire, push+pull directions) and **staleness_p95**
    (SSP lag observed at pull, in rounds — bounded by
    ``DMLC_PS_STALENESS``)."""
    t0 = time.time()
    features = int(os.environ.get("PS_FEATURES", 10_000_000))
    rows = int(os.environ.get("PS_ROWS", 40_000))
    nnz = int(os.environ.get("PS_NNZ", 32))
    batch_rows = int(os.environ.get("PS_BATCH_ROWS", 2048))
    nserver = int(os.environ.get("PS_SERVERS", 2))
    nworker = int(os.environ.get("PS_WORKERS", 2))
    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    from dmlc_core_tpu.data.row_block import RowBlock
    from dmlc_core_tpu.models.linear import GBLinear
    from dmlc_core_tpu.parallel.kvstore import DistAsyncKVStore
    from dmlc_core_tpu.parallel.ps import PSClient, PSScheduler, PSServer

    class _CTRStream:
        """Re-iterable synthetic sparse CTR pages (hashing space)."""

        def __init__(self, seed):
            self.seed = seed
            self.num_col = features

        def __iter__(self):
            rng = np.random.default_rng(self.seed)
            hot = rng.choice(features, 256, replace=False)
            w_true = rng.normal(size=256).astype(np.float32)
            page = 4 * batch_rows
            for lo in range(0, rows, page):
                n = min(page, rows - lo)
                idx = rng.integers(0, features, size=(n, nnz))
                # every row carries a few signal features
                idx[:, :4] = hot[rng.integers(0, 256, size=(n, 4))]
                vals = rng.normal(size=(n, nnz)).astype(np.float32)
                sig = np.searchsorted(np.sort(hot), idx[:, :4])
                m = (vals[:, :4] * w_true[np.argsort(hot)][sig]).sum(1)
                y = (m > 0).astype(np.float32)
                off = np.arange(0, n * nnz + 1, nnz, dtype=np.int64)
                yield RowBlock(offset=off, label=y,
                               index=idx.ravel().astype(np.int64),
                               value=vals.ravel())

    sched = PSScheduler("127.0.0.1", nworker=nworker, nserver=nserver)
    sched.start()
    servers = [PSServer("127.0.0.1", sched.port, server_id=i)
               for i in range(nserver)]
    for s in servers:
        s.start()
    sthreads = [threading.Thread(target=s.serve_forever, daemon=True)
                for s in servers]
    for st in sthreads:
        st.start()

    stats = {}

    def worker(rank):
        client = PSClient(root_uri="127.0.0.1", root_port=sched.port,
                          rank=rank)
        kv = DistAsyncKVStore(client, learning_rate=0.1)
        model = GBLinear(learning_rate=0.1, reg_lambda=0.0)
        model.fit_ps(_CTRStream(seed=rank), kv, num_col=features,
                     batch_rows=batch_rows, finalize=False)
        stats[rank] = {"keys": kv.stats["keys_synced"],
                       "staleness": list(kv.staleness_samples)}
        kv.close(shutdown_job=(rank == 0))

    wthreads = [threading.Thread(target=worker, args=(r,))
                for r in range(nworker)]
    t_train = time.time()
    for wt in wthreads:
        wt.start()
    for wt in wthreads:
        wt.join()
    elapsed = time.time() - t_train
    for st in sthreads:
        st.join(timeout=30)
    sched.join(timeout=30)

    keys = sum(s["keys"] for s in stats.values())
    lags = np.array(sum((s["staleness"] for s in stats.values()), []),
                    np.float64)
    rec = {
        "bench": "ps_sparse_ctr", "provisional": False,
        "features": features, "rows_per_worker": rows, "nnz": nnz,
        "batch_rows": batch_rows, "servers": nserver, "workers": nworker,
        "elapsed_s": round(elapsed, 2),
        "rows_per_sec": round(nworker * rows / max(elapsed, 1e-9), 1),
        # each pushed id was pulled the same round: count both directions
        "keys_per_sec": round(2 * keys / max(elapsed, 1e-9), 1),
        "keys_moved": int(2 * keys),
        "staleness_p95": (float(np.percentile(lags, 95))
                          if len(lags) else None),
        "staleness_max": float(lags.max()) if len(lags) else None,
        "staleness_bound": int(os.environ.get("DMLC_PS_STALENESS", 4)),
        "pull_rounds": int(len(lags)),
        "wall_s": round(time.time() - t0, 2),
        "basis": "in-process fleet, single host: wire framing + server "
                 "aggregation are real, network hops are loopback",
    }
    _attach_metrics(rec)
    _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


# ---------------------------------------------------------------------------
# --prodsim: production-day simulation — whole-stack chaos drill
# ---------------------------------------------------------------------------

_PRODSIM_TENANTS = ["t0", "t1", "t2", "t3", "t4"]
_PRODSIM_POISON = "t2"               # the tenant whose v2 publish is poisoned
_PRODSIM_LIVE = "live"               # the stream-refreshed tenant
_PRODSIM_HOSTS = ["p0", "p1", "p2", "p3", "p4", "p5"]


def _prodsim_emit(rec, final=False):
    rec = {"metric": "prodsim_availability", "unit": "ratio",
           "provisional": not final, **rec}
    if final:
        _attach_metrics(rec)
        _attach_slo(rec)
    with _EMIT_LOCK:
        sys.stdout.write(json.dumps(rec) + "\n")
        sys.stdout.flush()


def _prodsim_ps_blocks(rank, n_features, rows, nnz=8):
    """Deterministic per-worker CSR shard (32 shared signal features so
    every shard is learnable) — the sparse-CTR lane's data."""
    from dmlc_core_tpu.data.row_block import RowBlock

    sig_rng = np.random.default_rng(7)
    hot = sig_rng.choice(n_features, 32, replace=False)
    w_true = sig_rng.normal(size=32).astype(np.float32)
    rng = np.random.default_rng(100 + rank)
    blocks = []
    for _ in range(2):
        n = rows // 2
        idx = rng.integers(0, n_features, size=(n, nnz)).astype(np.int64)
        idx[:, :4] = hot[rng.integers(0, 32, size=(n, 4))]
        vals = rng.normal(size=(n, nnz)).astype(np.float32)
        order = np.argsort(hot)
        pos = order[np.searchsorted(hot[order], idx[:, :4])]
        y = ((vals[:, :4] * w_true[pos]).sum(1) > 0).astype(np.float32)
        off = np.arange(0, n * nnz + 1, nnz, dtype=np.int64)
        blocks.append(RowBlock(offset=off, label=y, index=idx.ravel(),
                               value=vals.ravel()))
    return blocks


def _prodsim_ps_server() -> None:
    """Internal ``--prodsim-ps-server`` entry (spawned by --prodsim)."""
    from dmlc_core_tpu.base import lockcheck
    from dmlc_core_tpu.parallel.ps import PSServer

    srv = PSServer("127.0.0.1", int(os.environ["PS_SCHED_PORT"]),
                   server_id=int(os.environ["DMLC_PS_SERVER_ID"]))
    srv.start()
    srv.serve_forever(timeout_s=600)
    out = os.environ.get("PS_SERVER_STATS")
    if out:
        with open(out, "w") as f:
            json.dump({"server_id": srv.server_id,
                       "restored_version": srv.restored_version}, f)
    lockcheck.check()


def _prodsim_ps_worker() -> None:
    """Internal ``--prodsim-ps-worker`` entry: loop ``GBLinear.fit_ps``
    passes until the stop file appears, so pushes span whatever chaos
    the parent schedules; then score train accuracy on the own shard."""
    from dmlc_core_tpu.base import lockcheck
    from dmlc_core_tpu.models.linear import GBLinear
    from dmlc_core_tpu.parallel.kvstore import DistAsyncKVStore
    from dmlc_core_tpu.parallel.ps import PSClient

    rank = int(os.environ["DMLC_TASK_ID"])
    stop_file = os.environ["PRODSIM_PS_STOP"]
    n_features = int(os.environ.get("PRODSIM_PS_FEATURES", "20000"))
    client = PSClient(root_uri="127.0.0.1",
                      root_port=int(os.environ["PS_SCHED_PORT"]), rank=rank)
    kv = DistAsyncKVStore(client, learning_rate=0.5)
    blocks = _prodsim_ps_blocks(
        rank, n_features, int(os.environ.get("PRODSIM_PS_ROWS", "1200")))
    model = None
    passes = 0
    while True:
        model = GBLinear(learning_rate=0.5, reg_lambda=0.0)
        model.fit_ps(blocks, kv, num_col=n_features, batch_rows=256,
                     n_epochs=1)
        passes += 1
        if os.path.exists(stop_file):
            break
        # server-side init is first-wins (idempotent across workers),
        # so dropping the client-side guard lets the next pass re-enter
        # fit_ps and keep training the SAME fleet-resident weights
        kv._shapes.pop("gblinear", None)
    correct = total = 0
    for blk in blocks:
        rows = np.repeat(np.arange(blk.size), np.diff(blk.offset))
        m = np.zeros(blk.size, np.float32)
        np.add.at(m, rows, model.weights[blk.index] * blk.value)
        m += model.bias
        correct += int(((m > 0) == (blk.label > 0.5)).sum())
        total += blk.size
    samples = kv.staleness_samples
    with open(os.path.join(os.environ["PS_OUT"],
                           f"worker-{rank}.json"), "w") as f:
        json.dump({"rank": rank, "accuracy": correct / total,
                   "passes": passes,
                   "staleness_max": max(samples) if samples else 0}, f)
    kv.close(shutdown_job=False)    # parent owns the scheduler
    lockcheck.check()


def _prodsim_bench() -> dict:
    """``--prodsim``: one production day in one run — every tier faulted.

    Composes everything the repo has grown into a single topology: a
    live event feed streaming into an :class:`OnlineTrainer` whose
    refreshes are published through tenant-scoped staged rollouts, a
    sparse-CTR ``fit_ps`` lane on a real multi-process PS fleet, and a
    multi-tenant replica fleet (FakeTransport "hosts" supervised by a
    :class:`LauncherScaler` JobSet) serving diurnal Zipf loadgen —
    while a deterministic chaos schedule (``DMLC_PRODSIM_CHAOS``, or a
    default derived from ``DMLC_PRODSIM_SECONDS``; wall-clock
    ``at=``/``every=`` triggers, seeded by ``DMLC_FAULT_SEED``) injects
    one fault in every tier mid-run:

    * ``prodsim_replica:kill``   — SIGKILL a serving replica
    * ``prodsim_ps:kill``        — SIGKILL a PS server (respawned same
      id, snapshot-restored)
    * ``launch_host:wave``       — spot-preemption wave: 30% of fake
      hosts down AT ONCE (fires inside the JobSet monitor tick)
    * ``prodsim_shard:corrupt``  — corrupt bytes appended to the live
      stream shard (tailer must resync)
    * ``prodsim_publish:poison`` — poisoned v2 publish for ONE tenant
      (eval gate must trip, rollback must stay tenant-scoped)

    The final line is the one SLO scorecard record: availability,
    dropped/wrong, per-tier chaos evidence, launch cause-fair respawn
    budgets, PS restore, stream staleness + resyncs, and rollback
    isolation.  Returns the record (``scripts/check_prodsim.py`` calls
    this in-process and gates GREEN on ``scripts/slo/prodsim.json``)."""
    t0 = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))

    import glob
    import signal as _signal
    import subprocess
    import tempfile

    from dmlc_core_tpu.base import faultinject
    from dmlc_core_tpu.base import jitcheck
    from dmlc_core_tpu.base import knobs as _knobs

    duration = min(float(_knobs.value("DMLC_PRODSIM_SECONDS")),
                   max(budget - 240, 6.0))
    chaos_spec = str(_knobs.value("DMLC_PRODSIM_CHAOS")).strip()
    if not chaos_spec:
        # the default all-tier schedule scales with the load window
        chaos_spec = ",".join([
            f"prodsim_replica:kill:at={0.25 * duration:.3f}:n=1",
            f"prodsim_ps:kill:at={0.35 * duration:.3f}:n=1",
            f"launch_host:wave=0.3:at={0.5 * duration:.3f}:n=1",
            f"prodsim_shard:corrupt:at={0.6 * duration:.3f}:n=1",
            f"prodsim_publish:poison:at={0.7 * duration:.3f}:n=1",
        ])
    seed = int(os.environ.get("DMLC_FAULT_SEED") or "1234")
    qps = float(os.environ.get("PRODSIM_QPS", 60))
    rate = float(os.environ.get("PRODSIM_EVENTS_PER_SEC", 800))
    feats = 8
    n_rows = 400

    if os.environ.get("BENCH_FORCE_CPU"):
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    cfg = {"duration_s": round(duration, 3), "qps": qps,
           "tenants": len(_PRODSIM_TENANTS), "hosts": len(_PRODSIM_HOSTS),
           "chaos_seed": seed}
    _prodsim_emit({"value": 0.0, "phase": "setup", **cfg})

    import jax  # noqa: F401 — device init before timing anything

    from dmlc_core_tpu.base.metrics import default_registry
    from dmlc_core_tpu.io.recordio import encode_records
    from dmlc_core_tpu.launch.transport import FakeTransport
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.ps import PSScheduler
    from dmlc_core_tpu.serve.client import ResilientClient
    from dmlc_core_tpu.serve.fleet import (FleetRouter, FleetTracker,
                                           HttpFleetAdmin, LauncherScaler,
                                           Rollout, run_loadgen)
    from dmlc_core_tpu.serve.registry import clone_model
    from dmlc_core_tpu.serve.tenancy import (TenantPolicy,
                                             checkpoint_tenant_model)
    from dmlc_core_tpu.stream import (OnlineTrainer, RecordIOTailer,
                                      encode_dense_events)

    stale_hist = default_registry().histogram(
        "stream_staleness_seconds",
        "event appended → servable prediction (an activated version "
        "has trained on it)",
        buckets=(0.25, 0.5, 1, 2, 4, 8, 16, 32, 64))

    # -- per-tenant v1 models, poisoned v2, and the live tenant's v1 -----
    root = tempfile.mkdtemp(prefix="prodsim_")
    rng = np.random.default_rng(42)
    X = rng.normal(size=(n_rows, feats)).astype(np.float32)
    models, npz = {}, {"X": X}
    for i, t in enumerate(_PRODSIM_TENANTS):
        y = (X[:, i % feats] + X[:, (i + 1) % feats]
             * X[:, (i + 2) % feats] > 0).astype(np.float32)
        m = HistGBT(n_trees=3 + i, max_depth=3, n_bins=16).fit(X, y)
        models[t] = (m, y)
        npz[f"{t}__v1"] = m.predict(X)
        checkpoint_tenant_model(f"file://{root}/{t}_v1.ckpt", t, m,
                                version=1)
    y_poison = np.random.default_rng(7).permutation(
        models[_PRODSIM_POISON][1])
    m_poison = HistGBT(n_trees=4, max_depth=3, n_bins=16).fit(X, y_poison)
    poison_uri = f"file://{root}/{_PRODSIM_POISON}_v2.ckpt"
    checkpoint_tenant_model(poison_uri, _PRODSIM_POISON, m_poison,
                            version=2)
    npz[f"{_PRODSIM_POISON}__v2"] = m_poison.predict(X)
    expected_npz = os.path.join(root, "expected.npz")
    np.savez(expected_npz, **npz)
    X_hold, y_hold = X[:64], models[_PRODSIM_POISON][1][:64]
    base_mse = float(np.mean(
        (models[_PRODSIM_POISON][0].predict(X_hold) - y_hold) ** 2))

    # the live (stream-refreshed) tenant never appears in the loadgen
    # mix; its oracle is a direct bit-equality probe after each rollout
    ev_rng = np.random.default_rng(13)

    def _make_events(gen, n, drift=0.0):
        Xe = gen.normal(size=(n, feats)).astype(np.float32)
        ye = (Xe[:, 0] * Xe[:, 1]
              + (0.5 + drift) * Xe[:, 2] > 0).astype(np.float32)
        return Xe, ye

    X_live, y_live = _make_events(np.random.default_rng(5), 256)
    m_live = HistGBT(n_trees=3, max_depth=3, n_bins=16,
                     learning_rate=0.3).fit(X_live, y_live)
    live_v1_uri = f"file://{root}/live_v1.ckpt"
    checkpoint_tenant_model(live_v1_uri, _PRODSIM_LIVE, m_live, version=1)

    # -- fleet: tracker + fake 6-host cluster + launcher-backed scaler ---
    _prodsim_emit({"value": 0.0, "phase": "spawn", **cfg})
    child_env = {"JAX_PLATFORMS": "cpu", "DMLC_TPU_FORCE_CPU": "1",
                 "FLEET_TENANCY": "1", "DMLC_FAULT_INJECT": ""}
    tracker = FleetTracker(nworker=16)
    tracker.start()
    transport = FakeTransport(hosts=list(_PRODSIM_HOSTS),
                              log_dir=os.path.join(root, "logs"))
    scaler = LauncherScaler(tracker, None, name="prodsim",
                            transport=transport, initial=3,
                            spawn_env=child_env, restart_limit=3)

    # -- shared state for the lanes --------------------------------------
    stop_gen = threading.Event()
    stop_stream = threading.Event()
    stop_chaos = threading.Event()
    stop_recon = threading.Event()
    live_lock = threading.Lock()
    live_state = {"version": 1, "activated": 1, "model": m_live,
                  "uri": live_v1_uri, "served_floor": 0}
    append_ts = []
    staleness = []
    refreshes = []
    live_rollouts = []
    chaos_log = []
    poison_report = {}
    ps_state = {}
    shard_dir = os.path.join(root, "events")
    os.makedirs(shard_dir)
    shard_events = 2048

    def _generator():
        written = 0
        shard_idx = 0
        f = open(os.path.join(shard_dir, f"part-{shard_idx:04d}.rec"), "ab")
        start = time.perf_counter()
        try:
            while not stop_gen.is_set():
                target = int((time.perf_counter() - start) * rate)
                burst = min(target - written, 2048)
                if burst <= 0:
                    time.sleep(0.01)
                    continue
                Xe, ye = _make_events(
                    ev_rng, burst, drift=0.2 * ((written // shard_events)
                                                % 3))
                f.write(encode_records(encode_dense_events(Xe, ye)))
                f.flush()
                now = time.time()
                append_ts.extend([now] * burst)
                written += burst
                if written // shard_events > shard_idx:
                    f.close()
                    shard_idx = written // shard_events
                    f = open(os.path.join(
                        shard_dir, f"part-{shard_idx:04d}.rec"), "ab")
        finally:
            f.close()

    tailer = RecordIOTailer(shard_dir,
                            cursor_uri=os.path.join(root, "cursor.ckpt"),
                            name="prodsim")
    live_model = HistGBT(n_trees=2, max_depth=3, n_bins=16,
                         learning_rate=0.3)
    trainer = OnlineTrainer(live_model, tailer, n_features=feats,
                            chunk_rows=512, window_chunks=2, decay=1.0,
                            name="prodsim")

    def _stream_lane():
        # tail → warm-start boost → tenant-scoped staged rollout; an
        # infra rollback (replica died mid-wave) is recorded and retried
        # by the next refresh — only a gate trip is a real rollback
        while not stop_stream.is_set():
            try:
                r = trainer.refresh(timeout=1.0, stop=stop_stream.is_set)
            except Exception as e:  # noqa: BLE001
                chaos_log.append({
                    "t": round(time.time() - t0, 3), "point": "stream",
                    "detail": f"refresh ERROR {type(e).__name__}: "
                              f"{e}"[:200]})
                time.sleep(0.2)
                continue
            if r is None:
                continue
            refreshes.append(r)
            if (jitcheck.installed()
                    and jitcheck.current_phase() == "warmup"
                    and r.get("window_rows", 0) >= 512 * 2):
                # trainer window (chunk_rows=512 × window_chunks=2) just
                # reached its final shape — the parent's only jax work
                # from here is refresh reuse, so compiles are stalls
                jitcheck.steady()
            with live_lock:
                version = live_state["version"] + 1
                live_state["version"] = version
            uri = f"file://{root}/live_v{version}.ckpt"
            snap = clone_model(live_model)
            checkpoint_tenant_model(uri, _PRODSIM_LIVE, snap,
                                    version=version)
            try:
                admin = HttpFleetAdmin(dict(tracker.serve_endpoints()))
                rep = Rollout(admin, wave_size=1, settle_s=0.1,
                              tenant=_PRODSIM_LIVE).run(uri)
            except Exception as e:  # noqa: BLE001
                live_rollouts.append({
                    "version": version,
                    "outcome": f"error: {type(e).__name__}"})
                continue
            live_rollouts.append({"version": version,
                                  "outcome": rep.get("outcome"),
                                  "waves": rep.get("waves")})
            if rep.get("outcome") != "activated":
                continue
            with live_lock:
                live_state.update(activated=version, model=snap, uri=uri)
                floor = live_state["served_floor"]
            now = time.time()
            covered = min(r["records_total"], len(append_ts))
            for seq in range(floor, covered):
                s = now - append_ts[seq]
                staleness.append(s)
                stale_hist.observe(s)
            with live_lock:
                live_state["served_floor"] = covered

    def _reconciler():
        # heal freshly-respawned replicas: any tenant missing from a
        # health doc is (re)loaded at its current good version — never
        # fights a rollout, which only moves tenants that ARE present
        all_tenants = _PRODSIM_TENANTS + [_PRODSIM_LIVE]
        while not stop_recon.is_set():
            try:
                eps = dict(tracker.serve_endpoints())
                admin = HttpFleetAdmin(eps)
                for rank in eps:
                    try:
                        tdoc = admin.health(rank).get("tenants", {})
                    except Exception:  # noqa: BLE001 — mid-respawn
                        continue
                    for t in all_tenants:
                        if t in tdoc:
                            continue
                        if t == _PRODSIM_LIVE:
                            with live_lock:
                                uri = live_state["uri"]
                        else:
                            uri = f"file://{root}/{t}_v1.ckpt"
                        try:
                            admin.load(rank, uri, activate=True, tenant=t)
                        except Exception:  # noqa: BLE001
                            pass
            except Exception:  # noqa: BLE001
                pass
            stop_recon.wait(0.4)

    # -- PS lane: scheduler in-parent, 2 servers + 2 workers as procs ----
    ps_dir = os.path.join(root, "ps")
    snap_dir = os.path.join(ps_dir, "snap")
    os.makedirs(snap_dir)
    ps_stop_file = os.path.join(ps_dir, "stop")
    sched = PSScheduler("127.0.0.1", nworker=2, nserver=2)
    sched.start()

    def _launch_ps(role, server_id=-1, rank=-1, stats=""):
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   DMLC_TPU_FORCE_CPU="1",
                   DMLC_FAULT_INJECT="",
                   DMLC_PS_SNAPSHOT_DIR=snap_dir,
                   DMLC_PS_SNAPSHOT_STRIDE="1",
                   DMLC_PS_RECONNECT_S="120",
                   DMLC_PS_SERVER_ID=str(server_id),
                   DMLC_TASK_ID=str(rank),
                   PS_SCHED_PORT=str(sched.port),
                   PS_OUT=ps_dir,
                   PS_SERVER_STATS=stats,
                   PRODSIM_PS_STOP=ps_stop_file)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             f"--prodsim-ps-{role}"], env=env)

    ps_servers = [_launch_ps("server", server_id=i) for i in range(2)]
    ps_workers = [_launch_ps("worker", rank=r) for r in range(2)]
    ps_state["respawn_stats"] = os.path.join(ps_dir, "respawn.json")

    # -- chaos actions (one per tier; launch_host fires in the JobSet
    # monitor tick, inside FakeTransport) --------------------------------
    def _fault_replica(fault):
        st = scaler.jobset.stats()
        live = sorted(r for r, d in st["ranks"].items() if not d["done"])
        if not live:
            return "no live rank"
        scaler.jobset.kill(live[0], sig=_signal.SIGKILL, respawn=True)
        return f"SIGKILL replica rank {live[0]}"

    def _fault_ps(fault):
        victim = ps_servers[1]
        victim.send_signal(_signal.SIGKILL)
        victim.wait(timeout=60)
        ps_state["victim_rc"] = victim.returncode
        ps_servers[1] = _launch_ps("server", server_id=1,
                                   stats=ps_state["respawn_stats"])
        return (f"SIGKILL ps server 1 (rc={victim.returncode}); "
                "respawned same id")

    def _fault_shard(fault):
        # smash 64 bytes at the tailer's OWN read position: consumed
        # offsets always sit on record boundaries, so the very next
        # poll sees non-magic where a record must start and has to
        # resync forward — corrupting the newest shard instead would
        # sit unread until the (slower) trainer caught up to it
        shards = sorted(glob.glob(os.path.join(shard_dir, "part-*.rec")))
        offs = dict(tailer.cursor().offsets)
        target, off = shards[-1], 0
        for path in shards:
            done = offs.get(path, 0)
            if done < os.path.getsize(path):
                target, off = path, done
                break
        with open(target, "r+b") as f:
            f.seek(off)
            f.write(b"\x00" * 64)    # no magic, keeps 4-byte alignment
        return (f"smashed 64 bytes at {os.path.basename(target)}"
                f"+{off} (tailer cursor)")

    def _poison_gate(admin, endpoints):
        def gate(version):
            # honest gate: score the holdout against each replica that
            # actually serves the candidate version for the tenant
            for rank, url in endpoints.items():
                try:
                    tdoc = admin.health(rank).get("tenants", {}).get(
                        _PRODSIM_POISON, {})
                    if tdoc.get("version") != version:
                        continue
                    p, v = ResilientClient(url).predict(
                        X_hold, tenant=_PRODSIM_POISON)
                except Exception:  # noqa: BLE001 — replica mid-churn
                    continue
                if v != version:
                    continue
                mse = float(np.mean((p - y_hold) ** 2))
                if mse > 2.0 * base_mse + 1e-6:
                    return False
            return True
        return gate

    def _fault_publish(fault):
        endpoints = dict(tracker.serve_endpoints())
        admin = HttpFleetAdmin(endpoints)
        rep = Rollout(admin, wave_size=1, settle_s=0.3,
                      eval_gate=_poison_gate(admin, endpoints),
                      tenant=_PRODSIM_POISON).run(poison_uri)
        poison_report.update(rep)
        return f"poisoned publish outcome={rep.get('outcome')}"

    def _chaos_driver():
        actions = (("prodsim_replica", _fault_replica),
                   ("prodsim_ps", _fault_ps),
                   ("prodsim_shard", _fault_shard),
                   ("prodsim_publish", _fault_publish))
        while not stop_chaos.is_set():
            for point, action in actions:
                fault = faultinject.check(point)
                if fault is None:
                    continue
                try:
                    detail = action(fault)
                except Exception as e:  # noqa: BLE001
                    detail = f"ERROR {type(e).__name__}: {e}"[:200]
                chaos_log.append({"t": round(time.time() - t0, 3),
                                  "point": point, "kind": fault.kind,
                                  "detail": detail})
            stop_chaos.wait(0.05)

    router = None
    merged = {}
    chaos_fired = {}
    chaos_rules = []
    try:
        deadline = time.time() + 180
        while len(tracker.serve_endpoints()) < 3:
            if time.time() > deadline:
                raise RuntimeError("prodsim replicas never registered")
            time.sleep(0.2)
        endpoints = dict(tracker.serve_endpoints())
        admin = HttpFleetAdmin(endpoints)
        for rank in endpoints:
            for t in _PRODSIM_TENANTS:
                admin.load(rank, f"file://{root}/{t}_v1.ckpt",
                           activate=True, tenant=t)
            admin.load(rank, live_v1_uri, activate=True,
                       tenant=_PRODSIM_LIVE)
        policy = TenantPolicy(classes="gold:t0;bronze:t4",
                              default_class="silver", quota=0,
                              max_inflight=256, shed_fraction=0.5,
                              hedge_ms=0)
        router = FleetRouter(tracker, probe_s=0.2, policy=policy).start()
        probe, ver = ResilientClient(router.url).predict(X[:8],
                                                         tenant="t1")
        if ver != 1 or not np.array_equal(probe, npz["t1__v1"][:8]):
            raise RuntimeError("prodsim: routed warmup predict mismatch")

        gen_t = threading.Thread(target=_generator, daemon=True,
                                 name="prodsim-gen")
        lane_t = threading.Thread(target=_stream_lane, daemon=True,
                                  name="prodsim-stream")
        recon_t = threading.Thread(target=_reconciler, daemon=True,
                                   name="prodsim-recon")
        gen_t.start()
        lane_t.start()
        recon_t.start()

        _prodsim_emit({"value": 0.0, "phase": "load", **cfg})
        with faultinject.inject(chaos_spec, seed=seed):
            chaos_t = threading.Thread(target=_chaos_driver, daemon=True,
                                       name="prodsim-chaos")
            chaos_t.start()
            merged = run_loadgen(
                router.url, expected_npz, duration_s=duration, procs=2,
                threads=3, base_qps=qps, amplitude=0.5,
                period_s=max(duration / 2.0, 2.0), timeout_ms=20_000,
                workdir=root, env=child_env,
                tenants=list(_PRODSIM_TENANTS))
            # let straggler rules (and the wave, which fires in the
            # supervisor tick) finish before tearing the schedule down
            fire_deadline = time.time() + max(duration, 10.0)
            while time.time() < fire_deadline:
                if all(r["fires"] >= 1 for r in faultinject.rules()):
                    break
                time.sleep(0.2)
            stop_chaos.set()
            chaos_t.join(timeout=90)
            chaos_fired = faultinject.stats()
            chaos_rules = faultinject.rules()
        wave_hosts = transport.down_hosts()

        stop_gen.set()
        gen_t.join(timeout=10)
        stop_stream.set()
        lane_t.join(timeout=120)

        # the production day is over — close the steady window before
        # the oracle probes below (their fresh batch shapes may compile;
        # that is post-run bookkeeping, not a serving-path stall)
        recompiles_steady = (len(jitcheck.compiles("steady"))
                             if jitcheck.installed() else None)
        jitcheck.warmup()

        # live-tenant oracle: the routed answer must be bit-identical to
        # the snapshot of the last ACTIVATED refresh (reconciler still
        # healing respawned replicas, so allow convergence time)
        with live_lock:
            want_ver = live_state["activated"]
            want_model = live_state["model"]
        want_pred = want_model.predict(X_live[:32])
        live_ok = 0
        client = ResilientClient(router.url)
        probe_deadline = time.time() + 60
        while time.time() < probe_deadline:
            try:
                p, v = client.predict(X_live[:32], tenant=_PRODSIM_LIVE)
                if v == want_ver and np.array_equal(p, want_pred):
                    live_ok = 1
                    break
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.5)

        # rollback isolation: every static tenant on every replica back
        # on v1 — the poisoned v2 stuck nowhere
        isolated = 0
        iso_deadline = time.time() + 60
        while time.time() < iso_deadline:
            try:
                eps = dict(tracker.serve_endpoints())
                admin = HttpFleetAdmin(eps)
                if eps and all(
                        admin.health(rank).get("tenants", {})
                        .get(t, {}).get("version") == 1
                        for rank in eps for t in _PRODSIM_TENANTS):
                    isolated = 1
                    break
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.5)
        stop_recon.set()
        recon_t.join(timeout=10)

        # drain the PS lane: stop file → workers finish the pass and
        # exit; job completion lets the servers write stats and exit
        with open(ps_stop_file, "w") as f:
            f.write("stop\n")
        ps_rcs = {"workers": [], "servers": []}
        ps_deadline = time.time() + 180
        for p in ps_workers + ps_servers:
            try:
                p.wait(timeout=max(1.0, ps_deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        ps_rcs["workers"] = [p.returncode for p in ps_workers]
        ps_rcs["servers"] = [p.returncode for p in ps_servers]
        worker_stats = {}
        for r in range(2):
            path = os.path.join(ps_dir, f"worker-{r}.json")
            if os.path.exists(path):
                with open(path) as f:
                    worker_stats[r] = json.load(f)
        respawn = None
        if os.path.exists(ps_state["respawn_stats"]):
            with open(ps_state["respawn_stats"]) as f:
                respawn = json.load(f)

        st = scaler.jobset.stats()
        giveups = sum(1 for e in scaler.jobset.events()
                      if e.get("event") == "giveup")
        static_rb = 0.0
        snap = default_registry().snapshot()["metrics"]
        for s in snap.get("dmlc_tenant_rollbacks_total",
                          {}).get("series", []):
            tlabel = s["labels"].get("tenant")
            if tlabel in _PRODSIM_TENANTS and tlabel != _PRODSIM_POISON:
                static_rb += s["value"]

        stale_sorted = sorted(staleness)

        def q(p):
            if not stale_sorted:
                return None
            return round(stale_sorted[min(len(stale_sorted) - 1,
                                          int(round(p * (len(stale_sorted)
                                                         - 1))))], 3)

        tiers = {
            "replica": int(any(l.get("point") == "prodsim_replica"
                               for l in chaos_log)),
            "ps": int(any(l.get("point") == "prodsim_ps"
                          for l in chaos_log)),
            "host": int(chaos_fired.get("launch_host:wave", 0) >= 1),
            "shard": int(any(l.get("point") == "prodsim_shard"
                             for l in chaos_log)),
            "publish": int(any(l.get("point") == "prodsim_publish"
                               for l in chaos_log)),
        }
        availability = merged.get("ok", 0) / max(merged.get("count", 0), 1)

        rec = {
            "value": round(availability, 5),
            "phase": "done",
            "elapsed_s": round(time.time() - t0, 1),
            "platform": jax.devices()[0].platform,
            "availability": round(availability, 5),
            "dropped": merged.get("dropped"),
            "wrong": merged.get("wrong"),
            "loadgen": {k: merged.get(k) for k in
                        ("count", "ok", "dropped", "wrong", "shed",
                         "throughput_rps", "latency_p50_ms",
                         "latency_p95_ms", "latency_p99_ms",
                         "by_tenant")},
            "chaos": {
                "schedule": chaos_spec,
                "seed": seed,
                "fired": chaos_fired,
                "rules": chaos_rules,
                "tiers": tiers,
                "tiers_faulted": int(sum(tiers.values())),
                "wave_hosts": wave_hosts,
                "log": chaos_log,
            },
            "launch": {
                "backend": st["backend"],
                "respawns": st["respawns"],
                "respawns_by_cause": st["respawns_by_cause"],
                "host_faults": st["host_faults"],
                "spawn_ms_p95": st["spawn_ms_p95"],
                "giveups": giveups,
            },
            "ps": {
                "victim_rc": ps_state.get("victim_rc"),
                "victim_sigkilled": int(ps_state.get("victim_rc")
                                        == -_signal.SIGKILL),
                "respawn": respawn,
                "restored_version": (respawn or {}).get(
                    "restored_version"),
                "workers": worker_stats,
                "min_accuracy": (min(w["accuracy"]
                                     for w in worker_stats.values())
                                 if worker_stats else None),
                "rcs": ps_rcs,
            },
            "stream": {
                "refreshes": len(refreshes),
                "rollouts": live_rollouts,
                "activated": sum(1 for lr in live_rollouts
                                 if lr.get("outcome") == "activated"),
                "staleness_seconds": {"p50": q(0.50), "p95": q(0.95),
                                      "p99": q(0.99)},
                "resyncs": tailer.resyncs,
                "events_appended": len(append_ts),
                "events_consumed": tailer.records_seen,
                "live_version": want_ver,
                "live_verified": live_ok,
            },
            "rollback": {
                "poisoned": int(poison_report.get("outcome")
                                == "rolled_back"),
                "poison_waves": poison_report.get("waves"),
                "static_rollbacks": static_rb,
                "isolated": isolated,
            },
            "recompiles_steady_state": recompiles_steady,
            **cfg,
        }
        _prodsim_emit(rec, final=True)
        if recompiles_steady is not None:
            # DMLC_JITCHECK=1 makes the record a gate: a compile during
            # the load window is a steady-state stall, fail loudly
            jitcheck.check()
        return rec
    finally:
        stop_gen.set()
        stop_stream.set()
        stop_chaos.set()
        stop_recon.set()
        if router is not None:
            router.close()
        try:
            tailer.close()
        except Exception:  # noqa: BLE001
            pass
        scaler.reap(timeout=15)
        tracker.stop()
        transport.close()
        for p in ps_workers + ps_servers:
            if p.poll() is None:
                p.kill()
        try:
            sched.stop()
        except Exception:  # noqa: BLE001
            pass


def main() -> None:
    EV["t0"] = time.time()
    budget = float(os.environ.get("BENCH_TIME_BUDGET", 480))
    deadline = EV["t0"] + budget
    _install_guards(deadline)

    warmup = int(os.environ.get("BENCH_WARMUP", 10))
    depth = int(os.environ.get("BENCH_DEPTH", 6))
    n_bins = int(os.environ.get("BENCH_BINS", 256))

    # PR 12 levers are ON in the flagship config: int4 bin
    # packing, exclusive-feature bundling, and loss-guide growth at half
    # the depth-wise build budget (16 expansions vs 2^(depth-1)=32
    # builds at depth 6).  setdefault so an operator can still A/B any
    # lever off (DMLC_BIN_PACK=0 etc.); the exact setting ships in the
    # record's config.levers block either way.  The growth policy and
    # the leaf budget are hyperparameters of the model
    # (``HistGBTParam.grow_policy`` / ``max_leaves``): BENCH_GROW_POLICY
    # and BENCH_MAX_LEAVES are this script's way to say them.
    os.environ.setdefault("DMLC_BIN_PACK", "1")
    os.environ.setdefault("DMLC_FEATURE_BUNDLE", "1")
    grow_policy = os.environ.get("BENCH_GROW_POLICY", "lossguide")
    max_leaves = int(os.environ.get("BENCH_MAX_LEAVES",
                                    max(1 << (depth - 2), 4)))

    if os.environ.get("BENCH_FORCE_CPU"):
        # self-test hook: N virtual CPU devices, asked for explicitly
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(int(os.environ["BENCH_FORCE_CPU"]))

    import jax

    from dmlc_core_tpu.base import compile_cache as _cc
    from dmlc_core_tpu.base import jitcheck
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    # persistent XLA compile cache (doc/performance.md): a warm rerun
    # of this bench deserializes the round program instead of paying
    # the ~30 s compile again; DMLC_COMPILE_CACHE=0 opts out
    _cc.configure()

    EV["phase"] = "probe"
    emit()
    devices = jax.devices()
    EV["platform"] = devices[0].platform
    EV["device_kind"] = devices[0].device_kind
    if EV["platform"] != "tpu" and not os.environ.get("BENCH_FORCE_CPU"):
        # a benchmark number comes from the chip or not at all
        emit(final=True, error=(
            f"no TPU: jax.devices() is {len(devices)} x "
            f"{EV['platform']} — set BENCH_FORCE_CPU=N for a CPU "
            f"self-test run"))
        sys.exit(2)
    peak = peak_bf16(devices[0])

    rows = int(os.environ.get("BENCH_ROWS", 10_000_000))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    rounds = int(os.environ.get("BENCH_ROUNDS", 100))
    EV["config"] = {"rows": rows, "features": feats, "rounds": rounds,
                    "max_depth": depth, "n_bins": n_bins,
                    "levers": {
                        "bin_pack": os.environ["DMLC_BIN_PACK"] == "1",
                        "feature_bundle":
                            os.environ["DMLC_FEATURE_BUNDLE"] == "1",
                        "grow_policy": grow_policy,
                        "max_leaves": max_leaves,
                    }}

    # chips=N mode (ISSUE 7): BENCH_CHIPS pins the data-mesh width (0 /
    # unset = every local device — 1 chip on a single-chip host, 8 on a
    # v5e-8 slice).  Rows shard over the mesh, the per-level histogram
    # psum is the only cross-chip traffic, and the headline stays
    # per-chip so the scaling block below can score efficiency.
    chips_req = int(os.environ.get("BENCH_CHIPS", "0") or 0)
    avail = len(devices)
    if chips_req > avail:
        EV["notes"].append(
            f"BENCH_CHIPS={chips_req} clamped to {avail} local devices")
        chips_req = avail
    mesh = local_mesh(chips_req or None)  # all local devices by default
    n_chips = mesh.devices.size
    EV["config"] = {**EV["config"], "chips": n_chips}   # rebind, no mutate
    model = HistGBT(
        n_trees=rounds,
        max_depth=depth,
        n_bins=n_bins,
        learning_rate=0.1,
        grow_policy=grow_policy,
        max_leaves=max_leaves,
        mesh=mesh,
    )
    # cold-start overlap, bench half: the round-program compile (or its
    # persistent-cache deserialize) starts NOW, overlapping the whole
    # datagen + cuts + ingest stretch below — this is what collapses
    # warmup_seconds to compile-join residue when the ci.sh pre-seed
    # already warmed the cache (compile_cache: hit)
    model.start_warmup(rows, feats)
    EV["phase"] = "datagen"
    emit()

    # HIGGS-like synthetic: dense gaussians + a nonlinear decision rule
    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, feats)).astype(np.float32)
    margin = X[:, 0] * X[:, 1] + 0.5 * X[:, 2] - 0.8 * X[:, 3] * (X[:, 4] > 0)
    y = (margin > 0).astype(np.float32)

    EV["phase"] = "prepare"      # cuts + bin on device: setup
    emit()
    dd = model.make_device_data(X, y)
    # everything from here runs off the device-resident handle; the host
    # copies (~1.2 GB at 10M×28) would otherwise sit in RAM to the end
    del X, y, margin
    # cold-start evidence: the host wall of the staging calls up to
    # their last enqueue (the round-program compile overlaps it — see
    # the per-run warmup breakdown)
    EV["config"] = {**EV["config"],
                    "bin_seconds": round(model.last_bin_seconds or 0.0, 3)}

    def _run_once(warmup_rounds):
        """One timed fit on the device-resident handle; returns an
        evidence dict with per-chunk rates.

        Each chunk arrival fires ``chunk_callback`` → a provisional JSON
        line, so even a SIGKILL mid-fit leaves the latest rate on
        stdout.  Per-chunk sec/round is the auditable unit: on a healthy
        chip all chunks run at the same rate; one stalled dispatch shows
        up as a worst/best chunk ratio ≫ 1."""
        EV["chunk_times"] = []
        steady_before = (len(jitcheck.compiles("steady"))
                         if jitcheck.installed() else 0)

        def cb(done, t_s):
            EV["chunk_times"].append((done, t_s))
            if (jitcheck.installed()
                    and jitcheck.current_phase() == "warmup"):
                # first chunk on host ⇒ warmup (compile-join + warm
                # dispatch) is over; any compile in chunks 2..N is the
                # PR 18 bug class resurfacing mid-fit
                jitcheck.steady()
            emit()

        model.fit_device(dd, warmup_rounds=warmup_rounds,
                         chunk_callback=cb)
        recompiles_steady = None
        if jitcheck.installed():
            recompiles_steady = (len(jitcheck.compiles("steady"))
                                 - steady_before)
            jitcheck.warmup()   # re-measures compile legitimately
        seconds = model.last_fit_seconds
        out = {
            "seconds": round(seconds, 3),
            "warmup_seconds": round(model.last_warmup_seconds, 3),
            "rounds_done": rounds,
        }
        if recompiles_steady is not None:
            out["recompiles_steady_state"] = recompiles_steady
        # cold-start breakdown (doc/performance.md): warmup_seconds =
        # compile-join residue + warm dispatch; compile_seconds is the
        # background compile's critical path (null on the inline path);
        # compile_cache says whether XLA read or wrote the persistent
        # cache ("warm" = no cache traffic at all — in-memory caches
        # served everything, e.g. the re-measure run)
        if model.last_compile_seconds is not None:
            out["compile_seconds"] = round(model.last_compile_seconds, 3)
        if model.last_warm_dispatch_seconds is not None:
            out["warm_dispatch_seconds"] = round(
                model.last_warm_dispatch_seconds, 3)
        # {trace, dispatch, device} attribution of warm_dispatch (the
        # r06 regression lever: 98 s of "warm dispatch" was the exec
        # warmup running the full K-round chunk on CPU — now the exec
        # runs on a TPU backend only and trace = inline AOT compile)
        if model.last_warmup_breakdown is not None:
            out["warmup_breakdown"] = model.last_warmup_breakdown
        out["compile_cache"] = model.last_compile_cache or "warm"
        out.update(chunk_stats(model.last_chunk_times, rounds, seconds))
        # time from entering the timed fit to the FIRST trained trees
        # arriving on host = warmup + the first dispatch chunk (add
        # config.bin_seconds for the full cold start incl. staging)
        if model.last_chunk_times:
            out["time_to_first_tree"] = round(
                model.last_warmup_seconds + model.last_chunk_times[0][1],
                3)
        out["wall_rounds_per_sec"] = round(rounds / seconds / n_chips, 4)
        return out

    EV["phase"] = "warmup+timed"
    emit()
    try:
        runs = [_run_once(warmup)]
        EV["runs"] = runs
        if runs[0]["anomaly"]:
            # one dispatch orders of magnitude slower than its
            # siblings.  Re-measure once ON THE
            # RESIDENT DATA (fit_device: no re-upload, jit cache warm) —
            # but only if the budget still fits a full run; otherwise the
            # median-chunk rate of run 1 is the defensible number.
            est = runs[0]["seconds"] * 1.5 + 30
            if deadline - time.time() > est:
                EV["notes"].append("chunk-rate anomaly: re-measuring once "
                                   "on resident data")
                emit()
                try:
                    runs.append(_run_once(1))
                except Exception as e:  # noqa: BLE001
                    EV["notes"].append(
                        f"re-measure failed ({type(e).__name__}: {e}), "
                        "keeping first run")
            else:
                EV["notes"].append(
                    f"chunk-rate anomaly but only {deadline - time.time():.0f}s "
                    f"budget left (< {est:.0f}s): re-measure skipped")
    except Exception as e:  # noqa: BLE001 — bench must always emit a line
        emit(final=True, error=f"{type(e).__name__}: {e}"[:500])
        os._exit(3)

    # Official selection: the FIRST non-anomalous run (never best-of-2 —
    # an upward-biased headline); if every run is anomalous, report the
    # best run's MEDIAN-chunk rate (the wall number is corrupted by the
    # stalled dispatch, the median chunk is not).
    non_anom = [r for r in runs if not r["anomaly"]]
    if non_anom:
        official = dict(non_anom[0])
        value = official["wall_rounds_per_sec"]
        EV["value_basis"] = "wall"
    else:
        official = dict(max(
            runs, key=lambda r: r["rounds_per_sec_median_chunk"]))
        value = official["rounds_per_sec_median_chunk"] / n_chips
        EV["value_basis"] = "median_chunk"
    official["value"] = value
    official.update(_derived_metrics(
        rows, feats, depth, n_bins,
        1.0 / (value * n_chips), peak, n_chips,
        layout=model._bin_layout,
        grow_policy=model.round_plan["grow_policy"],
        max_leaves=model.round_plan.get("max_leaves", 0)))
    official["round_plan"] = model.round_plan
    EV["official"] = official
    EV["runs"] = runs
    emit()           # headline is now on stdout before scaling

    # -- multi-chip evidence (chips > 1 only): psum probe + 1-chip
    # oracle re-measure for scaling efficiency.  Both budget-gated and
    # non-fatal; the headline above is already emitted.
    if n_chips > 1:
        try:
            official["psum_probe"] = _psum_probe(mesh, depth, feats,
                                                 n_bins)
        except Exception as e:  # noqa: BLE001
            EV["notes"].append(
                f"psum probe failed: {type(e).__name__}: {e}"[:200])
        baseline_est = (EV["config"].get("bin_seconds", 30.0)
                        + rows * feats * 4 / 60e6 + 30.0
                        + rounds / max(value, 1e-6))
        if os.environ.get("BENCH_SCALING", "1") == "0":
            EV["notes"].append("scaling baseline skipped: BENCH_SCALING=0")
        elif deadline - time.time() < baseline_est + 90:
            EV["notes"].append(
                f"scaling baseline skipped: needs ~{baseline_est:.0f}s "
                f"of the {deadline - time.time():.0f}s left")
        else:
            EV["phase"] = "scaling_baseline"
            emit()
            try:
                # same global rows (same datagen seed), same cuts, one
                # chip: the denominator of scaling_efficiency
                rng_b = np.random.default_rng(7)
                Xb = rng_b.normal(size=(rows, feats)).astype(np.float32)
                mb = Xb[:, 0] * Xb[:, 1] + 0.5 * Xb[:, 2] \
                    - 0.8 * Xb[:, 3] * (Xb[:, 4] > 0)
                yb = (mb > 0).astype(np.float32)
                model1 = HistGBT(n_trees=rounds, max_depth=depth,
                                 n_bins=n_bins, learning_rate=0.1,
                                 grow_policy=grow_policy,
                                 max_leaves=max_leaves,
                                 mesh=local_mesh(1))
                dd1 = model1.make_device_data(
                    Xb, yb, cuts=np.asarray(model.cuts))
                del Xb, yb, mb
                model1.fit_device(dd1, warmup_rounds=1)
                base_rate = rounds / model1.last_fit_seconds
                official["scaling"] = scaling_summary(
                    n_chips, value, base_rate)
            except Exception as e:  # noqa: BLE001
                EV["notes"].append(
                    f"scaling baseline failed: "
                    f"{type(e).__name__}: {e}"[:200])

    EV["phase"] = "done"
    emit(final=True)


if __name__ == "__main__":
    # observability plane: join the metrics spool when one is configured
    # (no-op otherwise) so the bench parent's registry merges with any
    # spawned replicas'/workers' under one DMLC_METRICS_SPOOL directory
    from dmlc_core_tpu.base.metrics_agg import install_spool
    if "--prodsim-ps-server" in sys.argv:
        install_spool("prodsim_ps_server",
                      int(os.environ.get("DMLC_PS_SERVER_ID", "0")))
        _prodsim_ps_server()
        sys.exit(0)
    if "--prodsim-ps-worker" in sys.argv:
        install_spool("prodsim_ps_worker",
                      int(os.environ.get("DMLC_TASK_ID", "0")))
        _prodsim_ps_worker()
        sys.exit(0)
    install_spool("bench", 0)
    if "--serve" in sys.argv:
        _serve_bench()
    elif "--fleet" in sys.argv:
        _fleet_bench()
    elif "--tenants" in sys.argv:
        _tenants_bench()
    elif "--stream" in sys.argv:
        _stream_bench()
    elif "--ps" in sys.argv:
        _ps_bench()
    elif "--prodsim" in sys.argv:
        _prodsim_bench()
    else:
        main()
