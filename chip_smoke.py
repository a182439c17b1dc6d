#!/usr/bin/env python
"""chip_smoke.py — does today's code still start on the chip?

One process drives the flagship path once, through the entry points a
user calls, at HIGGS-10M width: ``HistGBT(...).fit(X, y)`` (10,000,000 x
28 float32, depth 6, 256 bins, 50 rounds = two 25-round dispatches,
library defaults, no ``DMLC_*`` set, cuts and binning on the device),
``predict`` on a 1M-row slab, and the same model behind
``serve.ModelRunner`` across the bucket ladder, bit-compared with
``model.predict``.  Around it:

* it REFUSES to run unless ``jax.default_backend() == "tpu"`` — exit
  code 2, nothing trained, no result line.  There is no CPU mode;
* what ran is read from the model (``round_plan``, ``last_dispatch``,
  ``last_compile_cache``...), never re-derived: at this shape every
  tree level must have resolved to the ``pallas`` histogram and no
  kernel may be interpreted;
* the model must learn: training logloss falls across the rounds and
  AUC on 1M held-out rows clears :data:`AUC_FLOOR` (the label is a
  deterministic rule over five features — a wrong histogram shows as a
  model that does not learn);
* kernel roll-call: every Pallas kernel of ``ops/histogram.py`` at
  flagship widths, compiled by Mosaic and ``array_equal`` to the
  ``segment`` engine on bf16-exact gradients;
* with more than one device: the same fit on ``local_mesh()`` over all
  of them in this process — sharded ingest leaves every chip its share,
  held-out AUC matches the one-chip model, and ``DMLC_HIST_BLOCKS``
  still serializes byte-identically to one chip at a reduced size.

Every wall time printed here is a SMOKE number — one run, compile
included, no repeats — not a benchmark.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; the full
report also lands in ``chiprun_out/chip_smoke.json``.  Exit code 0 only
if every phase passed.

``tests/test_chip_smoke.py`` imports :func:`run_smoke` and drives it at
a tiny size on the virtual CPU mesh with the kernels interpreted, so
the script cannot rot between chip runs.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: held-out AUC the flagship fit must clear.  Set from the first passing
#: chip runs of this script (TPU v5 lite, PR 23): 0.99921 on one chip
#: and on four — the floor leaves room for near-tie splits, not for a
#: model that learned something else.
AUC_FLOOR = 0.995

#: |AUC(one chip) - AUC(all chips)| allowed on the held-out rows: the
#: plain psum sums shard partials in another order, so last-ulp gains
#: and an occasional near-tie split may differ — the ranking quality
#: must not (measured difference on the first run: 0.0 to five digits)
AUC_MESH_TOL = 1e-3

#: per-device spread of the bytes the mesh fit holds, (max-min)/max
SHARD_BYTES_TOL = 0.05


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one smoke run.  The defaults are what the script runs;
    the CPU test passes a tiny one."""
    rows: int = 10_000_000
    features: int = 28
    n_trees: int = 50
    max_depth: int = 6
    n_bins: int = 256
    holdout_rows: int = 1_000_000
    hist_method: str = "auto"
    serve_sizes: Tuple[int, ...] = (1, 8, 9, 100, 1000, 1024, 2500)
    rollcall_rows: int = 1_000_000
    rollcall_nodes: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    tile_rows: int = 0                      # 0 = the library's _TILE_ROWS
    det_rows: int = 1_000_000               # DMLC_HIST_BLOCKS parity size
    det_trees: int = 10
    auc_floor: float = AUC_FLOOR
    require_tpu: bool = True


class Smoke:
    """Report + pass/fail ledger of one run."""

    def __init__(self) -> None:
        self.report: Dict[str, Any] = {"smoke": True, "claim": None}
        self.failures: List[str] = []

    def say(self, msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            self.say(f"FAIL: {what}")
        return bool(ok)

    def phase(self, name: str, fn: Callable[[], Any]) -> Any:
        """Run one phase; an exception fails the phase (with the
        compiler's/runtime's own message) without hiding later ones."""
        self.say(f"--- {name}")
        t0 = time.perf_counter()
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — recorded, run fails
            traceback.print_exc()
            self.check(False, f"{name}: {type(e).__name__}: {e}"[:2000])
            return None
        finally:
            self.report.setdefault("phase_wall_s", {})[name] = round(
                time.perf_counter() - t0, 3)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def higgs_like(rows: int, features: int, holdout: int, seed: int = 7):
    """bench.py's HIGGS-shaped synthetic: dense gaussians and a
    nonlinear decision rule over the first five features.  The held-out
    rows continue the same stream."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def draw(n):
        X = rng.normal(size=(n, features)).astype(np.float32)
        margin = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2]
                  - 0.8 * X[:, 3] * (X[:, 4] > 0))
        return X, (margin > 0).astype(np.float32)

    X, y = draw(rows)
    Xh, yh = draw(holdout)
    return X, y, Xh, yh


def _metric(name: str, margin, y) -> float:
    """``logloss`` / ``auc`` by the library's own eval metrics."""
    import jax.numpy as jnp

    from dmlc_core_tpu.models.gbt_objectives import EVAL_METRICS

    return float(EVAL_METRICS[name][0](jnp.asarray(margin), jnp.asarray(y)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def fit_phase(sm: Smoke, cfg: SmokeConfig, mesh, X, y, Xh, yh, tag: str,
              on_ingest: Optional[Callable[[Dict[str, Any]], None]] = None):
    """fit -> what ran -> learning checks.  Returns (model, report,
    device-data handle or None).  ``on_ingest(handle)`` — the mesh
    phase's hook — is called between ingest and boosting."""
    import numpy as np

    from dmlc_core_tpu.base import compile_cache as cc
    from dmlc_core_tpu.models import HistGBT

    rep: Dict[str, Any] = {}
    mark = cc.marker()
    model = HistGBT(n_trees=cfg.n_trees, max_depth=cfg.max_depth,
                    n_bins=cfg.n_bins, hist_method=cfg.hist_method,
                    mesh=mesh)
    t0 = time.perf_counter()
    dd = None
    if on_ingest is not None:
        # make_device_data + fit_device IS fit() (which calls both);
        # taken apart only to look at the handle in between
        dd = model.make_device_data(X, y)
        on_ingest(dd)
        model.fit_device(dd)
    else:
        model.fit(X, y)
    rep["fit_wall_s"] = round(time.perf_counter() - t0, 3)
    hits, misses = cc.marker()
    plan = model.round_plan
    rep.update({
        "round_plan": plan,
        "dispatch": model.last_dispatch,
        "compile_cache": model.last_compile_cache,
        "cache_hits": hits - mark[0], "cache_misses": misses - mark[1],
        "warmup_breakdown": model.last_warmup_breakdown,
        "phase_s": {
            "cuts_bin": round(model.last_bin_seconds or 0.0, 3),
            "compile": (None if model.last_compile_seconds is None
                        else round(model.last_compile_seconds, 3)),
            "warmup_wait": round(model.last_warmup_seconds or 0.0, 3),
            "rounds": round(model.last_fit_seconds or 0.0, 3),
        },
        "chunk_times": [[d, round(t, 3)] for d, t in model.last_chunk_times],
    })
    sm.say(f"{tag}: round_plan={json.dumps(plan)}")
    sm.say(f"{tag}: dispatch={rep['dispatch']} "
           f"compile_cache={rep['compile_cache']} "
           f"phase_s={json.dumps(rep['phase_s'])} (smoke numbers)")
    sm.check(len(model.trees) == cfg.n_trees,
             f"{tag}: {len(model.trees)} trees, want {cfg.n_trees}")
    sm.check(all(m == "pallas" for m in plan["hist_method"]),
             f"{tag}: histogram method per level {plan['hist_method']} — "
             f"want pallas at every level")
    if cfg.require_tpu:
        sm.check(plan["pallas_interpret"] is False,
                 f"{tag}: Pallas kernels were INTERPRETED, not compiled")
    sm.check(rep["dispatch"] in ("aot", "jit"),
             f"{tag}: no dispatch record ({rep['dispatch']!r})")

    # learning: logloss over the first training rows at 0 / half / all
    # trees, AUC on the held-out rows (predict batches 2M rows a time)
    n_tr = min(len(X), cfg.holdout_rows)
    half = max(cfg.n_trees // 2, 1)
    ll = {0: _metric("logloss", np.zeros(n_tr, np.float32), y[:n_tr])}
    predict_s = {}

    def timed_predict(name, rows, **kw):
        t0 = time.perf_counter()
        out = model.predict(rows, output_margin=True, **kw)   # host array
        predict_s[name] = round(time.perf_counter() - t0, 3)
        return out

    for k in (half, cfg.n_trees):    # the first call carries the compile
        mg = timed_predict(f"train_rows_{k}_trees", X[:n_tr], n_trees=k)
        sm.check(mg.shape == (n_tr,) and bool(np.isfinite(mg).all()),
                 f"{tag}: predict({k} trees) shape {mg.shape} / non-finite")
        ll[k] = _metric("logloss", mg, y[:n_tr])
    mh = timed_predict("holdout_rows", Xh)
    rep["predict_s"] = {"rows_per_call": [n_tr, n_tr, len(Xh)], **predict_s}
    ll_h, auc_h = _metric("logloss", mh, yh), _metric("auc", mh, yh)
    rep.update({"train_logloss": {str(k): round(v, 5) for k, v in ll.items()},
                "holdout_logloss": round(ll_h, 5),
                "holdout_auc": round(auc_h, 5)})
    sm.say(f"{tag}: train logloss {rep['train_logloss']} "
           f"holdout logloss={ll_h:.5f} auc={auc_h:.5f}")
    sm.check(ll[0] > ll[half] > ll[cfg.n_trees],
             f"{tag}: training logloss does not fall: {rep['train_logloss']}")
    sm.check(mh.shape == (len(Xh),) and bool(np.isfinite(mh).all()),
             f"{tag}: holdout margins shape {mh.shape} / non-finite")
    sm.check(auc_h >= cfg.auc_floor,
             f"{tag}: holdout AUC {auc_h:.5f} < floor {cfg.auc_floor}")
    return model, rep, dd


def serve_phase(sm: Smoke, cfg: SmokeConfig, model, Xh) -> Dict[str, Any]:
    """The trained model behind ModelRunner across the bucket ladder,
    bit-compared with model.predict (row-wise, so one reference call
    over the longest slab serves every request)."""
    import numpy as np

    from dmlc_core_tpu.serve import ModelRunner

    runner = ModelRunner(model, max_batch=1024, min_bucket=8,
                         name="chip-smoke")
    n_ref = max(cfg.serve_sizes) + len(cfg.serve_sizes)
    ref = model.predict(Xh[:n_ref])
    equal = {}
    for i, k in enumerate(cfg.serve_sizes):
        got = runner.predict(Xh[i:i + k])      # a different window each
        equal[k] = bool(got.shape == (k,)
                        and np.array_equal(got, ref[i:i + k]))
        sm.check(equal[k], f"serve: request of {k} rows differs from "
                           f"model.predict")
    rep = {"request_sizes": list(cfg.serve_sizes),
           "buckets": sorted(runner.compiled_shapes),
           "bit_equal": all(equal.values())}
    sm.say(f"serve: {rep}")
    return rep


def _exact_gh(rng, n):
    """bf16-exact gradient/hessian draws (scripts/check_hist_kernel.py):
    every f32 partial sum is exact in any order, so kernels can be held
    to array_equal."""
    import numpy as np

    g = rng.choice(np.array([-1.0, -0.5, 0.5, 1.0], np.float32), size=n)
    h = rng.choice(np.array([0.5, 1.0], np.float32), size=n)
    return g, h


def _node_ids(rng, n, n_nodes):
    import numpy as np

    nid = rng.integers(0, n_nodes, size=n).astype(np.int32)
    nid[rng.random(n) < 0.1] = -1          # masked rows contribute nothing
    return nid


def rollcall_phase(sm: Smoke, cfg: SmokeConfig) -> Dict[str, Any]:
    """Every Pallas kernel of ops/histogram.py at the run's widths vs
    the segment engine: ``_hist_pallas`` plain and int4-packed."""
    import jax.numpy as jnp
    import numpy as np

    from dmlc_core_tpu.ops import binlayout as bl
    from dmlc_core_tpu.ops import histogram as H

    n, F, B = cfg.rollcall_rows, cfg.features, cfg.n_bins
    T = cfg.tile_rows or H._TILE_ROWS
    rng = np.random.default_rng(11)
    g, h = _exact_gh(rng, n)
    # plain matrix: every feature sweeps all B bins; packed matrix:
    # every third feature holds 2-6 SPREAD bin ids (packs to int4 after
    # the compact remap), the rest stay wide so sync_bins stays B
    plain = np.stack([(np.arange(n) * (2 * f + 1) + f) % B
                      for f in range(F)]).astype(np.uint8)
    narrow = plain.copy()
    for f in range(0, F, 3):
        ids = np.sort(rng.choice(B, size=2 + (f % 5), replace=False))
        narrow[f] = ids[rng.integers(0, len(ids), n)]
    layout = bl.compute_layout(bl.bin_counts(narrow, B), F, B, pack=True)
    assert layout is not None and layout.pairs, "packed layout must fire"
    phys = bl.pack_matrix(jnp.asarray(narrow), layout)
    plain_d, narrow_d = jnp.asarray(plain), jnp.asarray(narrow)
    g_d, h_d = jnp.asarray(g), jnp.asarray(h)
    rep: Dict[str, Any] = {
        "rows": n, "features": F, "n_bins": B, "tile_rows": T,
        "layout": f"{F}F->{layout.phys_rows}rows/{len(layout.pairs)}pairs",
        "interpret": H.pallas_interpret(), "kernels": {}}
    if cfg.require_tpu:
        sm.check(not H.pallas_interpret(),
                 "roll-call: kernels would be interpreted")

    def segment(bins_d, nid_d, n_nodes):
        return H.build_histogram(bins_d, nid_d, g_d, h_d, n_nodes, B,
                                 "segment", transposed=True)

    def case(name, fn):
        t0 = time.perf_counter()
        try:
            ok = bool(fn())
        except Exception as e:  # noqa: BLE001 — the compiler's message
            traceback.print_exc()
            err = f"{type(e).__name__}: {e}"[:2000]
            rep["kernels"][name] = {"ok": False, "error": err}
            sm.check(False, f"roll-call: {name}: {err}")
            return
        wall = time.perf_counter() - t0
        rep["kernels"][name] = {"ok": ok, "wall_s": round(wall, 2)}
        sm.check(ok, f"roll-call: {name} != segment")
        sm.say(f"roll-call: {name}: {'ok' if ok else 'MISMATCH'} "
               f"({wall:.1f}s incl. compile, smoke)")

    for nn in cfg.rollcall_nodes:
        nid_d = jnp.asarray(_node_ids(rng, n, nn))
        ref_plain = np.asarray(segment(plain_d, nid_d, nn))
        ref_narrow = np.asarray(segment(narrow_d, nid_d, nn))

        def hist_plain():
            got = H._hist_pallas(plain_d, nid_d, g_d, h_d, nn, B, T, 0,
                                 True, None)
            return np.array_equal(np.asarray(got), ref_plain)

        def hist_packed():
            st = H._hist_pallas(phys, nid_d, g_d, h_d, nn,
                                layout.sync_bins, T, 0, True, layout)
            got = bl.unbundle_hist(st, layout, B)
            return np.array_equal(np.asarray(got), ref_narrow)

        case(f"_hist_pallas[n_nodes={nn}]", hist_plain)
        case(f"_hist_pallas+int4[n_nodes={nn}]", hist_packed)

    rep["all_ok"] = all(k["ok"] for k in rep["kernels"].values())
    return rep


def _bytes_in_use(devices) -> Optional[List[int]]:
    """The allocator's ``bytes_in_use`` per device (None where the
    backend reports none, e.g. CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(not s or "bytes_in_use" not in s for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def mesh_phase(sm: Smoke, cfg: SmokeConfig, X, y, Xh, yh,
               auc_one: Optional[float]) -> Dict[str, Any]:
    """The same fit over every device of this process."""
    import jax

    from dmlc_core_tpu.parallel.mesh import local_mesh

    mesh = local_mesh()
    devs = list(mesh.devices.flat)
    # compiled single-device programs (cuts, predict, metrics, the
    # roll-call) live in device 0's memory — 64 MB of them after the
    # one-device phases on the first four-chip run.  Drop them before
    # each snapshot, so the deltas read this fit's data and not code
    jax.clear_caches()
    gc.collect()
    mem = {"before": _bytes_in_use(devs)}

    def on_ingest(dd):
        jax.block_until_ready([dd["bins_t"], dd["y_d"], dd["w_d"]])
        mem["after_ingest"] = _bytes_in_use(devs)

    model, rep, dd = fit_phase(sm, cfg, mesh, X, y, Xh, yh,
                               tag=f"{len(devs)}-device",
                               on_ingest=on_ingest)
    bins_t = dd["bins_t"]
    shard_devs = {s.device for s in bins_t.addressable_shards}
    shard_shapes = {tuple(s.data.shape) for s in bins_t.addressable_shards}
    rep["bins_t_shard_devices"] = len(shard_devs)
    rep["bins_t_shard_shapes"] = sorted(shard_shapes)
    sm.check(shard_devs == set(devs) and len(shard_shapes) == 1,
             f"sharded ingest: bins_t lives on {len(shard_devs)} of "
             f"{len(devs)} devices, shard shapes {sorted(shard_shapes)}")
    # the model and its handle are still alive: what each device holds
    # now is its share of the training state
    jax.clear_caches()
    gc.collect()
    mem["after_fit"] = _bytes_in_use(devs)
    rep["bytes_in_use"] = mem
    if mem["after_fit"] is None:
        sm.check(not cfg.require_tpu,
                 "sharded ingest: device.memory_stats() has no "
                 "bytes_in_use")
    else:
        added = [a - b for a, b in zip(mem["after_fit"], mem["before"])]
        spread = (max(added) - min(added)) / max(max(added), 1)
        rep["bytes_in_use_added"] = added
        sm.say(f"sharded ingest: bytes_in_use added per device {added} "
               f"(spread {spread:.2%})")
        sm.check(min(added) > 0 and spread <= SHARD_BYTES_TOL,
                 f"sharded ingest: bytes_in_use per device {added} differ "
                 f"by {spread:.1%} (> {SHARD_BYTES_TOL:.0%})")
    if auc_one is not None and "holdout_auc" in rep:
        d = abs(rep["holdout_auc"] - auc_one)
        rep["auc_delta_vs_one_device"] = round(d, 6)
        sm.check(d <= AUC_MESH_TOL,
                 f"mesh AUC {rep['holdout_auc']} vs one-device "
                 f"{auc_one}: |Δ|={d:.5f} > {AUC_MESH_TOL}")
    del model, dd, bins_t
    gc.collect()
    return rep


def det_parity_phase(sm: Smoke, cfg: SmokeConfig, X, y) -> Dict[str, Any]:
    """DMLC_HIST_BLOCKS (the mesh-shape-invariant reduction): a fit on
    all devices must serialize byte-identically to a one-device fit."""
    import jax

    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    ndev = len(jax.devices())
    n = min(cfg.det_rows, len(X))
    blocks = max(8, ndev)
    prev = os.environ.get("DMLC_HIST_BLOCKS")
    os.environ["DMLC_HIST_BLOCKS"] = str(blocks)
    try:
        blobs = {}
        with tempfile.TemporaryDirectory() as tmp:
            for width in (1, ndev):
                m = HistGBT(n_trees=cfg.det_trees, max_depth=cfg.max_depth,
                            n_bins=cfg.n_bins, hist_method=cfg.hist_method,
                            mesh=local_mesh(width))
                m.fit(X[:n], y[:n])
                sm.check(m.round_plan["hist_blocks"] > 0,
                         f"DMLC_HIST_BLOCKS ignored on {width} device(s)")
                path = os.path.join(tmp, f"w{width}.gbt")
                m.save_model(path)
                with open(path, "rb") as f:
                    blobs[width] = f.read()
    finally:
        if prev is None:
            del os.environ["DMLC_HIST_BLOCKS"]
        else:
            os.environ["DMLC_HIST_BLOCKS"] = prev
    same = blobs[1] == blobs[ndev]
    sm.check(same, f"DMLC_HIST_BLOCKS={blocks}: save_model bytes on "
                   f"{ndev} devices differ from one device ({n} rows)")
    rep = {"rows": n, "blocks": blocks, "devices": ndev,
           "trees": cfg.det_trees, "model_bytes": len(blobs[1]),
           "byte_identical": same}
    sm.say(f"hist-blocks parity: {rep}")
    return rep


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def describe_runtime() -> Dict[str, Any]:
    import jax
    import jaxlib

    from dmlc_core_tpu.base import compile_cache as cc
    from dmlc_core_tpu.data import _native

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — absent off the chip image
        libtpu = None
    devs = jax.devices()
    cc.configure()
    return {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu, "python": sys.version.split()[0]},
        "native_available": _native.native_available(),
        "compile_cache_dir": cc.cache_dir(),
        "compile_cache_dir_from_env":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "dmlc_env": sorted(k for k in os.environ if k.startswith("DMLC_")),
    }


def run_smoke(cfg: SmokeConfig) -> Smoke:
    """The body: every phase, in one process.  Returns the ledger;
    ``sm.failures`` empty means the smoke passed."""
    import jax

    from dmlc_core_tpu.base import compile_cache as cc
    from dmlc_core_tpu.parallel.mesh import local_mesh

    sm = Smoke()
    sm.report["config"] = dataclasses.asdict(cfg)
    sm.report["runtime"] = describe_runtime()
    sm.say(f"runtime: {json.dumps(sm.report['runtime'])}")
    if cfg.require_tpu:
        sm.check(jax.default_backend() == "tpu", "backend is not tpu")
        sm.check(sm.report["runtime"]["dmlc_env"] == [],
                 f"DMLC_* set in the environment: "
                 f"{sm.report['runtime']['dmlc_env']} — the smoke runs "
                 f"library defaults")

    data = sm.phase("datagen", lambda: higgs_like(
        cfg.rows, cfg.features, cfg.holdout_rows))
    if data is None:
        return sm
    X, y, Xh, yh = data

    one = sm.phase("fit_one_device", lambda: fit_phase(
        sm, cfg, local_mesh(1), X, y, Xh, yh, tag="1-device"))
    auc_one = None
    if one is not None:
        model, rep, _ = one
        sm.report["one_device"] = rep
        auc_one = rep.get("holdout_auc")
        sm.report["serve"] = sm.phase(
            "serve", lambda: serve_phase(sm, cfg, model, Xh))
        del model, one
    gc.collect()

    sm.report["rollcall"] = sm.phase(
        "kernel_rollcall", lambda: rollcall_phase(sm, cfg))
    gc.collect()

    if len(jax.devices()) > 1:
        sm.report["all_devices"] = sm.phase(
            "fit_all_devices", lambda: mesh_phase(
                sm, cfg, X, y, Xh, yh, auc_one))
        sm.report["hist_blocks_parity"] = sm.phase(
            "hist_blocks_parity", lambda: det_parity_phase(sm, cfg, X, y))

    stats = cc.stats()
    sm.report["compile_cache"] = stats
    sm.say(f"compile cache: dir={stats['dir']} hits={stats['hits']} "
           f"misses={stats['misses']}")
    sm.say(f"phase wall (smoke numbers, one run, compile included): "
           f"{json.dumps(sm.report['phase_wall_s'])}")
    sm.report["ok"] = not sm.failures
    sm.report["failures"] = sm.failures
    return sm


def _write_report(report: Dict[str, Any]) -> None:
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
    except OSError as e:
        print(f"[smoke] report not written: {e}", file=sys.stderr)


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        # never a CPU run: nothing is imported from the repo, nothing
        # trains, no result line is printed
        print(f"chip_smoke: jax.default_backend() is {backend!r}, not "
              f"'tpu' ({len(jax.devices())} x "
              f"{jax.devices()[0].device_kind}) — refusing to run",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    sm = run_smoke(SmokeConfig())
    sm.report["total_wall_s"] = round(time.perf_counter() - t0, 1)
    _write_report(sm.report)
    print("[smoke] report: " + json.dumps(sm.report, default=str),
          flush=True)
    if sm.failures:
        print(f"chip_smoke: {len(sm.failures)} check(s) failed:",
              file=sys.stderr)
        for f in sm.failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": sm.report["runtime"]["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
