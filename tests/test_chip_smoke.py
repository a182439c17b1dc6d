"""chip_smoke.py cannot rot between chip runs: its body runs here at a
tiny size on the virtual CPU mesh with the Pallas kernels interpreted,
its entry point must refuse any backend but ``tpu``, and the no-quiet-
fallback rule it relies on (explicit ``pallas`` never becomes another
engine) is pinned next to it."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

from dmlc_core_tpu.base.logging import Error  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops.histogram import (_pallas_ok,  # noqa: E402
                                         build_histogram,
                                         resolve_hist_method)
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

#: the flagship's shape of run, cut to what the interpreter can carry:
#: explicit pallas stands in for what "auto" picks on the chip, so the
#: same kernels (and the same checks) are live
TINY = chip_smoke.SmokeConfig(
    rows=4096, features=8, n_trees=4, max_depth=3, n_bins=32,
    holdout_rows=1200, hist_method="pallas",
    serve_sizes=(1, 8, 9, 100, 1024, 1100),
    rollcall_rows=700, rollcall_nodes=(1, 4),
    tile_rows=256, det_rows=1024, det_trees=2,
    auc_floor=0.75, require_tpu=False)


def test_body_runs_every_phase_on_the_virtual_mesh(monkeypatch):
    monkeypatch.setenv("DMLC_TPU_ROUNDS_PER_DISPATCH", "2")  # 2 dispatches
    sm = chip_smoke.run_smoke(TINY)
    assert sm.failures == [], sm.failures
    rep = sm.report
    assert rep["ok"] is True and rep["claim"] is None
    assert rep["runtime"]["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}
    one = rep["one_device"]
    assert one["round_plan"]["hist_method"] == ["pallas"] * TINY.max_depth
    assert one["round_plan"]["pallas_interpret"] is True      # CPU here
    assert one["dispatch"] in ("aot", "jit")
    assert [d for d, _ in one["chunk_times"]] == [2, 4]
    assert rep["serve"]["bit_equal"] is True
    assert rep["serve"]["buckets"] == [8, 16, 128, 1024]
    kernels = rep["rollcall"]["kernels"]
    assert set(kernels) == {
        "_hist_pallas[n_nodes=1]", "_hist_pallas+int4[n_nodes=1]",
        "_hist_pallas[n_nodes=4]", "_hist_pallas+int4[n_nodes=4]"}
    assert all(k["ok"] for k in kernels.values())
    # more than one device here, so the mesh phases ran too
    mesh = rep["all_devices"]
    assert mesh["round_plan"]["mesh_devices"] == len(jax.devices())
    assert mesh["bins_t_shard_devices"] == len(jax.devices())
    assert rep["hist_blocks_parity"]["byte_identical"] is True
    # the report is what main() writes out: it must serialize
    json.dumps(rep, default=str)


def test_entry_point_refuses_without_a_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert out.out == ""                       # no result line, nothing ran
    assert "refusing to run" in out.err


def test_failed_check_fails_the_run():
    sm = chip_smoke.Smoke()
    assert sm.check(True, "fine") and sm.failures == []
    assert sm.phase("boom", lambda: 1 / 0) is None
    assert len(sm.failures) == 1 and "ZeroDivisionError" in sm.failures[0]


def test_explicit_pallas_on_ineligible_shape_raises(monkeypatch):
    # the VMEM gate rejects this accumulator at even ONE node; "auto" may
    # pick another engine there, an explicit request must not be
    # rewritten.  (A build of many nodes is no longer such a shape: it
    # runs in node blocks, ISSUE 37.)
    n_bins, F, n_nodes = 1 << 17, 512, 4
    assert not _pallas_ok(n_bins, F, 1)
    with pytest.raises(Error, match="method='pallas' was requested"):
        resolve_hist_method("pallas", n_bins, F, n_nodes)
    n = 64
    with pytest.raises(Error, match="method='pallas' was requested"):
        build_histogram(jnp.zeros((F, n), jnp.int32),
                        jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.float32),
                        jnp.ones(n, jnp.float32), n_nodes, n_bins, "pallas",
                        transposed=True)
    assert resolve_hist_method("auto", n_bins, F, n_nodes) == "segment"
    # and the model says so before anything traces or compiles
    # (the model's n_bins stops at 256: a scoped-VMEM figure that holds
    # no bins block at all refuses every build there)
    from dmlc_core_tpu.ops import histogram as H
    m = HistGBT(n_trees=1, max_depth=3, n_bins=256, hist_method="pallas",
                mesh=local_mesh(1))
    with monkeypatch.context() as mp:
        mp.setattr(H, "_SCOPED_VMEM", H._TILE_ROWS * H._SCOPED_ROW_RESERVE)
        with pytest.raises(Error, match="method='pallas' was requested"):
            m._round_plan(512)
    # 64 nodes at 256 bins, once refused: one call does not take them,
    # two node blocks do
    assert not _pallas_ok(256, 512, 64)
    assert resolve_hist_method("pallas", 256, 512, 64) == "pallas"
    deep = HistGBT(n_trees=1, max_depth=8, n_bins=256, hist_method="pallas",
                   mesh=local_mesh(1))
    assert deep._round_plan(512).hist_method == ("pallas",) * 8
    assert deep.round_plan["hist_node_blocks"][-1] == [32, 32]
    assert np.all([v == "pallas" for v in HistGBT(
        n_trees=1, max_depth=6, n_bins=256, hist_method="pallas",
        mesh=local_mesh(1))._round_plan(28).hist_method])
