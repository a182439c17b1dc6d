"""ISSUE 42: node blocks AROUND feature blocks, on the chip's compiler
without the chip.  The last level of a depth-8 tree on a matrix wider
than one feature block builds its 64 nodes in two node blocks of 32,
each in five feature blocks (968 features): ten kernel calls.  Each call
alone fits the compiler's 16 MiB of scoped VMEM; inside some round
programs the v5e compiler charged the very same calls 16.5-19.1 MiB and
refused the program — the one-round program of the missing-value
deployment (what ``make_device_data`` compiles in the background for a
model of one tree), the dense 25-round program — while the 25-round
program under ``missing`` compiled and ran.  The calls of a build cut
both ways therefore state their own limit (``_NESTED_BLOCKS_VMEM``);
since PR 51 every call of a build cut on features does.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.ops import histogram as H

from test_hist_feature_blocks import _budget
from test_hist_node_blocks import _cap


@pytest.fixture(scope="module")
def chip_mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices[:1]), ("data",))


def test_the_one_round_program_of_the_wide_deep_table_compiles(
        chip_mesh, monkeypatch):
    """The program the parent's gate had refused by the compiler: one
    round at depth 8 over 968 feature rows, under ``missing``."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(H, "pallas_interpret", lambda: False)
    monkeypatch.setattr(G, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(G, "_ROUND_FN_CACHE", {})
    n, F = 4 * H._TILE_ROWS, 968
    model = HistGBT(n_trees=1, mesh=chip_mesh, max_depth=8, n_bins=256,
                    learning_rate=0.1, objective="binary:logistic")
    model._missing = True
    plan = model._round_plan(F)
    assert model.round_plan["hist_node_blocks"][-1] == [32, 32]
    assert model.round_plan["hist_feature_blocks"][-1] == \
        [200, 200, 200, 200, 168]
    mat = NamedSharding(chip_mesh, P(None, "data"))
    row = NamedSharding(chip_mesh, P("data"))
    args = (jax.ShapeDtypeStruct((F, n), np.uint8, sharding=mat),) + tuple(
        jax.ShapeDtypeStruct((n,), np.float32, sharding=row)
        for _ in range(3))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = model._build_round_fn(plan, 1).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    # six levels of three feature blocks, L6 of five, L7 of two times five
    assert text.count("tpu_custom_call") >= 6 * 3 + 5 + 10
    assert "dmlc.hist.nblock" in text and "dmlc.hist.fblock" in text


def _limits(jaxpr):
    """``vmem_limit_bytes`` of every ``pallas_call`` of a jaxpr (None
    where the call states none), in trace order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            cp = dict(eqn.params.get("compiler_params") or {})
            out.append(getattr(cp.get("mosaic_tpu"), "vmem_limit_bytes",
                               None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_limits(sub))
    return out


def test_every_call_of_a_build_cut_on_features_states_a_limit(monkeypatch):
    """The limit rides on every call of a build cut on features, with
    node blocks around the feature blocks or without (since PR 51: the
    8 builds of a level 4 on a 392-row block were refused at 18.75 MiB
    with no node block in sight), and on no other call: a build of one
    feature block (one call a build; node blocks alone) traces as it
    did."""
    rng = np.random.default_rng(0)
    n, n_bins = 700, 64

    def limits(F, n_nodes):
        args = (jnp.asarray(rng.integers(0, n_bins, (F, n)).astype(np.uint8)),
                jnp.asarray(rng.integers(0, n_nodes, n).astype(np.int32)),
                jnp.asarray(rng.normal(size=n).astype(np.float32)),
                jnp.ones(n, jnp.float32))
        return _limits(jax.make_jaxpr(lambda *a: H.build_histogram(
            *a, n_nodes, n_bins, "pallas", transposed=True))(*args).jaxpr)

    assert limits(44, 16) == [None]               # one call a build
    _cap(monkeypatch, 4)
    assert limits(12, 16) == [None] * 4           # node blocks alone
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    assert limits(44, 4) == [H._NESTED_BLOCKS_VMEM] * 3   # feature blocks
    assert limits(44, 16) == [H._NESTED_BLOCKS_VMEM] * 12
    assert H._NESTED_BLOCKS_VMEM > 16 << 20


@pytest.mark.parametrize("rows,points", [
    (4 * H._TILE_ROWS + 640, []),     # few rows: today's one scan
    (24_000_000, [8]),                # the leaf-wise cell's: clustered
])
def test_the_leaf_wise_round_program_compiles(chip_mesh, monkeypatch, rows,
                                              points):
    """ISSUE 56: what no TPU compiler had seen — a scan of 254 expansions
    with the 255-slot histogram pool in its carry, a ``dynamic_slice`` of
    one feature's row of the ``[F, n]`` bins an expansion, the node list's
    one lookup of 509 entries — at the leaf-wise cell's width and budget
    (the rows cut: they change no program but its sizes).  ISSUE 57: at
    the cell's own 24M rows the plan CLUSTERS the rows — the operands
    padded once, two scans around the re-cluster's sort, every build the
    scalar-prefetch kernel — and that program compiles and fits the
    chip too."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(H, "pallas_interpret", lambda: False)
    monkeypatch.setattr(G, "pallas_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(G, "_ROUND_FN_CACHE", {})
    n, F = rows, 28                          # not a multiple of the tile
    model = HistGBT(n_trees=5, mesh=chip_mesh, grow_policy="lossguide",
                    max_leaves=255, max_depth=0, n_bins=256,
                    learning_rate=0.1, min_child_weight=100.0)
    plan = model._round_plan(F, n)
    assert model.round_plan["hist_method"] == ["pallas"]
    # (rows a build is handed: before a fit the bound, all of them)
    assert (model.round_plan["max_leaves"], model.round_plan["expansions"],
            model.round_plan["hist_rows_per_build"],
            model.round_plan["recluster_at"]) == (255, 254, n, points)
    mat = NamedSharding(chip_mesh, P(None, "data"))
    row = NamedSharding(chip_mesh, P("data"))
    args = (jax.ShapeDtypeStruct((F, n), np.uint8, sharding=mat),) + tuple(
        jax.ShapeDtypeStruct((n,), np.float32, sharding=row)
        for _ in range(3))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = model._build_round_fn(plan, 5).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    text = compiled.as_text()
    # the kernel calls of the program: the root's and one a scan
    assert text.count("tpu_custom_call") >= 2 + len(points)
    for scope in ("dmlc.round.root", "dmlc.round.expand.pick",
                  "dmlc.round.expand.hist", "dmlc.round.expand.settle"):
        assert scope in text, scope
    # five trees of 509 entries come back
    assert "s32[5,509]" in text and "f32[5,509]" in text
    if points:
        # the re-cluster and the way back sort; nothing pads the matrix
        # inside a scan (the one pad of the tree is the root's)
        assert "dmlc.round.expand.recluster" in text
        assert text.count(" sort(") >= 2
        assert "dmlc.round.root/dmlc.hist.pad/jit(_pad)/pad" in text
        assert not re.search(
            r'dmlc\.round\.expand\.hist/[^"]*dmlc\.hist\.pad/jit\(_pad\)', text)
        mem = compiled.memory_analysis()
        # the sorted copies and the sort's temporaries beside the handle:
        # well inside a 16 GB chip
        assert mem.temp_size_in_bytes < 8 << 30
