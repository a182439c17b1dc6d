"""The round program is chosen in ONE place: ``HistGBT._round_plan``.

* the process-wide program cache is keyed on the param and the plan, so
  every lever that stays moves the key exactly when it moves the plan;
* once the plan is resolved, neither the key, the build nor the traced
  closure reads the environment again;
* the levers that lost on the chip (ISSUEs 33 and 47) are gone from
  the knob registry, the configuration page and the package, and so are
  the second round kernel and the third histogram engine.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from dmlc_core_tpu.base import knobs  # noqa: E402
from dmlc_core_tpu.base.logging import Error  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as hg  # noqa: E402
from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops import histogram as H  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F = 6
KW = dict(n_trees=4, max_depth=3, n_bins=32)


def _packed_layout():
    """A nibble-packed layout of F features: four narrow, two wide."""
    rng = np.random.default_rng(0)
    bins_t = rng.integers(0, KW["n_bins"], size=(F, 256)).astype(np.uint8)
    bins_t[:4] %= 5
    lay = bl.compute_layout(bl.bin_counts(bins_t, KW["n_bins"]), F,
                            KW["n_bins"], pack=True)
    assert lay is not None and lay.pairs
    return lay


def _model(env, monkeypatch, layout=None, **kw):
    """A model and its plan, resolved under ``env``; the environment is
    back to what it was before the key or the build is asked for."""
    with monkeypatch.context() as mp:
        for k, v in env.items():
            mp.setenv(k, v)
        m = HistGBT(mesh=local_mesh(1), **dict(KW, **kw))
        m._bin_layout = layout
        return m, m._round_plan(F)


#: lever -> (what the two sides of it are, which public key shows it)
_LEVERS = {
    "DMLC_HIST_BLOCKS": (dict(env={}), dict(env={"DMLC_HIST_BLOCKS": "2"}),
                         "hist_blocks"),
    # (two hyperparameters on the Parameter since ISSUE 56)
    "grow_policy": (dict(env={}),
                         dict(env={}, grow_policy="lossguide"),
                         "grow_policy"),
    "max_leaves": (
        dict(env={}, grow_policy="lossguide"),
        dict(env={}, grow_policy="lossguide", max_leaves=4),
        "max_leaves"),
    "packed_layout": (dict(env={}), dict(env={}, layout="packed"),
                      "bin_layout"),
    "hist_method": (dict(env={}, hist_method="segment"),
                    dict(env={}, hist_method="pallas"), "hist_method"),
}


@pytest.mark.parametrize("lever", sorted(_LEVERS))
def test_cache_key_follows_the_plan(lever, monkeypatch):
    side_a, side_b, shown = _LEVERS[lever]

    def make(side):
        side = dict(side)
        if side.get("layout") == "packed":
            side["layout"] = _packed_layout()
        return _model(side.pop("env"), monkeypatch, **side)

    (a, plan_a), (a2, plan_a2), (b, plan_b) = \
        make(side_a), make(side_a), make(side_b)
    # two models that differ in the lever: other plan, other key
    assert plan_a != plan_b
    assert a._round_fn_cache_key(plan_a, 2) != b._round_fn_cache_key(plan_b, 2)
    if shown is not None:
        assert a.round_plan[shown] != b.round_plan[shown]
    # two that do not: one plan, one key, ONE cached program
    assert plan_a == plan_a2 and a.round_plan == a2.round_plan
    key = a._round_fn_cache_key(plan_a, 2)
    assert key == a2._round_fn_cache_key(plan_a2, 2)
    hg._ROUND_FN_CACHE.pop(key, None)
    before = len(hg._ROUND_FN_CACHE)
    fn = a._build_round_fn(plan_a, 2)
    assert a2._build_round_fn(plan_a2, 2) is fn
    assert hg._ROUND_FN_CACHE[key] is fn
    assert len(hg._ROUND_FN_CACHE) == before + 1
    # and a lever set AFTER the plan was resolved moves nothing
    monkeypatch.setenv("DMLC_HIST_BLOCKS", "4")
    assert a._round_fn_cache_key(plan_a, 2) == key
    assert a._build_round_fn(plan_a, 2) is fn


@pytest.mark.parametrize("n_features", [28, 136])
@pytest.mark.parametrize("depth", [2, 3, 4, 5, 6, 7])
def test_auto_plans_the_staged_round_on_a_tpu(depth, n_features,
                                              monkeypatch):
    """With no ``DMLC_*`` variable set, a one-chip dense fit on a TPU
    plans the staged round — ``dmlc_hist`` at every level, one kernel
    call a build — at HIGGS's and MSLR's width and any depth up to 7."""
    for name in [k for k in os.environ if k.startswith("DMLC_")]:
        monkeypatch.delenv(name)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=depth, n_bins=256)
    m._round_plan(n_features)
    record = m.round_plan
    assert record["hist_method"] == ["pallas"] * depth
    builds = [1] + [1 << (lv - 1) for lv in range(1, depth)]
    assert record["hist_node_blocks"] == [[nb] for nb in builds]
    assert record["hist_feature_blocks"] == [[n_features]] * depth


class _NoLevers(dict):
    """``os.environ`` with every ``DMLC_*`` name unreadable."""

    @staticmethod
    def _guard(key):
        assert not str(key).startswith("DMLC_"), \
            f"{key} read after _round_plan returned"

    def __getitem__(self, key):
        self._guard(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self._guard(key)
        return super().get(key, default)

    def __contains__(self, key):
        self._guard(key)
        return super().__contains__(key)


@pytest.mark.parametrize("policy", ["depthwise", "lossguide"])
def test_no_environment_read_once_the_plan_is_resolved(policy, monkeypatch):
    m, plan = _model({}, monkeypatch, grow_policy=policy, max_leaves=5)
    assert plan.grow_policy == policy
    assert plan.max_leaves == (5 if policy == "lossguide" else 0)
    key_before = m._round_fn_cache_key(plan, 2)
    hg._ROUND_FN_CACHE.pop(key_before, None)        # a real build below
    monkeypatch.setattr(os, "environ", _NoLevers(os.environ))
    with pytest.raises(AssertionError, match="DMLC_HIST_BLOCKS"):
        m._round_plan(F)                            # the guard does bite
    for helper in ("_hist_blocks", "get_env"):
        def refuse(*a, _h=helper, **k):
            raise AssertionError(f"{_h} called after _round_plan returned")
        monkeypatch.setattr(hg, helper, refuse)
    assert m._round_fn_cache_key(plan, 2) == key_before
    fn = m._build_round_fn(plan, 2)
    # ... and neither does the closure when the program is traced
    mesh, n = m.mesh, 64
    row = NamedSharding(mesh, P("data"))
    lowered = fn.lower(
        jax.ShapeDtypeStruct((F, n), np.uint8,
                             sharding=NamedSharding(mesh, P(None, "data"))),
        jax.ShapeDtypeStruct((n,), np.float32, sharding=row),
        jax.ShapeDtypeStruct((n,), np.float32, sharding=row),
        jax.ShapeDtypeStruct((n,), np.float32, sharding=row))
    assert "dmlc.round" in lowered.as_text(debug_info=True)


def test_round_plan_record_is_the_plans_json_view():
    import json

    m = HistGBT(mesh=local_mesh(1), **KW)
    plan = m._round_plan(F)
    assert m.round_plan == plan.describe()
    assert json.loads(json.dumps(m.round_plan)) == m.round_plan
    assert set(m.round_plan) == {
        "hist_method", "missing", "pallas_interpret", "grow_policy",
        "bin_layout", "hist_features", "hist_feature_blocks",
        "hist_node_blocks", "hist_class_blocks", "route_lookups",
        "hist_blocks", "mesh_devices",
        "num_class", "trees_per_round", "margin_layout"}
    # a loss-guide plan says its leaves, expansions and rows a build too
    lg = HistGBT(mesh=local_mesh(1), grow_policy="lossguide", max_leaves=5,
                 **KW)
    lg_plan = lg._round_plan(F, 640)
    # (the rows a build is handed are a record of the shapes: the plan,
    # hence the program's key, is the same at every row count)
    assert lg_plan == lg._round_plan(F, 1280)
    lg._round_plan(F, 640)
    assert set(lg.round_plan) - set(m.round_plan) == {
        "max_leaves", "expansions", "hist_rows_per_build", "recluster_at"}
    # (before a fit the rows a build is handed are the bound: all of
    # them; a tree this small never re-clusters its rows, ISSUE 57)
    assert (lg.round_plan["max_leaves"], lg.round_plan["expansions"],
            lg.round_plan["hist_rows_per_build"],
            lg.round_plan["recluster_at"]) == (5, 4, 640, [])
    # a plan made without rows is a small fit's: route's unpacked tables
    assert m.round_plan["route_lookups"] == {
        "form": "pieces", "packed": False, "chained_entries": 0}
    assert (m.round_plan["num_class"], m.round_plan["trees_per_round"],
            m.round_plan["margin_layout"]) == (1, 1, "[n]")
    assert m.round_plan["missing"] is False
    # a record of what the Pallas kernels issue per row tile (dots
    # emitted, dots of the padded block): derived, never a field
    assert m.round_plan["hist_features"] == [F, 8]
    assert "hist_features" not in hg._RoundPlan._fields
    assert "fused_" + "round" not in hg._RoundPlan._fields
    assert m.round_plan["hist_method"] == ["segment"] * KW["max_depth"]
    # feature blocks are the Pallas builds': none for another engine
    assert m.round_plan["hist_feature_blocks"] == [[]] * KW["max_depth"]
    assert m.round_plan["hist_node_blocks"] == [[]] * KW["max_depth"]
    assert hash(plan) == hash(m._round_plan(F))


_DELETED = ["DMLC_TPU_FUSED_" + "DESCEND", "DMLC_HIST_" + "QUANT",
            "DMLC_COLDSTART_" + "OVERLAP", "DMLC_SHARDED_" + "INGEST",
            "DMLC_WARMUP_" + "EXEC", "DMLC_FUSED_" + "ROUND",
            # hyperparameters, on the Parameter since ISSUE 56
            "DMLC_GROW_" + "POLICY", "DMLC_MAX_" + "LEAVES"]


@pytest.mark.parametrize("name", _DELETED)
def test_deleted_lever_is_gone(name, monkeypatch):
    assert name not in knobs.names()
    assert len(knobs.names()) == 97
    # set, it is any undeclared name: the plan and the key do not move
    m, plan = _model({}, monkeypatch, hist_method="pallas")
    m1, plan1 = _model({name: "1"}, monkeypatch, hist_method="pallas")
    assert plan1 == plan and m1.round_plan == m.round_plan
    assert m1._round_fn_cache_key(plan1, 2) == m._round_fn_cache_key(plan, 2)
    with open(os.path.join(_REPO, "doc", "configuration.md")) as f:
        assert name not in f.read()
    hits = []
    for d, _, files in os.walk(os.path.join(_REPO, "dmlc_core_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(d, fn), errors="replace") as f:
                    if name in f.read():
                        hits.append(os.path.relpath(os.path.join(d, fn),
                                                    _REPO))
    assert hits == []


#: the one-kernel level, its VMEM gate and the plain-XLA MXU engine
#: (ISSUE 47): git has them, the package does not
@pytest.mark.parametrize("name", ["fused_" + "round", "fused_" + "round_ok",
                                  "_hist_" + "matmul"])
def test_deleted_kernel_is_gone(name):
    assert not hasattr(H, name) and name not in H.__all__


def test_the_deleted_engine_is_refused_by_the_enum():
    assert H.histogram_methods() == ["auto", "segment", "pallas"]
    with pytest.raises(Error, match="'hist_method'.*not in allowed set"):
        HistGBT(mesh=local_mesh(1), hist_method="mat" + "mul", **KW)


def test_the_deleted_engine_is_an_unknown_method():
    z = np.zeros(8, np.float32)
    with pytest.raises(Error, match="unknown method"):
        H.build_histogram(np.zeros((8, 2), np.uint8), z.astype(np.int32),
                          z, z, 1, 4, "mat" + "mul")
