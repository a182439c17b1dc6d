"""The seam to the system under test: the only module of the benchmark
that imports ``dmlc_core_tpu``.  It calls the entry points a user calls —
``HistGBT``, ``make_device_data``, ``fit_device``, ``predict`` — with the
configuration's parameters and nothing else: no ``cuts=``, no host
binning, no ``DMLC_*`` variable."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from benchmark import datagen

#: parameters of a configuration file that go to ``HistGBT`` as they are
MODEL_KEYS = ("max_depth", "n_bins", "learning_rate", "reg_lambda",
              "min_child_weight", "objective", "base_score")


def compile_events() -> Dict[str, int]:
    """Persistent-cache hits and misses of this process so far: every
    program a process compiles or reads back is one or the other."""
    from dmlc_core_tpu.base import compile_cache

    st = compile_cache.stats()
    return {"hits": int(st["hits"]), "misses": int(st["misses"])}


def new_model(ctx, n_trees: int):
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    kw = {k: ctx.config[k] for k in MODEL_KEYS if k in ctx.config}
    return HistGBT(n_trees=int(n_trees), mesh=local_mesh(ctx.chips), **kw)


def training_rows(ctx) -> Tuple[np.ndarray, np.ndarray]:
    return datagen.higgs_like(int(ctx.config["rows"]),
                              int(ctx.config["features"]), ctx.seed, stream=0)


def heldout_rows(ctx, rows: int) -> Tuple[np.ndarray, np.ndarray]:
    return datagen.higgs_like(int(rows), int(ctx.config["features"]),
                              ctx.seed, stream=1)


def ingest(model, X: np.ndarray, y: np.ndarray) -> Dict[str, Any]:
    """``make_device_data`` as a user calls it, waited for: binning is
    asynchronous, so the handle counts as made only when every array of
    it is ready."""
    import jax

    handle = model.make_device_data(X, y)
    jax.block_until_ready([v for v in handle.values()
                           if isinstance(v, jax.Array)])
    return handle


def join_background(model) -> None:
    """Wait for the round-program compile that ``make_device_data`` starts
    in the background (``fit_device`` joins it; a model that never fits
    would leave its threads to the interpreter's exit)."""
    pending = getattr(model, "_pending_warmup", None)
    if pending is not None:
        pending.join()
        model._pending_warmup = None


def drop_handle(handle: Dict[str, Any]) -> None:
    """Free a handle's device arrays now, not at the next collection."""
    import jax

    for v in handle.values():
        if isinstance(v, jax.Array):
            v.delete()
    handle.clear()


def host_trees(trees):
    """An ensemble as host arrays (``feat``, ``thr``, ``gain``, ``leaf``)."""
    return [{k: np.asarray(v) for k, v in t.items()} for t in trees]


def fetch_columns(arr, lo: int, k: int) -> np.ndarray:
    """Columns ``lo .. lo+k`` of a device matrix as a host array.  The
    offset is an operand of the slice, so one program serves every
    offset (a plain ``arr[:, lo:lo+k]`` would compile per offset)."""
    import jax

    return np.asarray(jax.lax.dynamic_slice_in_dim(arr, lo, k, axis=1))
