"""The seam for CATEGORICAL columns: a fourth module of the benchmark that
imports ``dmlc_core_tpu``, beside ``system.py`` (whose ``MODEL_KEYS`` are
the hyperparameters every configuration had until one came whose columns
are names; that file cannot be edited by the PR that adds this one),
``system_paged.py`` and ``system_lossguide.py``.  It passes three more keys
of the configuration's file to ``HistGBT`` — ``feature_types``,
``max_cat_to_onehot``, ``max_cat_threshold``, XGBoost's names — and
nothing else: no ``cuts=``, no ``DMLC_*`` variable; and it draws the
configuration's rows by ``datagen_cat``.  Everything else of the seam is
``system.py``'s.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from benchmark import datagen_cat, system

#: parameters of a configuration file that go to ``HistGBT`` as they are
MODEL_KEYS = system.MODEL_KEYS + ("feature_types", "max_cat_to_onehot",
                                  "max_cat_threshold")


def new_model(ctx, n_trees: int, **override):
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    kw = {k: ctx.config[k] for k in MODEL_KEYS if k in ctx.config}
    kw.update(override)
    return HistGBT(n_trees=int(n_trees), mesh=local_mesh(ctx.chips), **kw)


def training_rows(ctx) -> Tuple[np.ndarray, np.ndarray]:
    return datagen_cat.airline_like(int(ctx.config["rows"]), ctx.seed,
                                    stream=0)


def heldout_rows(ctx, rows: int) -> Tuple[np.ndarray, np.ndarray]:
    return datagen_cat.airline_like(int(rows), ctx.seed, stream=1)
