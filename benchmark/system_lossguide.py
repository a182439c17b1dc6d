"""The seam for the GROWTH POLICY: a third module of the benchmark that
imports ``dmlc_core_tpu``, beside ``system.py`` (whose ``MODEL_KEYS`` are
the hyperparameters every configuration had until a leaf-wise one came;
that file cannot be edited by the PR that adds this one) and
``system_paged.py``.  It passes two more keys of the configuration's file
to ``HistGBT`` — ``grow_policy`` and ``max_leaves``, XGBoost's names —
and nothing else: no ``cuts=``, no ``DMLC_*`` variable.  Everything else
of the seam is ``system.py``'s.
"""

from __future__ import annotations

from benchmark import system

#: parameters of a configuration file that go to ``HistGBT`` as they are
MODEL_KEYS = system.MODEL_KEYS + ("grow_policy", "max_leaves")


def new_model(ctx, n_trees: int):
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    kw = {k: ctx.config[k] for k in MODEL_KEYS if k in ctx.config}
    return HistGBT(n_trees=int(n_trees), mesh=local_mesh(ctx.chips), **kw)
