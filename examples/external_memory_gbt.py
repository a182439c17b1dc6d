"""Out-of-core boosting: LibSVM file → sharded parse → disk-paged CSR →
fit_external (the Criteo-scale path, BASELINE config 3).

Run: python examples/external_memory_gbt.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.data import RowBlockIter
from dmlc_core_tpu.models import HistGBT


def main():
    tmp = tempfile.mkdtemp()
    svm = os.path.join(tmp, "train.svm")
    rng = np.random.default_rng(0)
    n, F = 50_000, 16
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.int32)
    with open(svm, "w") as f:
        for i in range(n):
            cols = rng.choice(F, size=F // 2, replace=False)  # sparse rows
            feats = " ".join(f"{j}:{X[i, j]:.4f}" for j in sorted(cols))
            f.write(f"{y[i]} {feats}\n")

    # '#cache' suffix → DiskRowIter: parse once, page through a cache file
    it = RowBlockIter.create(f"{svm}#{tmp}/cache.bin", 0, 1, "libsvm")
    model = HistGBT(n_trees=30, max_depth=5, n_bins=64, learning_rate=0.3)
    # device memory bounded by DMLC_TPU_EXTERNAL_DEVICE_BUDGET: small
    # datasets auto-run the in-core cached engine, big ones stream
    # fixed-shape chunks per level
    model.fit_external(it, num_col=F, eval_every=10)
    print(f"out-of-core trained {len(model.trees)} trees")

    # scoring is streaming too — the dense matrix never exists on the
    # host, for training OR inference (iterating rewinds automatically)
    preds = model.predict_iter(it)
    acc = float(((preds > 0.5) == y).mean())
    print(f"streamed predictions over {len(preds)} rows, train acc {acc:.3f}")

    # the same pages as a DEVICE-RESIDENT handle for repeated fits
    # (make_device_data_iter: a streaming sketch pass, then each slab
    # binned onto the chip).  A slab is slab_rows x F x 4 bytes of
    # float32 whatever the pages hold, and two are in flight on the
    # host and on the device: size slab_rows from the COLUMNS (here
    # 8,192 x 16 x 4 = 0.5 MB; a 4,227-column one-hot table at 65,536
    # rows is 1.11 GB a slab).  An entry a row lacks is 0.0.
    from dmlc_core_tpu.data.iter import iter_dense_slabs

    resident = HistGBT(n_trees=10, max_depth=5, n_bins=64, learning_rate=0.3)
    handle = resident.make_device_data_iter(
        lambda: iter_dense_slabs(it, F, 8192))
    resident.fit_device(handle)
    print(f"paged handle of {handle['n']} rows x {handle['n_features']} "
          f"columns: {len(resident.trees)} trees")
    it.close()


if __name__ == "__main__":
    main()
