"""A scratch root for the self-tests: a ``BENCHMARK.json`` and data files
of its own, toy-sized, that reuse the benchmark's code untouched."""

import json
import os

TINY_CONFIG = {
    "source": "self-test", "chips": 1, "rows": 6000, "features": 8,
    "heldout_rows": 2048, "max_depth": 3, "n_bins": 32,
    "learning_rate": 0.3, "reg_lambda": 1.0, "min_child_weight": 1.0,
    "objective": "binary:logistic", "base_score": 0.0, "n_summary": 256,
    "reduced": [], "assumed": [],
}

TINY_MIXES = {
    "tiny-boost": {
        "op": "boost",
        "params": {"n_trees": 4, "warm_trees": 4, "check_bin_rows": 1024,
                   "check_heldout_rows": 1024, "check_train_rows": 1024},
        "end_to_end": {"boost_rounds_per_s": {"kind": "rate"}},
        "limits": {"rounds_share": {"limit": 1.0, "passes": "at_least"},
                   "bins_mismatches": 0, "tree0.root_gain_gap": 1e-5,
                   "tree0.reported_gain_gap": 1e-5, "tree0.leaf_gap": 1e-5,
                   "tree1.leaf_gap": 1e-5, "ops_trees_differ": 0,
                   "train_logloss": 0.69,
                   "heldout_auc": {"limit": 0.6, "passes": "at_least"}}},
    "tiny-ingest": {
        "op": "ingest",
        "params": {"check_features": 2, "check_bin_rows": 1024},
        "end_to_end": {"ingest_rows_per_s": {"kind": "rate"}},
        "limits": {"rows_share": {"limit": 1.0, "passes": "at_least"},
                   "cuts_gap": 1e-4, "bins_mismatches": 0}},
    "tiny-score": {
        "op": "score",
        "params": {"n_trees": 4, "heldout_rows": 2048, "slab_rows": 256,
                   "check_rows": 1024},
        "end_to_end": {"score_rows_per_s": {"kind": "rate"},
                       "score_p95_ms": {"kind": "percentile", "q": 95,
                                        "scale": 1000}},
        "limits": {"rows_share": {"limit": 1.0, "passes": "at_least"},
                   "score_gap": 1e-5,
                   "heldout_auc": {"limit": 0.6, "passes": "at_least"}}},
}

E2E = [
    {"name": "boost_rounds_per_s", "unit": "rounds/s", "better": "higher",
     "bound": 0.03, "source": "host_clock", "workloads": ["tiny.tiny-boost"]},
    {"name": "ingest_rows_per_s", "unit": "rows/s", "better": "higher",
     "bound": 0.03, "source": "host_clock", "workloads": ["tiny.tiny-ingest"]},
    {"name": "score_rows_per_s", "unit": "rows/s", "better": "higher",
     "bound": 0.03, "source": "host_clock", "workloads": ["tiny.tiny-score"]},
    {"name": "score_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
     "source": "host_clock", "workloads": ["tiny.tiny-score"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
]


def make_root(tmp, per_layer=(), extra_files=None):
    """Write a scratch root under ``tmp`` and return its path."""
    root = str(tmp)
    base = os.path.join(root, "bench_data")
    for sub in ("configs", "traffic", "metrics", "ops"):
        os.makedirs(os.path.join(base, sub), exist_ok=True)
    with open(os.path.join(base, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    for name, mix in TINY_MIXES.items():
        with open(os.path.join(base, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for rel, text in (extra_files or {}).items():
        with open(os.path.join(base, rel), "w") as f:
            f.write(text)
    bench = {
        "command": ["python3", "benchmark/run.py"], "paths": ["bench_data"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "self-test",
                     "file": "bench_data/configs/tiny.json", "reduced": [],
                     "why": "self-test"}],
        "workloads": [{"name": "tiny." + m, "config": "tiny", "traffic": m,
                       "chips": 1, "why": "self-test"} for m in TINY_MIXES],
        "end_to_end": E2E,
        "per_layer": [{"name": "compile.cache_misses", "unit": "count",
                       "better": "lower", "source": "program_counter",
                       "layer": "compile", "moves": "setup_s"}]
        + list(per_layer),
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
