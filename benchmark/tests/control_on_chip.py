#!/usr/bin/env python3
"""The controls, on the chip, at a configuration's own size.

Not a test pytest collects and not part of a benchmark run: the builder of
a `benchmark` PR runs it to read the two numbers every limit is set from —
what sound runs of the program give, and what the control gives when the
reference, computed one precision lower, is put in the program's place:

    chiprun -- python3 benchmark/tests/control_on_chip.py \
        --config higgs-24m-d6 --seeds 11,12,13

One process, one ingest and one 100-round fit per seed; the numbers of all
three operations (boost, ingest, score) are read from that one state, for
the program and for the controls (``bfloat16`` sums, ``float8``
gradients).  One JSON line per seed on standard output and in
``chiprun_out/control/<config>.jsonl``.  ``test_correct.py`` keeps the
same comparisons as CPU tests at a size a test run can hold.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import checks, harness, reference as ref, system  # noqa: E402


def one_seed(config_name: str, config: dict, seed: int, n_trees: int,
             slab_rows: int, n_slabs: int) -> dict:
    t0 = time.perf_counter()
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix={},
                      seed=seed, chips=int(config["chips"]))
    X, y = system.training_rows(ctx)
    n = len(y)
    model = system.new_model(ctx, n_trees)
    handle = system.ingest(model, X, y)
    model.fit_device(handle)
    peak = harness.peak_memory(__import__("jax").devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])[:, :n]
    out = {"config": config_name, "seed": seed, "rows": n,
           "memory_peak_gib": peak / 2**30,
           "fit_seconds": model.last_fit_seconds}

    # boost: the program's trees, then each control's in their place
    out["boost.program"] = checks.boost_tree_numbers(bins_t, y, trees, config)
    for prec in ("bfloat16", "float8"):
        out["boost.control." + prec] = checks.boost_tree_numbers(
            bins_t, y, checks.control_trees(bins_t, y, trees, config, prec),
            config)

    # ingest: cuts of three features, bins of a block of rows
    rng = np.random.default_rng(seed)
    feats = sorted(rng.choice(X.shape[1], size=3, replace=False).tolist())
    k = 1 << 20
    lo = int(rng.integers(0, n - k + 1))
    control_cuts = np.array(cuts)
    for f in feats:
        control_cuts[f] = ref.quantile_cuts(
            X[:, f], int(config["n_bins"]), int(config["n_summary"]),
            precision="bfloat16")
    out["ingest.program"] = {
        "cuts_gap": checks.cuts_gap(X, cuts, feats, config),
        "bins_mismatches": checks.bins_mismatches(
            X[lo:lo + k], bins_t[:, lo:lo + k], cuts)}
    out["ingest.control.bfloat16"] = {
        "cuts_gap": checks.cuts_gap(X, control_cuts, feats, config),
        "bins_mismatches": checks.bins_mismatches(
            X[lo:lo + k],
            ref.bin_rows(X[lo:lo + k], cuts, precision="bfloat16").T, cuts)}

    # score: a few slabs through predict, and the control's answers
    Xh, _yh = system.heldout_rows(ctx, slab_rows * n_slabs)
    slabs = [Xh[i * slab_rows:(i + 1) * slab_rows] for i in range(n_slabs)]
    got = [model.predict(s) for s in slabs]
    control = [ref.sigmoid(ref.ensemble_margin(
        s, cuts, trees, float(config["base_score"]), "bfloat16"))
        for s in slabs]
    out["score.program"] = {
        "score_gap": checks.score_gap(slabs, got, cuts, trees, config)}
    out["score.control.bfloat16"] = {
        "score_gap": checks.score_gap(slabs, control, cuts, trees, config)}
    out["seconds"] = time.perf_counter() - t0
    system.drop_handle(handle)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--n-trees", type=int, default=100)
    ap.add_argument("--slab-rows", type=int, default=16384)
    ap.add_argument("--slabs", type=int, default=7)
    args = ap.parse_args()
    bench = harness.load_benchmark(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".compile_cache"))
    harness.claim_devices(int(config["chips"]), require_chip=True)
    outdir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, args.config + ".jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(one_seed(args.config, config, seed,
                                       args.n_trees, args.slab_rows,
                                       args.slabs))
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
