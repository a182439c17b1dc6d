#!/usr/bin/env python3
"""The controls of the NATIVE-CATEGORICAL cell, on the chip, at the
configuration's own size:

    chiprun --timeout 2400 -- python3 benchmark/tests/cat_on_chip.py \
        --config expo-115m-cat-d8 --mix boost-r10-cat --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one ingest and one fit of the mix's rounds per seed, as the cell makes
them; then, from that one state:

* the numbers ``ops/boost_cat.py::check`` compares, for the program and
  with each control in its place — the same rows fitted with every column
  numeric (codes read as an order), every set of the first tree shifted by
  one bin, tree sums in ``bfloat16``, gradients in ``float8``, a fit
  stopped one round short for the learning numbers — each put through
  ``checks.apply_limits`` against the shipped mix: ``broken`` names the
  limits left;
* one ``predict`` and one ``predict_leaf`` of ``check_heldout_rows`` rows
  through the device's set descent against the reference's
  (``descent_gap``, ``descent_leaf_mismatches``), and their walls;
* ``device.memory_peak_bytes`` after the ingest and after the fit.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.cat.jsonl``; exit 1 unless the program
breaks no limit and every control the limit named for it.
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

from benchmark import (checks, checks_cat as cc, harness,  # noqa: E402
                       reference_cat as rc, system, system_cat)

#: the limit each control has to leave
NAMED = {"all_numeric": "tree0.best_gain_gap",
         "shifted_sets": "tree0.leaf_gap",
         "bfloat16": "tree0.reported_gain_gap",
         "float8": "tree1.leaf_gap_by_rows",
         "one_round_short": "rounds_share"}


def broken(ctx_like, numbers):
    """Names of ``numbers`` that leave the shipped mix's limits."""
    ctx = harness.Ctx(root=ROOT, workload="control", config=ctx_like.config,
                      mix=ctx_like.mix, seed=0, chips=1)
    checks.apply_limits(ctx, numbers)
    return sorted(c["name"] for c in ctx.comparisons if not c["ok"])


def one_seed(config_name, config, mix, seed):
    import jax

    t0 = time.perf_counter()
    p = mix["params"]
    types, n_bins = config["feature_types"], int(config["n_bins"])
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))
    model = system_cat.new_model(ctx, p["n_trees"])
    X, y = system_cat.training_rows(ctx)
    n = len(y)
    handle = system.ingest(model, X, y)
    peak_ingest = harness.peak_memory(jax.devices())
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])[:, :n]
    out = {"config": config_name, "seed": seed, "rows": n,
           "rounds": len(trees), "fit_seconds": model.last_fit_seconds,
           "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                               "after_fit": peak_fit / 2**30},
           "round_plan": model.round_plan}
    Xh, yh = system_cat.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)

    def learning(forest, scores):
        return cc.learning_numbers(X[:m], y[:m], yh, scores, cuts, forest,
                                   config)

    scores = model.predict(Xh)
    numbers, facts = cc.tree_numbers(bins_t, y, trees, cuts, config)
    numbers.update(learning(trees, scores))
    out["facts"] = facts
    out["program"] = {"numbers": numbers, "broken": broken(ctx, numbers)}
    controls = {
        "shifted_sets": [cc.shifted_sets(trees[0], cuts, config)] + trees[1:],
        "bfloat16": cc.control_trees(bins_t, y, trees, cuts, config,
                                     "bfloat16") + trees[2:],
        "float8": cc.control_trees(bins_t, y, trees, cuts, config,
                                   "float8") + trees[2:],
    }
    for name, forest in controls.items():
        nums, _ = cc.tree_numbers(bins_t, y, forest, cuts, config)
        out["control." + name] = {"numbers": nums,
                                  "broken": broken(ctx, nums)}
    model.trees = model.trees[:-1]
    short = dict(learning(trees[:-1], model.predict(Xh)),
                 rounds_share=(len(trees) - 1) / float(p["n_trees"]))
    model.trees = model.trees + [trees[-1]]
    out["control.one_round_short"] = {"numbers": short,
                                      "broken": broken(ctx, short)}

    # the device's set descent against the reference's
    t = time.perf_counter()
    got = model.predict(Xh, output_margin=True)
    predict_s = time.perf_counter() - t
    want = rc.ensemble_margin(Xh, cuts, types, trees,
                              float(config["base_score"]), n_bins)
    model.predict_leaf(Xh[:1024])
    t = time.perf_counter()
    leaf = model.predict_leaf(Xh)
    leaf_s = time.perf_counter() - t
    bins_h = np.ascontiguousarray(rc.bin_rows(Xh, cuts, types).T)
    want_leaf = np.stack([rc.descend_binned(bins_h, tr, n_bins)
                          for tr in trees], axis=1)
    out["descent"] = {
        "rows": len(Xh), "predict_s": predict_s, "predict_leaf_s": leaf_s,
        "descent_gap": float(np.max(np.abs(got - want))),
        "descent_leaf_mismatches": int(np.count_nonzero(leaf != want_leaf))}

    # the same rows with every column numeric: codes read as an order
    system.drop_handle(handle)
    del model
    plain = system_cat.new_model(ctx, 2, feature_types=[])
    handle = system.ingest(plain, X, y)
    plain.fit_device(handle)
    bins_q = np.asarray(handle["bins_t"])[:, :n]
    forest = [cc.as_sets(t, n_bins) for t in system.host_trees(plain.trees)]
    nums, _ = cc.tree_numbers(
        bins_q, y, forest, np.asarray(plain.cuts),
        dict(config, feature_types=["q"] * len(types)),
        rule=(bins_t, rc.used_bins(cuts, types)))
    out["control.all_numeric"] = {
        "numbers": nums, "broken": broken(ctx, nums),
        "fit_seconds_per_round": plain.last_fit_seconds / 2}
    system.drop_handle(handle)
    out["verdict"] = bool(
        not out["program"]["broken"]
        and all(NAMED[k[8:]] in v["broken"] for k, v in out.items()
                if k.startswith("control."))
        and out["descent"]["descent_leaf_mismatches"] == 0)
    out["seconds"] = time.perf_counter() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows in place of the configuration's (a dry run)")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a chip (a dry run of the plumbing)")
    args = ap.parse_args()
    bench = harness.load_benchmark(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    if args.rows:
        config["rows"] = args.rows
    with open(harness.find_file(ROOT, bench["paths"], "traffic",
                                args.mix + ".json")) as f:
        mix = json.load(f)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".compile_cache"))
    harness.claim_devices(int(config["chips"]), require_chip=not args.cpu)
    outdir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(outdir, exist_ok=True)
    ok = True
    with open(os.path.join(outdir, args.config + ".cat.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = one_seed(args.config, config, mix, seed)
            ok = ok and out["verdict"]
            line = json.dumps(out)
            print(line, flush=True)
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
