#!/usr/bin/env python3
"""The controls of the missing-value configuration, on the chip, at its
own size (``wide_on_chip.py`` for a table with holes):

    chiprun -- python3 benchmark/tests/missing_on_chip.py \
        --config bosch-1m-d8 --mix boost-r25-nan --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one ingest and one fit of the mix's rounds per seed, as the cell makes
them; then, from that one state:

* the numbers ``ops/boost_nan.py::check`` compares, for the program and
  with each control in its place — ``bfloat16`` sums, ``float8``
  gradients, every direction forced left (tree numbers, and the two
  learning numbers with the forced routing), half the rounds (the two
  learning numbers) — each beside the mix's limit;
* the numbers ``ops/ingest_nan.py::check`` compares, and with NaN
  aliased into the top value bin, the rows rounded to ``bfloat16``
  before the binning, ``bfloat16`` cuts, and the dense rule's cuts
  (positions ``q * (c - 1)`` in place of the midpoint rule's);
* one ``predict`` of ``heldout_rows`` against the reference's margins;
* ``device.memory_peak_bytes`` after the ingest and after the fit, and
  the host seconds of the two NaN scans and of one whole-matrix put.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.missing.jsonl``.
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

from benchmark import (checks_missing, datagen_missing, harness,  # noqa: E402
                       reference_missing as ref, system)


def one_seed(config_name: str, config: dict, mix: dict, seed: int) -> dict:
    import jax

    t0 = time.perf_counter()
    p = mix["params"]
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))
    X, y = datagen_missing.bosch_like(int(config["rows"]),
                                      int(config["features"]), seed)
    n = len(y)
    out = {"config": config_name, "seed": seed, "rows": n,
           "features": int(X.shape[1]), "datagen_s": time.perf_counter() - t0,
           "nan_share": float(np.isnan(X[:65536]).mean()),
           "positive_share": float(y.mean())}
    t = time.perf_counter()
    has_nan = bool(np.isnan(X).any())
    out["host.isnan_any_s"] = time.perf_counter() - t
    t = time.perf_counter()
    finite_any = np.isfinite(X).any(axis=0)
    out["host.isfinite_any_axis0_s"] = time.perf_counter() - t
    assert has_nan and finite_any.all()
    t = time.perf_counter()
    x_dev = jax.device_put(X)
    x_dev.block_until_ready()
    out["put_whole_s"] = time.perf_counter() - t
    out["put_bytes"] = int(X.nbytes)
    x_dev.delete()
    del x_dev

    model = system.new_model(ctx, p["n_trees"])
    t = time.perf_counter()
    handle = system.ingest(model, X, y)
    out["ingest_s"] = time.perf_counter() - t
    peak_ingest = harness.peak_memory(jax.devices())
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])[:, :n]
    out.update({"rounds": len(trees),
                "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                                    "after_fit": peak_fit / 2**30},
                "fit_seconds": model.last_fit_seconds,
                "round_plan": model.round_plan, "limits": mix["limits"],
                "root": {k: int(trees[0][k][0, 0])
                         for k in ("feat", "thr", "dir")}})
    line = datagen_missing.line_of(int(config["features"]), seed)
    out["line"] = {"a": line.a, "b": line.b, "c1": line.c1}

    Xh, yh = datagen_missing.bosch_like(int(p["check_heldout_rows"]),
                                        int(config["features"]), seed,
                                        stream=1)
    m = min(int(p["check_train_rows"]), n)
    worst = []
    out["boost.program"] = dict(
        checks_missing.boost_tree_numbers(bins_t, y, trees, config, worst),
        **checks_missing.learning_numbers(X[:m], y[:m], Xh, yh, cuts, trees,
                                          config))
    out["boost.program"]["tree1.worst_leaf_gap.not_compared"] = worst[0]
    for control in ("bfloat16", "float8", "force_left"):
        worst = []
        out["boost.control." + control] = checks_missing.boost_tree_numbers(
            bins_t, y, checks_missing.control_trees(bins_t, y, trees, config,
                                                    control), config, worst)
        out["boost.control." + control][
            "tree1.worst_leaf_gap.not_compared"] = worst[0]
    out["boost.control.force_left"].update(checks_missing.learning_numbers(
        X[:m], y[:m], Xh, yh, cuts, trees, config, force_left=True))
    out["boost.control.half_rounds"] = checks_missing.learning_numbers(
        X[:m], y[:m], Xh, yh, cuts, trees[:len(trees) // 2], config)

    rng = np.random.default_rng(seed)
    k = min(65536, n)
    lo = int(rng.integers(0, n - k + 1))
    feats = sorted(rng.choice(X.shape[1], size=16, replace=False).tolist())
    bf16_cuts, dense_cuts = np.array(cuts), np.array(cuts)
    for f in feats:
        bf16_cuts[f] = ref.quantile_cuts(
            X[:, f], int(config["n_bins"]), int(config["n_summary"]),
            precision="bfloat16")
        v = X[~np.isnan(X[:, f]), f].astype(np.float64)
        dense_cuts[f] = np.quantile(
            np.quantile(v, np.linspace(0, 1, int(config["n_summary"]))),
            np.linspace(0, 1, int(config["n_bins"]))[1:-1])
    block = X[lo:lo + k]
    out["ingest.program"] = dict(
        cuts_gap=checks_missing.cuts_gap(X, cuts, feats, config),
        **checks_missing.bin_numbers(block, bins_t[:, lo:lo + k], cuts,
                                     config))
    out["ingest.control.bfloat16_cuts"] = {
        "cuts_gap": checks_missing.cuts_gap(X, bf16_cuts, feats, config)}
    out["ingest.control.dense_rule_cuts"] = {
        "cuts_gap": checks_missing.cuts_gap(X, dense_cuts, feats, config)}
    out["ingest.control.alias_missing"] = checks_missing.bin_numbers(
        block, ref.bin_rows(block, cuts, alias_missing=True).T, cuts, config)
    out["ingest.control.bfloat16_rows"] = checks_missing.bin_numbers(
        block, ref.bin_rows(block, cuts, precision="bfloat16").T, cuts,
        config)

    Xs, _ys = datagen_missing.bosch_like(int(config["heldout_rows"]),
                                         int(config["features"]), seed,
                                         stream=1)
    model.predict(Xs)                         # warm: it compiles
    t = time.perf_counter()
    got = model.predict(Xs, output_margin=True)
    out["predict"] = {"rows": len(Xs), "call_s": time.perf_counter() - t}
    s = slice(0, 32768)
    want = ref.ensemble_margin(Xs[s], cuts, trees,
                               float(config["base_score"]))
    out["predict"]["margin_gap"] = float(np.max(np.abs(got[s] - want)))
    out["predict"]["margin_gap.force_left"] = float(np.max(np.abs(
        ref.ensemble_margin(Xs[s], cuts, trees, float(config["base_score"]),
                            force_left=True) - want)))
    out["seconds"] = time.perf_counter() - t0
    system.drop_handle(handle)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args()
    bench = harness.load_benchmark(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(harness.find_file(ROOT, bench["paths"], "traffic",
                                args.mix + ".json")) as f:
        mix = json.load(f)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".compile_cache"))
    harness.claim_devices(int(config["chips"]), require_chip=True)
    outdir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, args.config + ".missing.jsonl"),
              "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(one_seed(args.config, config, mix, seed))
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
