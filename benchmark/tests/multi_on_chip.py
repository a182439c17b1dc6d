#!/usr/bin/env python3
"""The controls of the multiclass configuration, on the chip, at its own
size (``rank_on_chip.py`` for a table of several classes):

    chiprun -- python3 benchmark/tests/multi_on_chip.py \
        --config covtype-7m-d6 --mix boost-r25-multi --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one ``make_device_data(X, y)`` and one fit of the mix's rounds per seed,
as the cell makes them; then, from that one state:

* the numbers ``ops/boost_multi.py::check`` compares, for the program and
  with each control in its place — one-vs-rest sigmoids in the softmax's
  place, the hessian without its factor 2, bfloat16 margins, bfloat16
  histogram sums, float8 gradients into the kernels, class c's trees
  added onto column c + 1 (tree numbers; the last two also against the
  one ``predict``), and a fit stopped at 12 rounds (the two learning
  numbers) — each beside the mix's limit;
* ``device.memory_peak_bytes`` after the ingest and after the fit, and
  the program's ``round_plan``.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.multi.jsonl``.
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

from benchmark import (checks, checks_multi, datagen_multi,  # noqa: E402
                       harness, system)


def one_seed(config_name: str, config: dict, mix: dict, seed: int) -> dict:
    import jax

    t0 = time.perf_counter()
    p = mix["params"]
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))
    model = system.new_model(ctx, p["n_trees"])
    X, y = datagen_multi.covtype_like(int(config["rows"]), seed)
    n = len(y)
    out = {"config": config_name, "seed": seed, "rows": n,
           "features": int(X.shape[1]),
           "datagen_s": time.perf_counter() - t0,
           "class_shares": (np.bincount(y.astype(np.int64)) / n).tolist()}
    t = time.perf_counter()
    handle = system.ingest(model, X, y)
    out["ingest_s"] = time.perf_counter() - t
    peak_ingest = harness.peak_memory(jax.devices())
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])[:, :n]
    out.update({"rounds": len(trees), "num_class": model.param.num_class,
                "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                                    "after_fit": peak_fit / 2**30},
                "fit_seconds": model.last_fit_seconds,
                "round_plan": model.round_plan, "limits": mix["limits"]})
    Xh, yh = datagen_multi.covtype_like(int(p["check_heldout_rows"]), seed,
                                        stream=1)
    m = min(int(p["check_train_rows"]), n)
    got = model.predict(Xh, output_margin=True)

    def learning(some_trees):
        return checks_multi.learning_numbers(X[:m], y[:m], Xh, yh, cuts,
                                             some_trees, config)

    t = time.perf_counter()
    worst = {}
    out["boost.program"] = dict(
        checks_multi.boost_tree_numbers(bins_t, y, trees, config, worst),
        worst_leaf=worst,
        score_gap=checks_multi.score_gap(Xh, got, cuts, trees, config),
        bins_mismatches=checks.bins_mismatches(
            X[:4096], bins_t[:, :4096], cuts),
        **learning(trees))
    out["check_s"] = time.perf_counter() - t
    for control in checks_multi.CONTROLS:
        t = time.perf_counter()
        worst = {}
        out["boost.control." + control] = checks_multi.boost_tree_numbers(
            bins_t, y, checks_multi.control_trees(bins_t, y, trees, config,
                                                  control), config, worst)
        out["boost.control." + control]["worst_leaf"] = worst
        out["boost.control." + control]["seconds"] = time.perf_counter() - t
    # the one predict, held against the descent with a fault in it
    out["boost.control.bf16_margin"]["score_gap"] = checks_multi.score_gap(
        Xh, got, cuts, trees, config, precision="bfloat16")
    out["boost.control.shifted"]["score_gap"] = checks_multi.score_gap(
        Xh, got, cuts, trees, config, shift=1)
    out["boost.control.half_rounds"] = learning(trees[:len(trees) // 2])
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
    with open(os.path.join(outdir, args.config + ".multi.jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(one_seed(args.config, config, mix, seed))
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
