#!/usr/bin/env python3
"""The controls of the LEAF-WISE cell, on the chip, at the configuration's
own size:

    chiprun --timeout 1800 -- python3 benchmark/tests/lossguide_on_chip.py \
        --config higgs-24m-l255 --mix boost-r5-lossguide --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one ingest and one fit of the mix's rounds per seed, as the cell makes
them; then, from that one state:

* the numbers ``ops/boost_lossguide.py::check`` compares, for the program
  and with each control in its place — tree sums in ``bfloat16``,
  gradients in ``float8``, two expansions swapped, a fit stopped one round
  short for the learning numbers — each put through
  ``checks.apply_limits`` against the shipped mix: ``broken`` names the
  limits left;
* one ``predict`` and one ``predict_leaf`` of ``check_heldout_rows`` rows
  through the device's node-list walker against the reference's descent
  (``walker_gap``, ``walker_leaf_mismatches``), and their walls;
* ``device.memory_peak_bytes`` after the ingest and after the fit.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.lossguide.jsonl``; exit 1 unless the
program breaks no limit and every control at least one.
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

from benchmark import (checks, checks_lossguide as cl, harness,  # noqa: E402
                       reference as ref, reference_lossguide as rl, system,
                       system_lossguide)


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
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))
    model = system_lossguide.new_model(ctx, p["n_trees"])
    X, y = system.training_rows(ctx)
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
    Xh, yh = system.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)

    def numbers_of(forest):
        tree_numbers, facts = cl.tree_numbers(bins_t, y, forest, config)
        return dict(tree_numbers, **cl.learning_numbers(
            X[:m], y[:m], Xh, yh, cuts, forest, config)), facts

    numbers, facts = numbers_of(trees)
    out["facts"] = facts
    out["program"] = {"numbers": numbers, "broken": broken(ctx, numbers)}
    controls = {
        "bfloat16": cl.control_trees(bins_t, y, trees, config, "bfloat16")
        + trees[2:],
        "float8": cl.control_trees(bins_t, y, trees, config, "float8")
        + trees[2:],
        "swapped_order": [cl.swapped_order(trees[0])] + trees[1:],
    }
    for name, forest in controls.items():
        numbers, _ = numbers_of(forest)
        out["control." + name] = {"numbers": numbers,
                                  "broken": broken(ctx, numbers)}
    short = dict(cl.learning_numbers(X[:m], y[:m], Xh, yh, cuts, trees[:-1],
                                     config),
                 rounds_share=(len(trees) - 1) / float(p["n_trees"]))
    out["control.one_round_short"] = {"numbers": short,
                                      "broken": broken(ctx, short)}

    # the device's node-list walker against the reference's descent
    model.predict(Xh)                          # (warm: it compiles)
    t = time.perf_counter()
    got = model.predict(Xh, output_margin=True)
    predict_s = time.perf_counter() - t
    want = rl.ensemble_margin(Xh, cuts, trees, float(config["base_score"]))
    t = time.perf_counter()
    leaf = model.predict_leaf(Xh)
    leaf_s = time.perf_counter() - t
    want_leaf = np.stack([rl.descend_raw(np.asarray(Xh, np.float64),
                                         cuts.astype(np.float64), tr)
                          for tr in trees], axis=1)
    out["walker"] = {
        "rows": len(Xh), "predict_s": predict_s, "predict_leaf_s": leaf_s,
        "walker_gap": float(np.max(np.abs(got - want))),
        "walker_leaf_mismatches": int(np.count_nonzero(leaf != want_leaf))}
    out["verdict"] = bool(
        not out["program"]["broken"]
        and all(v["broken"] for k, v in out.items()
                if k.startswith("control."))
        and out["walker"]["walker_leaf_mismatches"] == 0)
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
    ok = True
    with open(os.path.join(outdir, args.config + ".lossguide.jsonl"),
              "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = one_seed(args.config, config, mix, seed)
            ok = ok and out["verdict"]
            line = json.dumps(out)
            print(line, flush=True)
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
