#!/usr/bin/env python3
"""The controls of the ranking configuration, on the chip, at its own
size (``missing_on_chip.py`` for documents in query groups):

    chiprun -- python3 benchmark/tests/rank_on_chip.py \
        --config mslr-web30k-d6 --mix boost-r25-rank --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one ``make_device_data(X, y, qid=qid)`` and one fit of the mix's rounds
per seed, as the cell makes them; then, from that one state:

* the numbers ``ops/boost_rank.py::check`` compares, for the program and
  with each control in its place — every query TRUNCATED to its first
  128 documents, the |dNDCG| weight dropped (``rank:pairwise`` in
  ``rank:ndcg``'s place), ties broken by REVERSE position, ranks taken
  inside a padded width with the pads ranked FIRST, bfloat16 pair sums,
  bfloat16 histogram sums, float8 gradients into the kernels (tree
  numbers), and a fit stopped at 12 rounds (the two learning numbers) —
  each beside the mix's limit;
* ``device.memory_peak_bytes`` after the ingest and after the fit, the
  handle's rows against the documents, and the program's ``round_plan``.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.rank.jsonl``.
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

from benchmark import (checks, checks_rank, datagen_rank,  # noqa: E402
                       harness, reference_rank as rr, system)


def one_seed(config_name: str, config: dict, mix: dict, seed: int) -> dict:
    import jax

    t0 = time.perf_counter()
    p = mix["params"]
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))

    def rows(queries, n_rows, stream):
        return datagen_rank.mslr_like(
            int(queries), n_rows, int(config["features"]), seed,
            stream=stream, max_group=int(config["max_group"]))

    X, y, qid = rows(config["queries"], int(config["rows"]), 0)
    n = len(y)
    order, bounds = rr.query_bounds(qid)
    out = {"config": config_name, "seed": seed, "rows": n,
           "queries": len(bounds) - 1, "features": int(X.shape[1]),
           "datagen_s": time.perf_counter() - t0,
           "max_group": int(np.diff(bounds).max()),
           "grade_shares": (np.bincount(y.astype(np.int64), minlength=5)
                            / n).tolist(),
           "rows_out_of_query_order": int((np.diff(qid) < 0).sum())}

    model = system.new_model(ctx, p["n_trees"])
    t = time.perf_counter()
    handle = model.make_device_data(X, y, qid=qid)
    jax.block_until_ready(jax.tree.leaves(handle))
    out["ingest_s"] = time.perf_counter() - t
    peak_ingest = harness.peak_memory(jax.devices())
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])
    out.update({"rounds": len(trees), "handle_rows": int(bins_t.shape[1]),
                "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                                    "after_fit": peak_fit / 2**30},
                "fit_seconds": model.last_fit_seconds,
                "round_plan": model.round_plan, "limits": mix["limits"],
                "root": {k: int(trees[0][k][0, 0]) for k in ("feat", "thr")},
                # the largest gain of each level, first and last tree: what
                # a min-split-gain (gamma) would have to clear
                "level_gains": {str(k): np.asarray(trees[k]["gain"]).max(
                    axis=1).tolist() for k in (0, len(trees) - 1)}})
    rel = y[order]
    Xh, yh, qh = rows(p["check_heldout_queries"], None, 1)
    oh, bh = rr.query_bounds(qh)
    m = int(bounds[min(int(p["check_train_queries"]), len(bounds) - 1)])

    def learning(some_trees):
        return checks_rank.learning_numbers(
            X[order[:m]], rel[:m], bounds[bounds <= m], Xh[oh], yh[oh], bh,
            cuts, some_trees, config)

    round0 = {}
    t = time.perf_counter()
    out["boost.program"] = dict(
        checks_rank.boost_tree_numbers(bins_t, rel, bounds, trees, config,
                                       round0), **learning(trees))
    out["boost.program"]["bins_mismatches"] = checks.bins_mismatches(
        X[order[:4096]], bins_t[:, :4096], cuts)
    out["check_s"] = time.perf_counter() - t
    for control in checks_rank.CONTROLS:
        t = time.perf_counter()
        out["boost.control." + control] = checks_rank.boost_tree_numbers(
            bins_t, rel, bounds, checks_rank.control_trees(
                bins_t, rel, bounds, trees, config, control), config, round0)
        out["boost.control." + control]["seconds"] = time.perf_counter() - t
    out["boost.control.half_rounds"] = learning(trees[:len(trees) // 2])
    out["seconds"] = time.perf_counter() - t0
    system.drop_handle({k: v for k, v in handle.items()
                        if k in ("bins_t", "y_d", "w_d")})
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
    with open(os.path.join(outdir, args.config + ".rank.jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(one_seed(args.config, config, mix, seed))
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
