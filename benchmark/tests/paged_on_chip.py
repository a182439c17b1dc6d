#!/usr/bin/env python3
"""The controls of the paged one-hot configuration, on the chip, at its
own size (``missing_on_chip.py`` for a table that arrives in pages):

    chiprun -- python3 benchmark/tests/paged_on_chip.py \
        --config allstate-1m25-d8 --seeds 11,12

Not a test pytest collects and not part of a benchmark run.  One process,
one paged ingest and one fit of the boost mix's rounds per seed, as the
cells make them; then, from that one state, the numbers the two
operations' checks compare, for the program and with each control in its
place, each put through ``checks.apply_limits`` against the boost mix
(the cell ``BENCHMARK.json`` runs; the ingest controls against the
ingest mix too).  The program has to break no limit and every control at
least one, or the script exits 1:

* ingest: cuts sketched from the FIRST slab alone, and from every slab
  with a 64-point summary (``cuts_rank_error``); one indicator's cuts
  pushed past 1.0 (``indicator_cuts_missing``); the block binned from
  rows rounded to ``bfloat16`` (``bins_mismatches``);
* boost: ``bfloat16`` sums and ``float8`` gradients (the leaf gaps), the
  trees read one column to the right (``tree0.root_gain_gap``), four
  rounds of the five (``rounds_share``, ``train_logloss``);
* one ``predict`` of ``heldout_rows`` against the reference's descent;
* ``device.memory_peak_bytes`` after the ingest and after the fit, and
  the program's own record of the ingest (``[oplog]``).

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.paged.jsonl``; its ``broken`` names, for
the program and each control, the limits it left, and ``verdict`` what
is wrong with the seed (nothing, in a sound run).
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

from benchmark import (checks, checks_paged, datagen_onehot,  # noqa: E402
                       harness, reference as ref, reference_paged as refp,
                       system, system_paged)
from benchmark.metrics import _oplog  # noqa: E402


def broken(mix: dict, numbers: dict) -> list:
    """The limits of ``mix`` that ``numbers`` leave, as a run of the cell
    would find them: through ``checks.apply_limits`` (a number the mix
    has no limit for raises there, here too)."""
    ctx = harness.Ctx(root=ROOT, workload="control", config={}, mix=mix,
                      seed=0, chips=1)
    checks.apply_limits(ctx, numbers)
    return [c["name"] for c in ctx.comparisons if not c["ok"]]


def one_seed(config_name: str, config: dict, mixes: dict, seed: int) -> dict:
    import jax

    t0 = time.perf_counter()
    boost, ingest = mixes["boost"], mixes["ingest"]
    p = boost["params"]
    F, slab = int(config["features"]), int(config["slab_rows"])
    ctx = harness.Ctx(root=ROOT, workload="control", config=config,
                      mix=boost, seed=seed, chips=int(config["chips"]))
    model = system.new_model(ctx, p["n_trees"])
    blocks, pages = system_paged.stage_pages(ctx)
    y = np.concatenate([b[3] for b in blocks])
    n = len(y)
    out = {"config": config_name, "seed": seed, "rows": n, "features": F,
           "pages": pages.count, "stage_s": time.perf_counter() - t0,
           "positive_share": float(y.mean()),
           "limits": {"ingest": ingest["limits"], "boost": boost["limits"]}}
    t = time.perf_counter()
    handle = system_paged.ingest_paged(model, pages, F, slab)
    out["ingest_s"] = time.perf_counter() - t
    out["pages_replayed"] = pages.replayed / pages.count
    peak_ingest = harness.peak_memory(jax.devices())
    rec = system_paged.last_ingest_record()
    if rec is not None:
        print(_oplog.line(rec), flush=True)
        out["ingest_record"] = {"counts": rec["counts"],
                                "children": rec["children"]}
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = system_paged.fetch_feature_rows(handle["bins_t"], n)
    out.update({"rounds": len(trees), "fit_seconds": model.last_fit_seconds,
                "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                                    "after_fit": peak_fit / 2**30},
                "round_plan": model.round_plan,
                "root": {k: int(trees[0][k][0, 0]) for k in ("feat", "thr")}})

    # -- ingest: the program, then each control ----------------------------
    ids = list(range(datagen_onehot.NUMERIC))
    columns = checks_paged.numeric_columns(blocks, ids)
    occupied = checks_paged.occupied_indicators(blocks, n, F)
    rng = np.random.default_rng(seed)
    k = min(int(ingest["params"]["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    block = checks_paged.dense_rows(blocks, lo, k, F)
    out["eps"] = refp.sketch_eps(int(config["n_summary"]), -(-n // slab))
    out["table"] = {
        "occupied_indicators": len(occupied),
        "rarest_level_rows": int(min(
            np.bincount(np.concatenate([b[1] for b in blocks]),
                        minlength=F)[occupied]))}
    out["ingest.program"] = dict(
        checks_paged.cut_numbers(columns, ids, occupied, cuts),
        bins_mismatches=checks_paged.bins_mismatches(
            block, bins_t[:, lo:lo + k], cuts),
        rows_share=handle["n"] / float(config["rows"]),
        pages_replayed=out["pages_replayed"])
    for name, kw in (("first_slab", {"n_summary": config["n_summary"],
                                     "slabs": 1}),
                     ("n_summary_64", {"n_summary": 64})):
        thin = system_paged.sketch_cuts(pages, F, slab, config["n_bins"],
                                        **kw)
        out["ingest.control." + name] = checks_paged.cut_numbers(
            columns, ids, occupied, thin)
    lost = cuts.copy()
    lost[occupied[len(occupied) // 2]] = 2.0 + np.arange(cuts.shape[1])
    out["ingest.control.indicator_cut_dropped"] = {
        "indicator_cuts_missing": refp.unsplit_indicators(lost, occupied)}
    out["ingest.control.bfloat16_rows"] = {
        "bins_mismatches": checks_paged.bins_mismatches(
            block, ref.bin_rows(block, cuts, "bfloat16").T, cuts)}
    pages.drop()

    # -- boost: the program, then each control -----------------------------
    Xh, yh = system_paged.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    Xt = checks_paged.dense_rows(blocks, 0, m, F)
    worst = []
    out["boost.program"] = dict(
        checks_paged.boost_tree_numbers(bins_t, y, trees, config, worst),
        **checks.learning_numbers(Xt, y[:m], Xh, yh, cuts, trees, config),
        rounds_share=len(trees) / float(p["n_trees"]))
    out["not_compared"] = {"tree1.worst_leaf_gap": worst[0]}
    for control in ("bfloat16", "float8"):
        out["boost.control." + control] = checks_paged.boost_tree_numbers(
            bins_t, y, checks.control_trees(bins_t, y, trees, config,
                                            control), config)
    shifted = [dict(t, feat=(t["feat"] + 1) % F) for t in trees]
    out["boost.control.column_plus_1"] = dict(
        checks_paged.boost_tree_numbers(bins_t, y, shifted, config),
        **checks.learning_numbers(Xt, y[:m], Xh, yh, cuts, shifted, config))
    out["boost.control.four_rounds"] = dict(
        checks.learning_numbers(Xt, y[:m], Xh, yh, cuts, trees[:-1], config),
        rounds_share=(len(trees) - 1) / float(p["n_trees"]))
    del bins_t

    rows = int(config["heldout_rows"])
    Xs = system_paged.heldout_rows(ctx, rows)[0].astype(np.float32)
    model.predict(Xs[:1024])
    t = time.perf_counter()
    got = model.predict(Xs)
    out["predict"] = {"rows": rows, "call_s": time.perf_counter() - t}
    s = slice(0, 32768)
    out["boost.program"]["score_gap"] = checks.score_gap(
        [Xs[s].astype(np.float64)], [got[s]], cuts, trees, config)
    out["boost.control.bfloat16_descent"] = {"score_gap": checks.score_gap(
        [Xs[s].astype(np.float64)], [got[s]], cuts, trees, config,
        "bfloat16")}
    system.drop_handle(handle)

    # -- every set of numbers through the cells' own comparison ------------
    out["broken"] = {}
    for name in [k for k in out if k.startswith(("ingest.", "boost."))]:
        numbers = out[name]
        left = broken(boost, {k: v for k, v in numbers.items()
                              if k in boost["limits"]})
        if name.startswith("ingest."):
            left = sorted(set(left) | set(broken(ingest, numbers)))
        out["broken"][name] = left
    out["verdict"] = {
        "program_breaks": sorted(
            lim for name, left in out["broken"].items()
            if name.endswith(".program") for lim in left),
        "controls_that_break_nothing": sorted(
            name for name, left in out["broken"].items()
            if ".control." in name and not left)}
    out["seconds"] = time.perf_counter() - t0
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args()
    bench = harness.load_benchmark(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    mixes = {}
    for key, name in (("ingest", "ingest-paged"), ("boost", "boost-r5-paged")):
        with open(harness.find_file(ROOT, bench["paths"], "traffic",
                                    name + ".json")) as f:
            mixes[key] = json.load(f)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".compile_cache"))
    harness.claim_devices(int(config["chips"]), require_chip=True)
    outdir = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(outdir, exist_ok=True)
    sound = True
    with open(os.path.join(outdir, args.config + ".paged.jsonl"),
              "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = one_seed(args.config, config, mixes, seed)
            line = json.dumps(out)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
            sound = sound and not any(out["verdict"].values())
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
