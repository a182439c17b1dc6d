#!/usr/bin/env python3
"""The controls and one scoring call, on the chip, at a WIDE
configuration's own size (``control_on_chip.py`` draws a 2^20-row block
for its ingest check, more rows than a wide table has, and boosts 100
rounds where the wide cell's mix boosts 25):

    chiprun -- python3 benchmark/tests/wide_on_chip.py \
        --config epsilon-400k-d6 --mix boost-r25 --seeds 11,12,13

Not a test pytest collects and not part of a benchmark run.  One process,
one ingest and one fit of the mix's rounds per seed, as the cell makes
them; then, from that one state:

* the numbers ``ops/boost.py::check`` compares, for the program and with
  each control in its place (``bfloat16`` sums, ``float8`` gradients,
  ``bfloat16`` rows into the binning, ``bfloat16`` cuts; half the rounds
  for the two learning numbers), each beside the mix's limit;
* one ``predict`` of the configuration's ``heldout_rows`` against
  ``checks.score_gap`` and its ``bfloat16`` control, the call's wall and
  (with ``--trace 1``) the device's busy share of it;
* ``device.memory_peak_bytes`` after the ingest and after the fit.

One JSON line per seed on standard output and in
``chiprun_out/control/<config>.wide.jsonl``.
"""

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import checks, harness, reference as ref, system  # noqa: E402


def one_seed(config_name: str, config: dict, mix: dict, seed: int,
             trace: bool) -> dict:
    import jax

    t0 = time.perf_counter()
    p = mix["params"]
    ctx = harness.Ctx(root=ROOT, workload="control", config=config, mix=mix,
                      seed=seed, chips=int(config["chips"]))
    X, y = system.training_rows(ctx)
    n = len(y)
    model = system.new_model(ctx, p["n_trees"])
    handle = system.ingest(model, X, y)
    peak_ingest = harness.peak_memory(jax.devices())
    model.fit_device(handle)
    peak_fit = harness.peak_memory(jax.devices())
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    bins_t = np.asarray(handle["bins_t"])[:, :n]
    out = {"config": config_name, "seed": seed, "rows": n,
           "features": int(X.shape[1]), "rounds": len(trees),
           "memory_peak_gib": {"after_ingest": peak_ingest / 2**30,
                               "after_fit": peak_fit / 2**30},
           "fit_seconds": model.last_fit_seconds,
           "round_plan": model.round_plan, "limits": mix["limits"]}

    # boost: the program's trees, then each control's in their place
    Xh, yh = system.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    out["boost.program"] = dict(
        checks.boost_tree_numbers(bins_t, y, trees, config),
        **checks.learning_numbers(X[:m], y[:m], Xh, yh, cuts, trees, config))
    for prec in ("bfloat16", "float8"):
        out["boost.control." + prec] = checks.boost_tree_numbers(
            bins_t, y, checks.control_trees(bins_t, y, trees, config, prec),
            config)
    # ... and a fit that stopped halfway, for the two learning limits
    out["boost.control.half_rounds"] = checks.learning_numbers(
        X[:m], y[:m], Xh, yh, cuts, trees[:len(trees) // 2], config)

    # the binned matrix: a seeded block of rows, then both ingest controls
    rng = np.random.default_rng(seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    feats = sorted(rng.choice(X.shape[1], size=3, replace=False).tolist())
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

    # score: the held-out table in one call (warm first: it compiles)
    Xs, _ys = system.heldout_rows(ctx, int(config["heldout_rows"]))
    model.predict(Xs)
    logdir = os.path.join(ROOT, harness.TRACE_DIR, "wide_on_chip")
    with (harness.device_trace(logdir) if trace
          else contextlib.nullcontext()):
        walls = []
        for _ in range(3):
            t = time.perf_counter()
            got = model.predict(Xs)
            walls.append(time.perf_counter() - t)
    score = {"rows": len(Xs), "call_ms": [1e3 * w for w in walls]}
    if trace:
        from benchmark import xplane

        s = xplane.summarize(xplane.load(xplane.newest_xplane(logdir)))
        score["device_busy_ms_per_call"] = 1e3 * s.busy_s / len(walls)
        score["device_ops"] = [[nm, sec] for nm, sec in s.top_ops(6)]
    sample = slice(0, 16384)
    control = ref.sigmoid(ref.ensemble_margin(
        Xs[sample], cuts, trees, float(config["base_score"]), "bfloat16"))
    score["score_gap"] = checks.score_gap([Xs], [got], cuts, trees, config)
    score["score_gap.control.bfloat16"] = checks.score_gap(
        [Xs[sample]], [control], cuts, trees, config)
    out["score"] = score
    out["seconds"] = time.perf_counter() - t0
    system.drop_handle(handle)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
    with open(os.path.join(outdir, args.config + ".wide.jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(one_seed(args.config, config, mix, seed,
                                       bool(args.trace)))
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
