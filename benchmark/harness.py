"""One run of one cell, driven by data.

``BENCHMARK.json`` names the cell's configuration and traffic mix; the
configuration is a file of sizes, the mix a file of parameters that names
the operation it repeats, the operation a module under ``ops/``, every
per-layer metric a module under ``metrics/``.  All are found by name in
the directories that ``BENCHMARK.json`` lists under ``paths`` — nothing
here knows the name of a cell, a configuration, a mix or a metric, so a
later PR adds files and entries and edits no file that is there.

A run: set-up (data from the seed, the system's state, a warm operation of
every shape the window uses) -> the measured window, a closed loop of
whole operations: an operation starts while the window is open, the one in
flight finishes and counts -> the peak memory -> ``correct`` against the
plain reference (outside the window, not counted in ``setup_s``) -> one
JSON object as the last line of standard output, every number compared
beside its limit under its last key ``compared`` and, after it, on the
last lines of standard error.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark import stats

#: where a traced run writes its profile, inside the checkout and ignored
TRACE_DIR = os.path.join("benchmark", ".out", "trace")
#: seconds of the window a traced run covers unless the mix says otherwise
TRACE_SECONDS = 10.0


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, no such cell)."""


@dataclasses.dataclass
class Ctx:
    """What one run's operation module works with."""
    root: str
    workload: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    chips: int
    state: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    comparisons: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    op_work: List[float] = dataclasses.field(default_factory=list)
    op_seconds: List[float] = dataclasses.field(default_factory=list)
    summary: Any = None                # xplane.Summary of a traced run
    device_kind: str = ""
    say: Callable[[str], None] = print

    @property
    def params(self) -> Dict[str, Any]:
        return self.mix.get("params", {})

    def compare(self, name: str, value: float, limit: float,
                passes: str = "at_most") -> bool:
        """Record one number beside its limit.  ``passes`` is ``at_most``
        (value <= limit) or ``at_least``."""
        value = float(value)
        ok = value <= limit if passes == "at_most" else value >= limit
        ok = bool(ok and value == value)           # NaN never passes
        self.comparisons.append({"name": name, "value": value,
                                 "limit": limit, "passes": passes, "ok": ok})
        return ok


# -- finding things by name ------------------------------------------------------

def load_benchmark(root: str) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_file(root: str, paths: List[str], kind: str, name: str) -> str:
    """``<path>/<kind>/<name>`` in the first of ``paths`` that has it,
    else beside this file (a checkout's own ``paths`` hold this file; the
    self-test's scratch root does not)."""
    here = os.path.dirname(os.path.abspath(__file__))
    for base in [os.path.join(root, p) for p in paths] + [here]:
        cand = os.path.join(base, kind, name)
        if os.path.isfile(cand):
            return cand
    raise FileNotFoundError(f"no {kind}/{name} under {paths} of {root}")


def load_module(path: str):
    name = "benchmark_ext_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, workload: str):
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    mix_path = find_file(root, bench["paths"], "traffic",
                         cell["traffic"] + ".json")
    with open(mix_path) as f:
        mix = json.load(f)
    return bench, cell, config, mix


def metrics_of(bench: Dict[str, Any], group: str, workload: str
               ) -> List[Dict[str, Any]]:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


# -- the device --------------------------------------------------------------------

def claim_devices(chips: int, require_chip: bool):
    """Import jax and refuse unless an accelerator with enough chips is
    there.  ``require_chip=False`` is the self-test's way in."""
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise Refused("jax found no accelerator (platform cpu): the "
                      "benchmark does not run on a CPU")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, jax found "
                      f"{len(devs)}")
    return devs


def peak_memory(devs) -> int:
    """Peak bytes in use on the fullest device, as the runtime reports."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use",
                                st.get("bytes_in_use", 0))))
    return max(peaks)


# -- tracing -----------------------------------------------------------------------

@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's own trace (nothing when no trace is
    being taken)."""
    import jax

    with jax.profiler.TraceAnnotation("bench." + name):
        yield


@contextlib.contextmanager
def device_trace(logdir: str):
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # python frames: huge, and unread
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with span("window"):
            yield
    finally:
        jax.profiler.stop_trace()


# -- the result's metrics -----------------------------------------------------------

def end_to_end_metrics(bench, ctx: Ctx, setup_s: float) -> Dict[str, Any]:
    """``setup_s`` and the mix's own end-to-end arithmetic over the
    window's operations (nothing but ``setup_s`` if none finished)."""
    values: Dict[str, float] = {"setup_s": setup_s}
    if ctx.op_seconds:
        for name, spec in ctx.mix["end_to_end"].items():
            values[name] = stats.end_to_end(spec, ctx.op_work, ctx.op_seconds)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(bench, "end_to_end", ctx.workload)
            if m["name"] in values}


def per_layer_metrics(bench, ctx: Ctx, logdir: str) -> Dict[str, Any]:
    """Reduce the trace, then ask each of the cell's per-layer readers; a
    reader that finds nothing to read returns None and is left out."""
    from benchmark import xplane

    ctx.summary = xplane.summarize(xplane.load(xplane.newest_xplane(logdir)))
    out = {}
    for m in metrics_of(bench, "per_layer", ctx.workload):
        reader = load_module(find_file(ctx.root, bench["paths"], "metrics",
                                       m["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def comparison_line(name: str, c: Dict[str, Any]) -> str:
    """One number compared, beside its limit, as a run prints it."""
    return (f"[correct] {name}: {c['value']!r} ({c['passes']} "
            f"{c['limit']!r}) {'ok' if c['ok'] else 'NOT OK'}")


# -- one run -----------------------------------------------------------------------

def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True,
             say: Callable[[str], None] = lambda s: print(s, flush=True),
             t_start: Optional[float] = None) -> Dict[str, Any]:
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, config, mix = load_cell(root, workload)
    # the compile cache: where the machine says, else one fixed directory
    # inside this checkout (the path is part of the cache's key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".compile_cache"))
    devs = claim_devices(int(cell["chips"]), require_chip)
    ctx = Ctx(root=root, workload=workload, config=config, mix=mix,
              seed=int(seed), chips=int(cell["chips"]),
              device_kind=devs[0].device_kind, say=say)
    opmod = load_module(find_file(root, bench["paths"], "ops",
                                  mix["op"] + ".py"))
    say(f"[bench] {workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"on {len(devs)} x {devs[0].device_kind}")

    from benchmark import system

    opmod.setup(ctx)
    compiles_before = system.compile_events()
    ctx.counters["compile.cache_misses"] = compiles_before["misses"]
    setup_s = time.perf_counter() - t_start
    say(f"[bench] set-up {setup_s:.3f} s; compile cache {compiles_before}")

    window_s = float(seconds)
    if trace:
        window_s = min(window_s, float(mix.get("trace_seconds",
                                               TRACE_SECONDS)))
    logdir = os.path.join(root, TRACE_DIR, workload)
    attempted = failed = 0
    with (device_trace(logdir) if trace else contextlib.nullcontext()):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < window_s:
            attempted += 1
            t_op = time.perf_counter()
            try:
                with span("op"):
                    work = opmod.op(ctx, attempted - 1)
            except Exception as e:  # noqa: BLE001 - counted, run is not correct
                failed += 1
                say(f"[bench] op {attempted - 1} FAILED: "
                    f"{type(e).__name__}: {e}")
                continue
            ctx.op_seconds.append(time.perf_counter() - t_op)
            ctx.op_work.append(float(work))
    compiles_after = system.compile_events()
    if hasattr(opmod, "finish"):
        opmod.finish(ctx)
    memory_peak = peak_memory(devs)
    say(f"[bench] window: {attempted} ops, {failed} failed, "
        f"{sum(ctx.op_seconds):.3f} s in ops; peak memory "
        f"{memory_peak / 2**30:.3f} GiB")

    # correct: the timed path's own outputs against the plain reference
    t_check = time.perf_counter()
    ctx.compare("window.compiles",
                sum(compiles_after.values()) - sum(compiles_before.values()),
                0)
    ctx.compare("ops.failed", failed, 0)
    if ctx.op_seconds:
        try:
            opmod.check(ctx)
        except Exception as e:  # noqa: BLE001 - a check that cannot run fails
            say(f"[bench] check raised {type(e).__name__}: {e}")
            ctx.compare("check.raised", 1, 0)
    for c in ctx.comparisons:
        say(comparison_line(c["name"], c))
    correct = bool(ctx.op_seconds) and all(c["ok"] for c in ctx.comparisons)
    say(f"[bench] check took {time.perf_counter() - t_check:.3f} s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed}
    if not trace:
        out["metrics"] = end_to_end_metrics(bench, ctx, setup_s)
    else:
        t_red = time.perf_counter()
        out["metrics"] = per_layer_metrics(bench, ctx, logdir)
        device["busy_s"] = ctx.summary.busy_s
        device["window_s"] = ctx.summary.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.summary.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in ctx.summary.top_gaps(10)]}
        say(f"[bench] trace reduced in {time.perf_counter() - t_red:.3f} s")
    out["device"] = device
    # every number compared beside its limit, last in the line
    out["compared"] = {c["name"]: {k: c[k] for k in ("value", "limit",
                                                     "passes", "ok")}
                       for c in ctx.comparisons}
    return out


def main(argv: Optional[List[str]] = None, root: Optional[str] = None,
         t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    # ... and as the last lines of standard error
    for name, c in out["compared"].items():
        print(comparison_line(name, c), file=sys.stderr)
    return 0
