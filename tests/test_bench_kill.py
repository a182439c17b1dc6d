"""bench.py survivability: the official record must exist no matter how
the process dies (VERDICT r3 #1 — two consecutive rounds produced an
empty/blind official capture).

Each test launches bench.py as a real subprocess (BENCH_FORCE_CPU asks
for the CPU self-test run), kills it at a chosen point, and asserts the LAST
stdout line — the driver's parse target — is a complete JSON record with
a usable rate.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def _env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)        # conftest's 8-device forcing would
    env.pop("JAX_PLATFORMS", None)    # fight BENCH_FORCE_CPU's own setup
    env.update({
        "BENCH_FORCE_CPU": "1",
        "BENCH_ROWS": "20000",
        "BENCH_FEATURES": "28",
        "BENCH_WARMUP": "1",
        "BENCH_DEPTH": "6",
        "BENCH_BINS": "256",
    })
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _spawn(**extra):
    return subprocess.Popen(
        [sys.executable, _BENCH], env=_env(**extra),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=_REPO)


def _read_until_chunk(proc, timeout=240):
    """Collect stdout lines until one carries timed-chunk evidence."""
    lines = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("chunks_so_far"):
            return lines, rec
    raise AssertionError(
        f"no timed-chunk line within {timeout}s; got: {lines[-3:]}")


def _drain(proc, timeout=60):
    try:
        rest, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        rest, _ = proc.communicate()
    return rest


def _last_record(all_text):
    lines = [ln for ln in all_text.splitlines() if ln.strip()]
    assert lines, "no stdout at all"
    return json.loads(lines[-1])


@pytest.mark.slow
class TestBenchSurvivesKill:
    def test_sigterm_mid_fit_flushes_record(self):
        # enough rounds that the fit is still going when we fire
        proc = _spawn(BENCH_ROUNDS=500, BENCH_TIME_BUDGET=600)
        lines, _ = _read_until_chunk(proc)
        proc.send_signal(signal.SIGTERM)
        rest = _drain(proc)
        rec = _last_record("".join(lines) + rest)
        assert rec["metric"] == "histgbt_rounds_per_sec_per_chip"
        assert rec["terminated"] == "SIGTERM"
        assert rec["value"] > 0           # evidence-so-far, not empty
        assert rec["unit"] == "rounds/s/chip"
        assert "vs_baseline" in rec

    def test_sigkill_mid_fit_leaves_valid_last_line(self):
        # SIGKILL cannot be handled: the per-chunk provisional lines ARE
        # the survival mechanism here
        proc = _spawn(BENCH_ROUNDS=500, BENCH_TIME_BUDGET=600)
        lines, rec_seen = _read_until_chunk(proc)
        proc.kill()
        rest = _drain(proc)
        rec = _last_record("".join(lines) + rest)
        assert rec["metric"] == "histgbt_rounds_per_sec_per_chip"
        assert rec["value"] > 0
        assert rec["provisional"] is True
        assert rec_seen["chunks_so_far"]

    def test_budget_exhaustion_flushes_and_exits_zero(self):
        # budget expires mid-fit (the config is never shrunk to fit):
        # the watchdog thread must flush and exit 0 well before the
        # outer 240s cap
        proc = _spawn(BENCH_ROUNDS=2000, BENCH_TIME_BUDGET=30)
        try:
            out, _ = proc.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise AssertionError("watchdog did not enforce the budget")
        rec = _last_record(out)
        assert rec["terminated"] == "budget_exhausted"
        assert proc.returncode == 0

    def test_clean_run_final_line(self):
        proc = _spawn(BENCH_ROUNDS=50, BENCH_WARMUP=2,
                      BENCH_TIME_BUDGET=220)
        out, _ = proc.communicate(timeout=240)
        rec = _last_record(out)
        assert rec["provisional"] is False
        assert rec["phase"] == "done"
        assert rec["value"] > 0
        assert rec["anomaly"] is False
        assert rec["platform"] == "cpu" and rec["mfu"] is None
        assert proc.returncode == 0

    def test_default_run_without_a_tpu_exits_nonzero(self):
        # no BENCH_FORCE_CPU: a CPU host must not produce a record that
        # reads like a benchmark
        env = _env()
        del env["BENCH_FORCE_CPU"]
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen(
            [sys.executable, _BENCH], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, cwd=_REPO)
        out, _ = proc.communicate(timeout=240)
        rec = _last_record(out)
        assert proc.returncode == 2
        assert "no TPU" in rec["error"] and rec["value"] == 0.0
