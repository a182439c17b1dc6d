"""dmlcheck (static analyzer) + lockcheck (dynamic verifier) contracts.

Each static pass gets golden fixture snippets: at least one that MUST
flag and one that must stay clean, so a pass that silently dies (or
silently over-matches) fails here before it lies in CI.  Fixtures are
written into a throwaway mini-repo layout (the walker scans the same
directory names as the real one) — nothing is imported, only parsed.
"""

from __future__ import annotations

import os
import textwrap
import threading
import time

import pytest

from dmlc_core_tpu.analysis import analyze, load_baseline, write_baseline
from dmlc_core_tpu.base import lockcheck


def _mini_repo(tmp_path, files, docs=None, knobs=()):
    """Lay out {relpath: source} plus an optional doc set and a knob
    registry; returns the root to hand to analyze()."""
    root = tmp_path / "repo"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    knob_lines = ["def declare(*a, **k):\n    pass\n"] + [
        f'declare("{name}", "", "doc")\n' for name in knobs]
    kp = root / "dmlc_core_tpu" / "base" / "knobs.py"
    if not kp.exists():
        kp.parent.mkdir(parents=True, exist_ok=True)
        kp.write_text("".join(knob_lines))
    for rel, text in (docs or {}).items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return str(root)


def _findings(ctx, rule=None):
    return [f for f in ctx.findings if rule is None or f.rule == rule]


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

_LOCKED_CLASS_BAD = """
    import threading

    class Shared:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def add(self, v):
            with self._lock:
                self._items.append(v)

        def peek(self):
            return self._items[-1]      # unguarded read of locked state
"""

_LOCKED_CLASS_GOOD = """
    import threading

    class Shared:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            self._config = 3            # never locked -> never flagged

        def add(self, v):
            with self._lock:
                self._items.append(v)

        def peek(self):
            with self._lock:
                return self._items[-1]

        def _drain_locked(self):
            # *_locked convention: caller holds the lock
            out = list(self._items)
            self._items.clear()
            return out

        def scale(self):
            return self._config * 2
"""


def test_lock_discipline_flags_unguarded_access(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _LOCKED_CLASS_BAD}),
                  rules=["lock-discipline"])
    got = _findings(ctx, "lock-discipline")
    assert len(got) == 1 and "Shared._items" in got[0].message
    assert got[0].key == "Shared._items:peek"


def test_lock_discipline_clean_class_and_locked_convention(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _LOCKED_CLASS_GOOD}),
                  rules=["lock-discipline"])
    assert _findings(ctx) == []


def test_lock_discipline_ignores_code_outside_package(tmp_path):
    # the pass hunts product code, not test fixtures/scripts
    ctx = analyze(_mini_repo(tmp_path,
                             {"scripts/tool.py": _LOCKED_CLASS_BAD}),
                  rules=["lock-discipline"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# lock-release
# ---------------------------------------------------------------------------

_ACQUIRE_BAD = """
    import threading
    _lk = threading.Lock()

    def leaky():
        _lk.acquire()
        do_work()
        _lk.release()
"""

_ACQUIRE_GOOD = """
    import threading
    _lk = threading.Lock()

    def safe():
        _lk.acquire()
        try:
            do_work()
        finally:
            _lk.release()
"""


def test_lock_release_flags_missing_try_finally(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _ACQUIRE_BAD}),
                  rules=["lock-release"])
    got = _findings(ctx, "lock-release")
    assert len(got) == 1 and "try/finally" in got[0].message


def test_lock_release_accepts_try_finally(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _ACQUIRE_GOOD}),
                  rules=["lock-release"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

_JIT_BAD = """
    import os
    import time
    import jax

    def _helper(x):
        return x * float(os.environ.get("SCALE", "1"))

    @jax.jit
    def kernel(x):
        return _helper(x) + time.time()

    _log = []

    def stepper(x):
        _log.append(1)
        return x + 1

    step = jax.jit(stepper)
"""

_JIT_GOOD = """
    import os
    import jax
    import jax.numpy as jnp

    CFG = float(os.environ.get("SCALE", "1"))   # read at import, fine

    @jax.jit
    def kernel(x):
        def inner(c, v):
            return c + v * CFG, None
        total, _ = jax.lax.scan(inner, jnp.zeros(()), x)
        return total
"""


def test_jit_purity_flags_env_clock_and_closure_mutation(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _JIT_BAD}),
                  rules=["jit-purity"])
    msgs = [f.message for f in _findings(ctx, "jit-purity")]
    assert any("os.environ" in m and "via _helper" in m for m in msgs), msgs
    assert any("clock" in m for m in msgs), msgs
    assert any("mutates closed-over '_log'" in m for m in msgs), msgs


def test_jit_purity_clean_kernel(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _JIT_GOOD}),
                  rules=["jit-purity"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# knob registry
# ---------------------------------------------------------------------------

_KNOB_USE = """
    import os
    FLAG = os.environ.get("DMLC_FIXTURE_FLAG", "0")
"""


def test_knob_registry_flags_undeclared(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _KNOB_USE}),
                  rules=["knob-registry"])
    got = _findings(ctx, "knob-registry")
    assert len(got) == 1 and got[0].key == "DMLC_FIXTURE_FLAG"


def test_knob_registry_and_doc_clean_when_declared_and_documented(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": _KNOB_USE},
                      docs={"doc/configuration.md":
                            "| `DMLC_FIXTURE_FLAG` | ... |\n"},
                      knobs=["DMLC_FIXTURE_FLAG"])
    ctx = analyze(root, rules=["knob-registry", "knob-doc"])
    assert _findings(ctx) == []


def test_knob_doc_flags_undocumented_declaration(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": _KNOB_USE},
                      knobs=["DMLC_FIXTURE_FLAG"])
    ctx = analyze(root, rules=["knob-doc"])
    got = _findings(ctx, "knob-doc")
    assert len(got) == 1 and got[0].path.endswith("knobs.py")


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------

_METRIC_A = """
    def mod_metrics(r):
        return r.counter("widget_total", "widgets", labels=("kind",))
"""

_METRIC_B_CONFLICT = """
    def other_metrics(r):
        return r.counter("widget_total", "widgets", labels=("color",))
"""


def test_metric_registry_flags_label_conflict(tmp_path):
    root = _mini_repo(tmp_path, {
        "dmlc_core_tpu/a.py": _METRIC_A,
        "dmlc_core_tpu/b.py": _METRIC_B_CONFLICT,
    }, docs={"doc/observability.md": "`dmlc_widget_total`\n"})
    ctx = analyze(root, rules=["metric-registry", "metric-doc"])
    got = _findings(ctx, "metric-registry")
    assert len(got) == 1 and "re-declared" in got[0].message
    assert _findings(ctx, "metric-doc") == []


def test_metric_registry_identical_redeclaration_ok_and_doc_flags(tmp_path):
    root = _mini_repo(tmp_path, {
        "dmlc_core_tpu/a.py": _METRIC_A,
        "dmlc_core_tpu/b.py": _METRIC_A.replace("mod_", "other_"),
    })
    ctx = analyze(root, rules=["metric-registry", "metric-doc"])
    assert _findings(ctx, "metric-registry") == []
    got = _findings(ctx, "metric-doc")
    assert len(got) == 1 and got[0].key == "dmlc_widget_total"


# ---------------------------------------------------------------------------
# style / unused imports (the folded lint.py)
# ---------------------------------------------------------------------------

def test_style_and_unused_import(tmp_path):
    src = ("import os\n"
           "import sys  # noqa\n"
           "X = 1   \n")
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["unused-import", "style", "syntax"])
    rules = sorted(f.rule for f in ctx.findings)
    assert rules == ["style", "unused-import"]   # noqa respected
    assert any("trailing whitespace" in f.message for f in ctx.findings)


def test_syntax_error_reported_not_crashed(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": "def broken(:\n"}))
    got = _findings(ctx, "syntax")
    assert len(got) == 1


# ---------------------------------------------------------------------------
# suppression + baseline round-trip
# ---------------------------------------------------------------------------

def test_inline_suppression(tmp_path):
    src = _LOCKED_CLASS_BAD.replace(
        "return self._items[-1]      # unguarded read of locked state",
        "return self._items[-1]  # dmlcheck: off:lock-discipline")
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["lock-discipline"])
    assert _findings(ctx) == []
    assert ctx.suppressed_count == 1


def test_file_level_suppression(tmp_path):
    src = "# dmlcheck: off\n" + textwrap.dedent(_LOCKED_CLASS_BAD)
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["lock-discipline"])
    assert _findings(ctx) == [] and ctx.suppressed_count == 1


def test_unknown_suppression_rule_is_loud(tmp_path):
    src = "x = 1  # dmlcheck: off:not-a-rule\n"
    with pytest.raises(ValueError, match="unknown dmlcheck rule"):
        analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}))


def test_docstring_mentioning_grammar_does_not_suppress(tmp_path):
    src = '"""Docs: use ``# dmlcheck: off`` to suppress."""\n' \
          + textwrap.dedent(_LOCKED_CLASS_BAD)
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["lock-discipline"])
    assert len(_findings(ctx, "lock-discipline")) == 1


def test_baseline_round_trip_and_line_drift(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": _LOCKED_CLASS_BAD})
    ctx = analyze(root, rules=["lock-discipline"])
    assert len(ctx.findings) == 1
    bp = str(tmp_path / "baseline.json")
    write_baseline(bp, ctx.findings)
    baseline = load_baseline(bp)
    assert [f for f in ctx.findings
            if f.fingerprint not in baseline] == []
    # insert lines ABOVE the finding: lineno moves, fingerprint must not
    mod = os.path.join(root, "dmlc_core_tpu", "mod.py")
    with open(mod) as f:
        drifted = "# a comment\n# another\n" + f.read()
    with open(mod, "w") as f:
        f.write(drifted)
    ctx2 = analyze(root, rules=["lock-discipline"])
    assert len(ctx2.findings) == 1
    assert ctx2.findings[0].line != ctx.findings[0].line
    assert ctx2.findings[0].fingerprint in baseline


def test_repo_is_clean_under_committed_baseline():
    """The acceptance gate itself: the real repo, the real baseline."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ctx = analyze(root)
    baseline = load_baseline(
        os.path.join(root, "scripts", "dmlcheck_baseline.json"))
    live = [f for f in ctx.findings if f.fingerprint not in baseline]
    assert live == [], "\n".join(f.render() for f in live)
    # baseline discipline: base/, serve/, tracker/ must not be
    # grandfathered — their findings get FIXED (ISSUE 5 satellite)
    for fp in baseline:
        assert not fp.startswith(("dmlc_core_tpu/base/",
                                  "dmlc_core_tpu/serve/",
                                  "dmlc_core_tpu/tracker/")), fp


# ---------------------------------------------------------------------------
# lockcheck: the dynamic side
# ---------------------------------------------------------------------------

@pytest.fixture
def traced():
    installed_before = lockcheck.installed()
    if not installed_before:
        lockcheck.install()
    yield
    if not installed_before:
        lockcheck.uninstall()
    lockcheck.reset()


def test_lockcheck_detects_inverted_pair(traced):
    a = threading.Lock()
    b = threading.Lock()

    def ab():
        with a:
            time.sleep(0.005)
            with b:
                pass

    def ba():
        with b:
            time.sleep(0.005)
            with a:
                pass

    for fn in (ab, ba):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    assert lockcheck.violations(), "inverted lock order not detected"
    with pytest.raises(lockcheck.LockOrderError):
        lockcheck.check()


def test_lockcheck_consistent_order_is_clean(traced):
    a = threading.Lock()
    b = threading.Lock()

    def ab():
        with a:
            with b:
                pass

    ts = [threading.Thread(target=ab) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert lockcheck.violations() == []
    lockcheck.check()   # must not raise


def test_lockcheck_condition_queue_integration(traced):
    """Traced plain Locks must survive Condition wait/notify — the
    ConcurrentBlockingQueue path every producer/consumer rides."""
    from dmlc_core_tpu.io.concurrency import ConcurrentBlockingQueue

    q = ConcurrentBlockingQueue(max_size=2)
    got = []

    def consumer():
        for _ in range(20):
            got.append(q.pop(timeout=5.0))

    t = threading.Thread(target=consumer)
    t.start()
    for i in range(20):
        q.push(i, timeout=5.0)
    t.join()
    assert got == list(range(20))
    assert lockcheck.violations() == []


def test_lockcheck_rlock_condition_wait(traced):
    """Default Condition() (RLock inside) exercises the
    _release_save/_acquire_restore protocol on the traced wrapper."""
    cond = threading.Condition()
    ready = []

    def waiter():
        with cond:
            while not ready:
                cond.wait(timeout=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)
    with cond:
        ready.append(1)
        cond.notify_all()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert lockcheck.violations() == []


def test_lockcheck_env_gate(monkeypatch):
    monkeypatch.setenv("DMLC_LOCKCHECK", "1")
    assert lockcheck.env_enabled()
    monkeypatch.setenv("DMLC_LOCKCHECK", "0")
    assert not lockcheck.env_enabled()


# ---------------------------------------------------------------------------
# lock-blocking (ISSUE 11): blocking calls while a lock is held
# ---------------------------------------------------------------------------

_BLOCKING_BAD = """
    import threading
    import time

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._done = threading.Event()
            self._jobs = []

        def step(self):
            with self._lock:
                time.sleep(1.0)         # world stops with you

        def push_locked(self, sock):
            data = sock.recv(4096)      # network time under the lock
            self._jobs.append(data)

        def drain(self, work_queue):
            with self._lock:
                return work_queue.get()     # untimed queue op

        def settle(self):
            with self._lock:
                self._done.wait()       # Event.wait releases NOTHING
"""

_BLOCKING_GOOD = """
    import threading
    import time

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)
            self._jobs = []

        def step(self):
            with self._lock:
                jobs = list(self._jobs)
            time.sleep(0.1)             # sleep OUTSIDE the lock
            return jobs

        def wait_ready(self):
            with self._cv:
                self._cv.wait()         # own condvar: releases monitor

        def bounded(self, work_queue, ev):
            with self._lock:
                item = work_queue.get(timeout=1.0)   # bounded
                ev.wait(0.5)                         # bounded
                return item
"""


def test_lock_blocking_flags_sleep_socket_queue_wait(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _BLOCKING_BAD}),
                  rules=["lock-blocking"])
    got = _findings(ctx, "lock-blocking")
    whats = sorted(f.key.split(":")[-1] for f in got)
    assert whats == ["queue.get", "socket.recv", "time.sleep", "wait"]


def test_lock_blocking_clean_patterns(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _BLOCKING_GOOD}),
                  rules=["lock-blocking"])
    assert _findings(ctx, "lock-blocking") == []


def test_lock_blocking_skips_lockless_classes(tmp_path):
    src = """
        import time

        class Free:
            def nap(self):
                time.sleep(1.0)     # no lock attrs -> out of scope
    """
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["lock-blocking"])
    assert _findings(ctx, "lock-blocking") == []


# ---------------------------------------------------------------------------
# atomicity (ISSUE 11): unlocked compounds on mixed-locking attributes
# ---------------------------------------------------------------------------

_ATOMICITY_BAD = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            self._open = False

        def snapshot(self):
            with self._lock:
                return self._n, self._open

        def bump(self):
            self._n += 1            # unlocked RMW: updates lost

        def close_once(self):
            if self._open:
                self._open = False  # unlocked check-then-act
"""

_ATOMICITY_GOOD = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            self._hits = 0          # never locked -> lock-free by design

        def snapshot(self):
            with self._lock:
                return self._n

        def bump(self):
            with self._lock:
                self._n += 1        # compound under the lock

        def hit(self):
            self._hits += 1

        def _drain_locked(self):
            self._n += 1            # *_locked: caller holds the lock
"""


def test_atomicity_flags_unlocked_rmw_and_check_then_act(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _ATOMICITY_BAD}),
                  rules=["atomicity"])
    got = _findings(ctx, "atomicity")
    kinds = sorted((f.key.split(":")[0], f.key.split(":")[-1])
                   for f in got)
    assert kinds == [("Counter._n", "rmw"),
                     ("Counter._open", "check-then-act")]
    assert all("not atomic" in f.message for f in got)


def test_atomicity_clean_locked_compounds_and_lockfree_attrs(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _ATOMICITY_GOOD}),
                  rules=["atomicity"])
    assert _findings(ctx, "atomicity") == []


def test_atomicity_suppression(tmp_path):
    src = _ATOMICITY_BAD.replace(
        "self._n += 1            # unlocked RMW: updates lost",
        "self._n += 1  # dmlcheck: off:atomicity")
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["atomicity"])
    assert len(_findings(ctx, "atomicity")) == 1    # the other one
    assert ctx.suppressed_count == 1


# ---------------------------------------------------------------------------
# resource-leak (ISSUE 15): acquisition shapes for OS handles
# ---------------------------------------------------------------------------

_RESOURCE_BAD = """
    import socket
    import subprocess
    import tempfile

    def probe(host):
        s = socket.socket()
        s.connect((host, 80))
        data = s.recv(1)        # s never closed/transferred
        return data

    def fire():
        subprocess.Popen(["sleep", "1"])    # bare: only handle discarded

    def scratch(blob):
        fd, path = tempfile.mkstemp()
        record(path, blob)      # fd leaks (path escaped, fd did not)

    class Holder:
        def start(self):
            self._sock = socket.create_connection(("h", 80))
        # no close/stop/shutdown/__del__ anywhere in the class
"""

_RESOURCE_GOOD = """
    import socket
    import subprocess
    import os
    import tempfile

    def probe(host):
        with socket.create_connection((host, 80)) as s:
            return s.recv(1)

    def connect(host):
        s = socket.socket()
        s.connect((host, 80))
        return s                # ownership transferred to the caller

    def spawn(cmd, registry):
        p = subprocess.Popen(cmd)
        registry.track(p)       # handed to an owner
        return p.pid

    def scratch(blob):
        fd, path = tempfile.mkstemp()
        os.close(fd)
        return path

    class Holder:
        def start(self):
            self._sock = socket.create_connection(("h", 80))

        def close(self):        # registered teardown owns self._sock
            self._sock.close()
"""


def test_resource_leak_flags_unreleased_shapes(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _RESOURCE_BAD}),
                  rules=["resource-leak"])
    keys = sorted(f.key for f in _findings(ctx, "resource-leak"))
    assert keys == ["Holder.start:self._sock", "fire:bare-subprocess",
                    "probe:s", "scratch:fd"]
    assert any("declares no teardown" in f.message for f in ctx.findings)


def test_resource_leak_clean_lifecycle_shapes(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _RESOURCE_GOOD}),
                  rules=["resource-leak"])
    assert _findings(ctx) == []


def test_resource_leak_suppression(tmp_path):
    src = _RESOURCE_BAD.replace(
        'subprocess.Popen(["sleep", "1"])    # bare: only handle discarded',
        'subprocess.Popen(["sleep", "1"])  # dmlcheck: off:resource-leak')
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["resource-leak"])
    assert len(_findings(ctx, "resource-leak")) == 3
    assert ctx.suppressed_count == 1


# ---------------------------------------------------------------------------
# thread-lifecycle (ISSUE 15): joinable-and-joined, or daemon-and-lockfree
# ---------------------------------------------------------------------------

_THREAD_BAD = """
    import threading

    class Server:
        def __init__(self):
            self._t = None

        def start(self):
            self._t = threading.Thread(target=self._loop)
            self._t.start()     # no method of Server ever joins it

        def _loop(self):
            pass

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()

        def kick(self):
            t = threading.Thread(target=self._work, daemon=True)
            t.start()           # daemon, but _work takes self._lock

        def _work(self):
            with self._lock:
                pass

    def fire_and_forget(fn):
        threading.Thread(target=fn).start()     # never joinable
"""

_THREAD_GOOD = """
    import threading

    class Server:
        def __init__(self):
            self._t = None

        def start(self):
            self._t = threading.Thread(target=self._loop)
            self._t.start()

        def close(self):
            self._t.join(timeout=2.0)   # bounded join in teardown

        def _loop(self):
            pass

    class Beacon:
        def kick(self):
            t = threading.Thread(target=self._ping, daemon=True)
            t.start()           # daemon AND lock-free: allowed

        def _ping(self):
            pass

    def batch(fns):
        ts = [threading.Thread(target=f) for f in fns]
        for t in ts:
            t.start()
        for t in ts:
            t.join()            # comp joined via the loop var
        return ts
"""


def test_thread_lifecycle_flags_unjoined_and_daemon_lockers(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _THREAD_BAD}),
                  rules=["thread-lifecycle"])
    keys = sorted(f.key for f in _findings(ctx, "thread-lifecycle"))
    assert keys == ["Pool.kick:t", "Server.start:self._t",
                    "fire_and_forget:chain-thread"]
    assert any("acquires the class's locks" in f.message
               for f in ctx.findings)


def test_thread_lifecycle_clean_join_daemon_and_comp_shapes(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _THREAD_GOOD}),
                  rules=["thread-lifecycle"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# collective-discipline (ISSUE 15): rank-invariant collective order
# ---------------------------------------------------------------------------

_COLLECTIVE_BAD = """
    def save(coll, rank, model):
        if rank == 0:
            write(model)
            coll.barrier("ckpt")    # ranks != 0 never arrive
"""

_COLLECTIVE_GOOD = """
    def save(coll, rank, model):
        if rank == 0:
            write(model)
        coll.barrier("ckpt")        # every rank arrives

    def broadcast(coll, rank, v):
        # transport implementations branch on rank by definition
        if rank == 0:
            coll.bcast(v)
        return coll.recv()

    def report(rank, log):
        if rank == 0:
            log.commit_msg()        # commit_msg is not 'commit'
"""


def test_collective_discipline_flags_rank_conditional_barrier(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _COLLECTIVE_BAD}),
                  rules=["collective-discipline"])
    got = _findings(ctx, "collective-discipline")
    assert len(got) == 1 and got[0].key == "save:barrier"
    assert "rank-conditional" in got[0].message


def test_collective_discipline_clean_hoisted_and_transport_exempt(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _COLLECTIVE_GOOD}),
                  rules=["collective-discipline"])
    assert _findings(ctx) == []


def test_collective_discipline_suppression_with_rationale(tmp_path):
    src = _COLLECTIVE_BAD.replace(
        'coll.barrier("ckpt")    # ranks != 0 never arrive',
        'coll.barrier("ckpt")  # dmlcheck: off:collective-discipline')
    ctx = analyze(_mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": src}),
                  rules=["collective-discipline"])
    assert _findings(ctx) == [] and ctx.suppressed_count == 1


# ---------------------------------------------------------------------------
# wire-schema (ISSUE 15): the registry is the wire contract
# ---------------------------------------------------------------------------

_WIRE_REGISTRY = """
    COMMANDS = {
        "ping": frozenset({"cmd", "token"}),
        "bye": frozenset({"cmd"}),
    }
    WIRE_FRAMING = frozenset({"arrays"})
    ENV_ABI = frozenset({"DMLC_TASK_ID"})
"""

_WIRE_BAD = """
    def send(conn, tok, c):
        conn.request({"cmd": "ping", "token": tok, "extra": 1})
        conn.request({"cmd": "nope"})
        conn.request({"cmd": c, "mystery": tok})
"""

_WIRE_GOOD = """
    def send(conn, tok, c, blob):
        conn.request({"cmd": "ping", "token": tok})
        conn.request({"cmd": "bye", "arrays": blob})    # framing key
        conn.request({"cmd": c, "token": tok})          # dynamic, in vocab
        route({"command": "free-form"})  # no "cmd" key: not a wire dict
"""


def _wire_repo(tmp_path, files, registry=_WIRE_REGISTRY):
    files = dict(files)
    if registry is not None:
        files["dmlc_core_tpu/base/wire_schemas.py"] = registry
    return _mini_repo(tmp_path, files)


def test_wire_schema_flags_unknown_cmd_key_and_dynamic(tmp_path):
    ctx = analyze(_wire_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _WIRE_BAD}),
                  rules=["wire-schema"])
    keys = sorted(f.key for f in _findings(ctx, "wire-schema"))
    assert keys == ["cmd:nope", "dynamic.mystery", "ping.extra"]


def test_wire_schema_clean_declared_framing_and_dynamic(tmp_path):
    ctx = analyze(_wire_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _WIRE_GOOD}),
                  rules=["wire-schema"])
    assert _findings(ctx) == []


def test_wire_schema_missing_registry_is_loud(tmp_path):
    ctx = analyze(_wire_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _WIRE_GOOD},
                             registry=None),
                  rules=["wire-schema"])
    got = _findings(ctx, "wire-schema")
    assert got and all(f.key == "registry-missing" for f in got)


_ENV_INJECT = """
    def inject(env):
        env["DMLC_TASK_ID"] = "0"           # declared in ENV_ABI
        env["DMLC_FIXTURE_ROGUE"] = "1"
        env.setdefault("DMLC_FIXTURE_LAZY", "2")
"""


def test_wire_schema_env_abi_only_in_launch_and_tracker(tmp_path):
    ctx = analyze(_wire_repo(tmp_path, {
        "dmlc_core_tpu/launch/envs.py": _ENV_INJECT,
        "dmlc_core_tpu/mod.py": _ENV_INJECT,     # out of ABI scope
    }), rules=["wire-schema"])
    keys = sorted(f.key for f in _findings(ctx, "wire-schema"))
    assert keys == ["env:DMLC_FIXTURE_LAZY", "env:DMLC_FIXTURE_ROGUE"]
    assert all(f.path.endswith("launch/envs.py") for f in ctx.findings)


# ---------------------------------------------------------------------------
# CLI satellites: --explain, stale-baseline FAIL, per-pass timings
# ---------------------------------------------------------------------------

def test_rule_help_has_doc_and_example_pair():
    from dmlc_core_tpu.analysis import rule_help

    for rule in ("lock-blocking", "atomicity", "resource-leak",
                 "thread-lifecycle", "collective-discipline",
                 "wire-schema"):
        info = rule_help(rule)
        assert info["rule"] == rule
        assert info["doc"] and info["flagged"] and info["clean"]
    with pytest.raises(ValueError, match="unknown dmlcheck rule"):
        rule_help("not-a-rule")


def _run_cli(args):
    import subprocess
    import sys as _sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [_sys.executable, os.path.join(root, "scripts", "dmlcheck.py"),
         *args], capture_output=True, text=True)


def test_cli_explain_prints_pass_doc():
    r = _run_cli(["--explain", "atomicity"])
    assert r.returncode == 0
    assert "[atomicity]" in r.stdout
    assert "flagged:" in r.stdout and "clean:" in r.stdout
    r2 = _run_cli(["--explain", "nope"])
    assert r2.returncode == 2
    assert "unknown dmlcheck rule" in r2.stderr


def test_cli_stale_baseline_entry_fails_with_remove_me(tmp_path):
    import json as _json

    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": "x = 1\n"})
    bp = tmp_path / "baseline.json"
    bp.write_text(_json.dumps(
        {"findings": ["dmlc_core_tpu/gone.py::atomicity::X._n:bump:rmw"]}))
    r = _run_cli(["--root", root, "--baseline", str(bp)])
    assert r.returncode == 1
    assert "stale baseline" in r.stderr and "remove me" in r.stderr


def test_cli_timings_reports_new_passes(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": "x = 1\n"})
    bp = tmp_path / "baseline.json"
    r = _run_cli(["--root", root, "--baseline", str(bp), "--timings"])
    assert r.returncode == 0
    assert "per-pass timings" in r.stderr
    assert "blocking" in r.stderr and "atomicity" in r.stderr
    assert "resources" in r.stderr and "protocol" in r.stderr


# ---------------------------------------------------------------------------
# recompile-hazard (analysis/jaxpass)
# ---------------------------------------------------------------------------

_RECOMPILE_BAD = """
    import os
    import jax
    from functools import partial

    @partial(jax.jit, static_argnums=(1,))
    def kernel(x, cfg):
        return x

    class Model:
        def step(self, x):
            return jax.jit(self._impl)(x)       # fresh wrapper per call

        def steps(self, xs):
            fns = []
            for x in xs:
                fns.append(jax.jit(self._impl)) # rebuilt per iteration
            return fns

        def predict(self, x):
            return kernel(x, f"k-{x.shape}")    # fresh static key per call

        def _round_fn_cache_key(self):
            return (os.environ.get("DMLC_FIXTURE_FLAG", "0"),)
"""

_RECOMPILE_GOOD = """
    import jax
    from functools import partial

    _EXEC_CACHE = {}

    @partial(jax.jit, static_argnums=(1,))
    def kernel(x, depth):
        return x

    class Model:
        def __init__(self):
            self._impl_jit = jax.jit(self._impl)   # built once

        def step(self, x):
            return self._impl_jit(x)

        def warm(self, shapes):
            for s in shapes:
                _EXEC_CACHE[s] = jax.jit(self._impl)  # parked in a cache

        def predict(self, x, depth):
            return kernel(x, depth)                # hashable static

        def _round_fn_cache_key(self):
            return (knobs.value("DMLC_FIXTURE_FLAG"),)
"""


def test_recompile_hazard_flags_unstable_shapes(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _RECOMPILE_BAD},
                             knobs=["DMLC_FIXTURE_FLAG"]),
                  rules=["recompile-hazard"])
    msgs = [f.message for f in _findings(ctx, "recompile-hazard")]
    assert any("fresh jax.jit wrapper per call" in m for m in msgs), msgs
    assert any("inside a loop" in m for m in msgs), msgs
    assert any("static position 1" in m for m in msgs), msgs
    assert any("compile-cache key" in m and "knobs" in m
               for m in msgs), msgs


def test_recompile_hazard_clean_idioms(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _RECOMPILE_GOOD},
                             knobs=["DMLC_FIXTURE_FLAG"]),
                  rules=["recompile-hazard"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# donation-discipline (analysis/jaxpass)
# ---------------------------------------------------------------------------

_DONATION_BAD = """
    import jax

    def update(state, grads):
        return state

    step = jax.jit(update, donate_argnums=(0,))

    def train(state, grads):
        new = step(state, grads)
        print(state)                              # read after donation
        return new
"""

_DONATION_GOOD = """
    import jax

    def update(state, grads):
        return state

    step = jax.jit(update, donate_argnums=(0,))

    def train(state, grads):
        state = step(state, grads)     # rebinding kills the old name
        return state
"""


def test_donation_discipline_flags_use_after(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _DONATION_BAD}),
                  rules=["donation-discipline"])
    msgs = [f.message for f in _findings(ctx, "donation-discipline")]
    assert len(msgs) == 1, msgs
    assert "reads 'state' after donating" in msgs[0], msgs


def test_donation_discipline_clean_rebound(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _DONATION_GOOD}),
                  rules=["donation-discipline"])
    assert _findings(ctx) == []


# ---------------------------------------------------------------------------
# transfer-discipline (analysis/jaxpass)
# ---------------------------------------------------------------------------

_TRANSFER_BAD = """
    import jax
    import numpy as np

    @jax.jit
    def kernel(x):
        return np.asarray(x).sum()      # host transfer inside trace

    round_fn = jax.jit(lambda p: p)

    def fit(preds, table, n):
        done = 0
        while done < n:
            cfg = jax.device_put(table)   # re-uploaded per round
            preds = round_fn(preds)
            done += preds.item()          # device sync per round
        return preds
"""

_TRANSFER_GOOD = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(x):
        return jnp.asarray(x).sum()

    round_fn = jax.jit(lambda p: p)

    def fit(preds, table, n):
        cfg = jax.device_put(table)       # ingest: once, outside
        for _ in range(n):
            preds = round_fn(jax.device_put(preds))  # feeding the call
        return float(preds.sum())          # one sync after the loop
"""


def test_transfer_discipline_flags_traced_and_roundloop(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _TRANSFER_BAD}),
                  rules=["transfer-discipline"])
    msgs = [f.message for f in _findings(ctx, "transfer-discipline")]
    assert any("np.asarray" in m for m in msgs), msgs
    assert any("device_put inside its round loop" in m for m in msgs), msgs
    assert any(".item() inside its round loop" in m for m in msgs), msgs


def test_transfer_discipline_clean_ingest_and_jnp(tmp_path):
    ctx = analyze(_mini_repo(tmp_path,
                             {"dmlc_core_tpu/mod.py": _TRANSFER_GOOD}),
                  rules=["transfer-discipline"])
    assert _findings(ctx) == []


def test_jax_rule_help_has_doc_and_example_pair():
    from dmlc_core_tpu.analysis import rule_help

    for rule in ("recompile-hazard", "donation-discipline",
                 "transfer-discipline"):
        info = rule_help(rule)
        assert info["rule"] == rule
        assert info["doc"] and info["flagged"] and info["clean"]


# ---------------------------------------------------------------------------
# incremental cache
# ---------------------------------------------------------------------------

def test_cache_full_hit_reuses_findings(tmp_path):
    root = _mini_repo(tmp_path,
                      {"dmlc_core_tpu/mod.py": _DONATION_BAD})
    cache = tmp_path / "cache.bin"
    ctx1 = analyze(root, rules=["donation-discipline"],
                   cache_path=str(cache))
    assert ctx1.cache_stats == {"files": len(ctx1.files), "hits": 0,
                                "findings_reused": False}
    assert cache.exists()
    ctx2 = analyze(root, rules=["donation-discipline"],
                   cache_path=str(cache))
    assert ctx2.cache_stats["hits"] == ctx2.cache_stats["files"]
    assert ctx2.cache_stats["findings_reused"] is True
    assert [f.fingerprint for f in ctx2.findings] == \
        [f.fingerprint for f in ctx1.findings]
    assert ctx2.suppressed_count == ctx1.suppressed_count


def test_cache_invalidates_on_edit_and_rule_change(tmp_path):
    root = _mini_repo(tmp_path,
                      {"dmlc_core_tpu/mod.py": _DONATION_BAD,
                       "dmlc_core_tpu/other.py": "x = 1\n"})
    cache = tmp_path / "cache.bin"
    analyze(root, rules=["donation-discipline"], cache_path=str(cache))
    # a rules change must not reuse the previous run's findings
    ctx_r = analyze(root, rules=["style"], cache_path=str(cache))
    assert ctx_r.cache_stats["findings_reused"] is False
    assert _findings(ctx_r, "donation-discipline") == []
    # an edited file re-parses (one miss), findings recompute
    analyze(root, rules=["donation-discipline"], cache_path=str(cache))
    mod = os.path.join(root, "dmlc_core_tpu", "mod.py")
    with open(mod, "a") as f:
        f.write("\nY = 2\n")
    ctx3 = analyze(root, rules=["donation-discipline"],
                   cache_path=str(cache))
    assert ctx3.cache_stats["findings_reused"] is False
    assert ctx3.cache_stats["hits"] == ctx3.cache_stats["files"] - 1
    assert _findings(ctx3, "donation-discipline")


def test_cache_corrupt_file_is_cold_run(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": "x = 1\n"})
    cache = tmp_path / "cache.bin"
    cache.write_bytes(b"not a pickle")
    ctx = analyze(root, cache_path=str(cache))
    assert ctx.cache_stats["findings_reused"] is False
    assert ctx.findings == []


def test_cli_no_cache_and_hit_rate(tmp_path):
    root = _mini_repo(tmp_path, {"dmlc_core_tpu/mod.py": "x = 1\n"})
    os.makedirs(os.path.join(root, "scripts"), exist_ok=True)
    bp = tmp_path / "baseline.json"
    r1 = _run_cli(["--root", root, "--baseline", str(bp), "--timings"])
    assert r1.returncode == 0
    assert "cache:" in r1.stderr and "findings recomputed" in r1.stderr
    r2 = _run_cli(["--root", root, "--baseline", str(bp), "--timings"])
    assert "findings reused" in r2.stderr
    assert "(100%)" in r2.stderr
    r3 = _run_cli(["--root", root, "--baseline", str(bp), "--timings",
                   "--no-cache"])
    assert r3.returncode == 0
    assert "cache:" not in r3.stderr
