"""ThreadGroup / ThreadLocalStore / Tracer tests.

Reference test models: thread_group + thread_local behavior mirrors
test/unittest/unittest_thread_group.cc's lifecycle checks (SURVEY.md §4);
Tracer is the §5 tracing superset (no reference counterpart — asserted on
its own contract: Chrome trace JSON).
"""

import json
import threading
import time

import pytest

from dmlc_core_tpu.base.thread_local import ThreadLocalStore
from dmlc_core_tpu.io.thread_group import ThreadGroup
from dmlc_core_tpu.utils.profiler import Tracer, current_op, span


def test_thread_group_runs_and_joins():
    results = []
    grp = ThreadGroup()
    for i in range(4):
        grp.create(f"w{i}", lambda sd, i=i: results.append(i))
    grp.join_all()
    assert sorted(results) == [0, 1, 2, 3]
    assert grp.size() == 4
    assert sorted(grp.names()) == ["w0", "w1", "w2", "w3"]


def test_thread_group_shutdown_signal():
    started = threading.Event()

    def loop(sd):
        started.set()
        while not sd.requested:
            sd.wait(0.01)

    grp = ThreadGroup()
    t = grp.create("looper", loop)
    assert started.wait(5.0)
    assert t.is_alive()
    grp.request_shutdown_all()
    grp.join_all(timeout=5.0)
    assert not t.is_alive()


def test_thread_group_duplicate_name_rejected():
    grp = ThreadGroup()
    grp.create("dup", lambda sd: None)
    with pytest.raises(Exception):
        grp.create("dup", lambda sd: None)
    grp.join_all()


def test_thread_group_propagates_worker_exception():
    def boom(sd):
        raise ValueError("worker died")

    grp = ThreadGroup()
    grp.create("boom", boom)
    with pytest.raises(ValueError, match="worker died"):
        grp.join_all()


def test_thread_group_context_manager():
    stopped = []

    def loop(sd):
        sd.wait(10.0)
        stopped.append(sd.requested)

    with ThreadGroup() as grp:
        grp.create("cm", loop)
        time.sleep(0.02)
    assert stopped == [True]


def test_thread_local_store_per_thread_instances():
    store = ThreadLocalStore(list)
    main = store.get()
    assert store.get() is main
    seen = {}

    barrier = threading.Barrier(2)

    def worker_waits():
        seen["other"] = store.get()
        barrier.wait()   # registered while alive
        barrier.wait()   # released after the assertion below

    t = threading.Thread(target=worker_waits)
    t.start()
    barrier.wait()
    assert seen["other"] is not main
    assert len(store.instances()) == 2  # both threads still alive
    barrier.wait()
    t.join()
    # dead threads are pruned: their instances are not pinned forever
    assert len(store.instances()) == 1
    store.clear()
    assert store.instances() == []
    assert store.get() is not main  # re-created after clear


def test_tracer_chrome_json(tmp_path):
    tr = Tracer()
    with tr.scope("parse", file="a.rec"):
        tr.instant("mark")
        tr.counter("queue_depth", 3)
    path = tr.save(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    phases = {e["ph"] for e in data["traceEvents"]}
    assert {"X", "i", "C"} <= phases
    x = [e for e in data["traceEvents"] if e["ph"] == "X"][0]
    assert x["name"] == "parse" and x["dur"] >= 0
    assert x["args"]["file"] == "a.rec"


def test_tracer_threads_have_distinct_rows():
    tr = Tracer()
    both_alive = threading.Barrier(2, timeout=10)

    def work(name):
        with tr.scope(name):
            both_alive.wait()      # a finished thread's id can be reused

    ts = [threading.Thread(target=work, args=(f"t{i}",)) for i in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == 2


def test_annotations_are_safe_noops_anywhere():
    # must never raise, profiler active or not; children share the
    # parent's op, and nothing is left open afterwards
    with span("region", rows=3) as outer:
        with span("region.step", step=0) as inner:
            assert current_op() == inner.counts["op"] == outer.counts["op"]
    assert current_op() is None
