"""Fleet serving: consistent-hash stability, router failover
bit-parity, staged rollout/rollback (pure), admission control honored
by ResilientClient, autoscale policy hysteresis, and a slow-marked
3-replica soak with one replica SIGKILLed mid-traffic."""

import json
import os
import signal
import tempfile
import threading
import time

import numpy as np
import pytest

from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.base.resilience import RetryPolicy
from dmlc_core_tpu.serve import ResilientClient, checkpoint_model
from dmlc_core_tpu.serve.fleet import (AutoscalePolicy, FleetAdmin,
                                       FleetRouter, FleetTracker, HashRing,
                                       Replica, Rollout, RolloutController,
                                       diurnal_qps, plan_waves, sample_size,
                                       spawn_replica)

F = 6


def _make_data(n, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


class TestHashRing:
    def test_deterministic_and_complete(self):
        keys = [f"key-{i}".encode() for i in range(500)]
        r1 = HashRing([0, 1, 2, 3], vnodes=64)
        r2 = HashRing([3, 2, 1, 0], vnodes=64)   # order-independent
        assert [r1.lookup(k) for k in keys] == [r2.lookup(k) for k in keys]
        # every node owns SOME keys (vnodes spread the ring)
        owners = {r1.lookup(k) for k in keys}
        assert owners == {0, 1, 2, 3}

    def test_bounded_key_movement_on_membership_change(self):
        """Removing one of n nodes moves ONLY the keys it owned (~1/n);
        every other key keeps its owner — the property that makes the
        ring worth having over hash-mod-n."""
        keys = [f"req-{i}".encode() for i in range(4000)]
        full = HashRing([0, 1, 2, 3, 4], vnodes=64)
        down = HashRing([0, 1, 2, 3], vnodes=64)
        moved = 0
        for k in keys:
            before, after = full.lookup(k), down.lookup(k)
            if before != after:
                moved += 1
                assert before == 4          # only the dead node's keys move
        # ~1/5 of keys lived on node 4; generous slack for hash variance
        assert 0.05 < moved / len(keys) < 0.40

    def test_sequence_is_distinct_failover_order(self):
        ring = HashRing(["a", "b", "c"], vnodes=32)
        for i in range(50):
            seq = ring.sequence(f"k{i}".encode())
            assert seq[0] == ring.lookup(f"k{i}".encode())
            assert sorted(seq) == ["a", "b", "c"]    # all, no dupes

    def test_empty_ring(self):
        assert HashRing([]).sequence(b"x") == []


class _FakeAdmin(FleetAdmin):
    """Pure in-memory fleet: per-rank version registries, optional
    fail-health injection after a given activation count."""

    def __init__(self, ranks, fail_on_activation=None):
        self._ranks = list(ranks)
        self.active = {r: 1 for r in ranks}
        self.staged = {r: [1] for r in ranks}
        self.log = []
        self._fail_on = fail_on_activation      # rank whose health lies
        self._next_version = {r: 2 for r in ranks}

    def replicas(self):
        return {r: f"fake://{r}" for r in self._ranks}

    def load(self, rank, uri, activate=False):
        v = self._next_version[rank]
        self._next_version[rank] += 1
        self.staged[rank].append(v)
        self.log.append(("load", rank, v, activate))
        if activate:
            self.active[rank] = v
        return v

    def activate(self, rank, version):
        assert version in self.staged[rank]
        self.active[rank] = version
        self.log.append(("activate", rank, version))

    def health(self, rank):
        status = "ok"
        if self._fail_on is not None and rank == self._fail_on \
                and self.active[rank] != 1:
            status = "unhealthy"
        return {"status": status, "version": self.active[rank]}


class TestRolloutPure:
    def test_plan_waves(self):
        assert plan_waves([1, 2, 3, 4, 5], 2) == [[1, 2], [3, 4], [5]]
        assert plan_waves([7], 3) == [[7]]
        assert plan_waves([], 1) == []
        with pytest.raises(Exception):
            plan_waves([1], 0)

    def test_controller_happy_path(self):
        ctrl = RolloutController([0, 1, 2, 3, 4], wave_size=2)
        ctrl.staged()
        seen = []
        while (wave := ctrl.next_wave()) is not None:
            seen.append(wave)
            ctrl.wave_ok()
        assert seen == [[0, 1], [2, 3], [4]]
        assert ctrl.state == RolloutController.DONE
        assert ctrl.activated == [0, 1, 2, 3, 4]
        assert ctrl.next_wave() is None          # idempotent when done

    def test_controller_rollback_targets(self):
        ctrl = RolloutController([0, 1, 2, 3], wave_size=1)
        ctrl.staged()
        ctrl.next_wave(); ctrl.wave_ok()         # 0 activated
        ctrl.next_wave(); ctrl.wave_ok()         # 1 activated
        ctrl.next_wave()                          # 2 activating...
        targets = ctrl.wave_failed()
        # failed wave included, most recent first
        assert targets == [2, 1, 0]
        assert ctrl.state == RolloutController.ROLLED_BACK

    def test_rollout_driver_activates_in_waves(self):
        admin = _FakeAdmin([0, 1, 2])
        report = Rollout(admin, wave_size=2, settle_s=0.0).run("fake://v2")
        assert report["outcome"] == "activated"
        assert [w["replicas"] for w in report["waves"]] == [[0, 1], [2]]
        assert admin.active == {0: 2, 1: 2, 2: 2}
        # staged on ALL replicas before the FIRST activation
        first_activate = admin.log.index(("activate", 0, 2))
        loads = [e for e in admin.log[:first_activate] if e[0] == "load"]
        assert len(loads) == 3 and all(not e[3] for e in loads)

    def test_rollout_rolls_back_on_health_regression(self):
        admin = _FakeAdmin([0, 1, 2], fail_on_activation=1)
        report = Rollout(admin, wave_size=1, settle_s=0.0).run("fake://v2")
        assert report["outcome"] == "rolled_back"
        assert report["rolled_back"] == [1, 0]   # reverse activation order
        assert admin.active == {0: 1, 1: 1, 2: 1}   # all back on v1

    def test_rollout_eval_gate_rejection(self):
        admin = _FakeAdmin([0, 1])
        r = Rollout(admin, wave_size=2, settle_s=0.0,
                    eval_gate=lambda v: False)
        report = r.run("fake://v2")
        assert report["outcome"] == "rolled_back"
        assert admin.active == {0: 1, 1: 1}


class TestAutoscalePolicy:
    def test_patience_hysteresis(self):
        p = AutoscalePolicy(high_s=0.1, low_s=0.01, patience=3,
                            min_replicas=1, max_replicas=8)
        assert p.observe(0.5, 3) == 0            # streak 1
        assert p.observe(0.5, 3) == 0            # streak 2
        assert p.observe(0.005, 3) == 0          # opposite sample resets
        assert p.observe(0.5, 3) == 0
        assert p.observe(0.5, 3) == 0
        assert p.observe(0.5, 3) == 1            # 3 consecutive highs
        assert p.observe(0.5, 3) == 0            # recommendation consumed

    def test_bounds_and_idle(self):
        p = AutoscalePolicy(high_s=0.1, low_s=0.01, patience=1,
                            min_replicas=2, max_replicas=3)
        assert p.observe(None, 2) == 0           # no signal: hold
        assert p.observe(0.5, 3) == 0            # at ceiling: no +1
        assert p.observe(0.001, 2) == 0          # at floor: no -1
        assert p.observe(0.001, 3) == -1
        assert p.observe(0.5, 2) == 1

    def test_in_band_resets(self):
        p = AutoscalePolicy(high_s=0.1, low_s=0.01, patience=2)
        assert p.observe(0.5, 1) == 0
        assert p.observe(0.05, 1) == 0           # in-band: reset
        assert p.observe(0.5, 1) == 0
        assert p.observe(0.5, 1) == 1


class TestLoadgenPure:
    def test_sample_size_bounds_and_tail(self):
        rng = np.random.default_rng(7)
        sizes = [sample_size(rng, alpha=1.2, max_size=32)
                 for _ in range(5000)]
        assert min(sizes) >= 1 and max(sizes) <= 32
        small = sum(1 for s in sizes if s <= 4)
        big = sum(1 for s in sizes if s >= 16)
        assert small > len(sizes) * 0.5          # mostly small...
        assert big > 0                           # ...with a real tail

    def test_diurnal_qps_envelope(self):
        qs = [diurnal_qps(t, 100.0, amplitude=0.5, period_s=10.0)
              for t in np.linspace(0, 10, 101)]
        assert max(qs) == pytest.approx(150.0, rel=0.01)
        assert min(qs) >= 10.0                   # floored
        assert qs[0] == pytest.approx(100.0)


class _FleetHarness:
    """3 in-process replicas + tracker + router over real sockets."""

    def __init__(self, tmp, n=3, **router_kw):
        X, y = _make_data(400)
        self.X = X
        m1 = HistGBT(n_trees=3, max_depth=3, n_bins=16).fit(X, y)
        m2 = HistGBT(n_trees=5, max_depth=3, n_bins=16).fit(X, y)
        self.direct = {1: m1.predict(X), 2: m2.predict(X)}
        self.v1 = f"file://{tmp}/v1.ckpt"
        self.v2 = f"file://{tmp}/v2.ckpt"
        checkpoint_model(self.v1, m1, version=1)
        checkpoint_model(self.v2, m2, version=2)
        self.tracker = FleetTracker(nworker=8)
        self.tracker.start()
        self.replicas = [
            Replica("127.0.0.1", self.tracker.port, model_uri=self.v1,
                    max_batch=32, heartbeat_s=0.1) for _ in range(n)]
        self.router = FleetRouter(self.tracker, probe_s=0.1,
                                  **router_kw).start()

    def close(self):
        self.router.close()
        for r in self.replicas:
            try:
                r.close()
            except Exception:
                pass
        self.tracker.stop()


class TestFleetRouter:
    def test_failover_bit_parity_vs_direct(self):
        """Predicts through the router are bit-identical to direct
        model.predict — including after a replica dies uncleanly and
        traffic reroutes."""
        with tempfile.TemporaryDirectory() as tmp:
            h = _FleetHarness(tmp)
            try:
                client = ResilientClient(
                    h.router.url, policy=RetryPolicy(max_attempts=6,
                                                     base_backoff_s=0.01))
                for lo, k in ((0, 1), (7, 5), (100, 17), (390, 9)):
                    preds, ver = client.predict(h.X[lo:lo + k])
                    assert ver == 1
                    assert np.array_equal(preds, h.direct[1][lo:lo + k])
                # unclean death: socket drops, no shutdown cmd
                h.replicas[0].close(clean=False)
                h.router.probe_now()
                assert 0 in h.tracker.dead_workers
                for lo, k in ((3, 4), (55, 8), (200, 3), (301, 12)):
                    preds, ver = client.predict(h.X[lo:lo + k])
                    assert np.array_equal(preds, h.direct[1][lo:lo + k])
                docs = h.router.replica_docs()
                assert sum(1 for d in docs.values() if d["healthy"]) == 2
            finally:
                h.close()

    def test_admission_control_503_honored_by_client(self):
        """A fleet-wide queue-bound shed answers 503 + Retry-After; the
        ResilientClient retries (spaced by the hint) and succeeds once
        the bound lifts — no caller-visible failure."""
        with tempfile.TemporaryDirectory() as tmp:
            h = _FleetHarness(tmp, max_queue=-1)   # every predict sheds
            try:
                client = ResilientClient(
                    h.router.url,
                    policy=RetryPolicy(max_attempts=8, base_backoff_s=0.01,
                                       retry_after_cap_s=0.2))
                lifted = threading.Event()

                def lift():
                    time.sleep(0.4)
                    h.router.max_queue = 10_000
                    lifted.set()

                threading.Thread(target=lift, daemon=True).start()
                t0 = time.monotonic()
                preds, ver = client.predict(h.X[:4])
                assert lifted.is_set()            # success only after lift
                assert time.monotonic() - t0 >= 0.2   # spaced, not hammered
                assert np.array_equal(preds, h.direct[ver][:4])
            finally:
                h.close()

    def test_staged_rollout_under_light_traffic(self):
        """v1→v2 rollout with wave_size=1 while predicts flow: every
        response bit-matches the version it claims, final state all-v2,
        zero hard failures."""
        from dmlc_core_tpu.serve.fleet import HttpFleetAdmin, Rollout

        with tempfile.TemporaryDirectory() as tmp:
            h = _FleetHarness(tmp)
            try:
                client = ResilientClient(
                    h.router.url, policy=RetryPolicy(max_attempts=6,
                                                     base_backoff_s=0.01))
                out, stop = [], threading.Event()

                def loop(seed):
                    rng = np.random.default_rng(seed)
                    while not stop.is_set():
                        k = int(rng.integers(1, 9))
                        lo = int(rng.integers(0, len(h.X) - k))
                        try:
                            preds, ver = client.predict(h.X[lo:lo + k])
                            out.append((ver, bool(np.array_equal(
                                preds, h.direct[ver][lo:lo + k]))))
                        except Exception as e:
                            out.append(("error", repr(e)))

                threads = [threading.Thread(target=loop, args=(s,))
                           for s in range(3)]
                for t in threads:
                    t.start()
                time.sleep(0.3)
                admin = HttpFleetAdmin(h.tracker.serve_endpoints())
                report = Rollout(admin, wave_size=1,
                                 settle_s=0.1).run(h.v2)
                time.sleep(0.3)
                stop.set()
                for t in threads:
                    t.join(timeout=30)
                assert report["outcome"] == "activated"
                errors = [o for o in out if o[0] == "error"]
                assert not errors, errors[:3]
                assert all(match for _, match in out)
                assert {v for v, _ in out} == {1, 2}  # both served traffic
                for r in h.replicas:
                    assert r.registry.current_version() == 2
            finally:
                h.close()


class TestFrontendDrain:
    def test_drain_stops_admission_finishes_inflight(self):
        """Regression for graceful shutdown: /drain flips healthz,
        sheds NEW predicts with 503 + Retry-After, while queued and
        in-flight requests complete correctly; close() then returns
        with nothing dropped."""
        import urllib.request

        from dmlc_core_tpu.serve import ModelRegistry, ServeFrontend

        class _Slow:
            def predict(self, Z):
                time.sleep(0.25)
                return Z[:, 0]

        reg = ModelRegistry(name="drain-test", max_batch=4, min_bucket=1)
        reg.publish(_Slow())
        fe = ServeFrontend(reg, max_batch=4, max_delay=0.0, max_queue=64,
                           request_timeout=10.0)
        fe.start()
        results = []

        def hit(lo):
            body = json.dumps(
                {"rows": [[float(lo)] * F]}).encode()
            req = urllib.request.Request(
                fe.url + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    results.append((r.status, json.loads(r.read())))
            except urllib.error.HTTPError as e:
                results.append((e.code, json.loads(e.read() or b"{}")))

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        # all three admitted (a loaded host can take longer than any
        # fixed sleep to get a client thread as far as its request)
        deadline = time.time() + 10
        while (fe.inflight() + len(results) < 3
               and time.time() < deadline):
            time.sleep(0.005)
        time.sleep(0.1)                  # in-flight inside the batcher
        st, body = _post_raw(fe.url + "/drain")
        assert st == 200 and body["status"] == "draining"
        # new work is refused with the backpressure contract
        st, body, headers = _post_predict_raw(fe.url, [[1.0] * F])
        assert st == 503 and "retry-after" in headers
        st, health = _get_json(fe.url + "/healthz")
        assert health["status"] == "draining"
        for t in threads:
            t.join(timeout=30)
        fe.close()
        assert len(results) == 3
        for st, body in results:
            assert st == 200                     # in-flight all completed
        # after close the socket is gone
        with pytest.raises(Exception):
            _get_json(fe.url + "/healthz", timeout=2)


def _get_json(url, timeout=10):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _post_raw(url):
    import urllib.request

    req = urllib.request.Request(url, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _post_predict_raw(url, rows):
    import urllib.request

    body = json.dumps({"rows": np.asarray(rows).tolist()}).encode()
    req = urllib.request.Request(
        url + "/predict", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        hdrs = {k.lower(): v for k, v in e.headers.items()}
        return e.code, json.loads(e.read() or b"{}"), hdrs


@pytest.mark.slow
class TestFleetSoak:
    def test_sigkill_one_replica_zero_dropped_zero_wrong(self):
        """3 subprocess replicas behind the router; SIGKILL one mid-
        traffic.  The router fails predicts over, its breaker opens, the
        tracker records the death — and NOT ONE client request is
        dropped or answered wrong."""
        with tempfile.TemporaryDirectory() as tmp:
            X, y = _make_data(400)
            m1 = HistGBT(n_trees=3, max_depth=3, n_bins=16).fit(X, y)
            direct = {1: m1.predict(X)}
            v1 = f"file://{tmp}/v1.ckpt"
            checkpoint_model(v1, m1, version=1)
            tracker = FleetTracker(nworker=8)
            tracker.start()
            env = {"JAX_PLATFORMS": "cpu", "DMLC_TPU_FORCE_CPU": "1"}
            procs = [spawn_replica("127.0.0.1", tracker.port,
                                   model_uri=v1, max_batch=32,
                                   extra_env=env) for _ in range(3)]
            router = None
            try:
                deadline = time.time() + 120
                while len(tracker.serve_endpoints()) < 3:
                    assert time.time() < deadline, "replicas never joined"
                    time.sleep(0.2)
                router = FleetRouter(tracker, probe_s=0.1).start()
                client_policy = RetryPolicy(max_attempts=8, base_backoff_s=0.02,
                                            deadline_s=30.0)
                out, stop = [], threading.Event()

                def loop(seed):
                    c = ResilientClient(router.url, policy=client_policy)
                    rng = np.random.default_rng(seed)
                    while not stop.is_set():
                        k = int(rng.integers(1, 9))
                        lo = int(rng.integers(0, len(X) - k))
                        try:
                            preds, ver = c.predict(X[lo:lo + k],
                                                   timeout_ms=10_000)
                            out.append(("ok", bool(np.array_equal(
                                preds, direct[ver][lo:lo + k]))))
                        except Exception as e:
                            out.append(("dropped", repr(e)))

                threads = [threading.Thread(target=loop, args=(s,))
                           for s in range(4)]
                for t in threads:
                    t.start()
                time.sleep(1.0)
                victim = procs[1]
                os.kill(victim.pid, signal.SIGKILL)
                victim.wait(timeout=10)
                time.sleep(2.0)
                stop.set()
                for t in threads:
                    t.join(timeout=60)

                dropped = [o for o in out if o[0] == "dropped"]
                oks = [o for o in out if o[0] == "ok"]
                assert not dropped, f"dropped: {dropped[:3]}"
                assert len(oks) > 50
                assert all(m for _, m in oks), "wrong answers"
                assert tracker.dead_workers, "tracker missed the death"
                assert len(tracker.serve_endpoints()) == 2
            finally:
                if router is not None:
                    router.close()
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                        try:
                            p.wait(timeout=15)
                        except Exception:
                            p.kill()
                tracker.stop()
