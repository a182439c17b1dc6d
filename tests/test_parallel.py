"""Tests for the distributed layer: mesh, collectives (in-jit + host),
topology oracle, KVStore, checkpoint, tracker service, local multi-process
launch (the reference's local.py testing pattern, SURVEY.md §4)."""

import os
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base.logging import Error
from dmlc_core_tpu.io import TemporaryDirectory
from dmlc_core_tpu.parallel import (
    KVStore,
    MeshSpec,
    allreduce,
    allgather,
    broadcast,
    create_mesh,
    data_sharding,
    rank,
    world_size,
)
from dmlc_core_tpu.parallel.checkpoint import checkpoint, load_checkpoint
from dmlc_core_tpu.parallel.collectives import (
    device_allgather,
    device_allreduce,
    find_share_ring,
    get_link_map,
    get_tree,
)
from dmlc_core_tpu.parallel.mesh import local_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from dmlc_core_tpu.tracker.tracker import (
    RabitTracker,
    WorkerSession,
    submit as tracker_submit,
)


class TestTopologyOracle:
    @pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 16, 33])
    def test_tree_properties(self, n):
        parent, children = get_tree(n)
        assert parent[0] == -1
        for r in range(1, n):
            assert parent[r] == (r - 1) // 2
            assert r in children[parent[r]]
        # every non-root reachable from root
        seen = set()
        stack = [0]
        while stack:
            r = stack.pop()
            seen.add(r)
            stack.extend(children[r])
        assert seen == set(range(n))

    @pytest.mark.parametrize("n", [1, 2, 5, 8, 13])
    def test_ring_is_dfs_permutation(self, n):
        parent, children = get_tree(n)
        ring = find_share_ring(children)
        assert sorted(ring) == list(range(n))
        assert ring[0] == 0

    @pytest.mark.parametrize("n", [2, 6, 9])
    def test_link_map_consistent(self, n):
        links = get_link_map(n)
        for r, link in links.items():
            # ring closes: next of prev is me
            assert links[link["ring_next"]]["ring_prev"] == r
            assert links[link["ring_prev"]]["ring_next"] == r
            for c in link["children"]:
                assert links[c]["parent"] == r


class TestMesh:
    def test_spec_resolve_wildcard(self):
        spec = MeshSpec()
        assert spec.resolve(8) == {"data": 8, "model": 1, "pipe": 1, "seq": 1, "expert": 1}
        spec = MeshSpec(data=-1, model=2)
        assert spec.resolve(8)["data"] == 4

    def test_spec_mismatch_fatal(self):
        with pytest.raises(Error):
            MeshSpec(data=3, model=1).resolve(8)

    def test_create_mesh_all_devices(self):
        mesh = create_mesh()
        assert mesh.devices.size == len(jax.devices())
        assert mesh.axis_names == ("data", "model", "pipe", "seq", "expert")

    def test_data_sharding_places_shards(self):
        mesh = local_mesh()
        n = len(jax.devices())
        x = np.arange(n * 4, dtype=np.float32).reshape(n, 4)
        arr = jax.device_put(x, data_sharding(mesh, ndim=2))
        assert len(arr.addressable_shards) == n
        np.testing.assert_array_equal(np.asarray(arr), x)


class TestDeviceCollectives:
    def test_device_allreduce_sum(self):
        mesh = local_mesh()
        n = len(jax.devices())
        x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        arr = jax.device_put(x, data_sharding(mesh, ndim=2))
        out = device_allreduce(arr, mesh, "sum")
        np.testing.assert_allclose(np.asarray(out), x.sum(axis=0))

    def test_device_allreduce_max_min(self):
        mesh = local_mesh()
        n = len(jax.devices())
        x = np.random.default_rng(0).normal(size=(n, 5)).astype(np.float32)
        arr = jax.device_put(x, data_sharding(mesh, ndim=2))
        np.testing.assert_allclose(
            np.asarray(device_allreduce(arr, mesh, "max")), x.max(axis=0)
        )
        np.testing.assert_allclose(
            np.asarray(device_allreduce(arr, mesh, "min")), x.min(axis=0)
        )

    def test_device_allgather(self):
        mesh = local_mesh()
        n = len(jax.devices())
        x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
        arr = jax.device_put(x, data_sharding(mesh, ndim=2))
        out = device_allgather(arr, mesh)
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_unknown_op_fatal(self):
        mesh = local_mesh()
        with pytest.raises(Error):
            device_allreduce(jnp.zeros((8, 2)), mesh, "median")


class TestHostCollectivesSingleProcess:
    def test_identity_paths(self):
        x = np.arange(5.0)
        np.testing.assert_array_equal(allreduce(x, "sum"), x)
        np.testing.assert_array_equal(broadcast(x), x)
        assert allgather(x).shape == (1, 5)
        assert rank() == 0 and world_size() == 1

    def test_bad_op(self):
        with pytest.raises(Error):
            allreduce(np.zeros(3), "xor")


class TestKVStore:
    def test_local_push_pull_sgd(self):
        kv = KVStore.create("local", learning_rate=0.5)
        kv.init(3, np.ones(4, np.float32))
        kv.push(3, np.full(4, 2.0, np.float32))
        out = np.asarray(kv.pull(3))
        np.testing.assert_allclose(out, 1.0 - 0.5 * 2.0)

    def test_push_accumulates(self):
        kv = KVStore.create("local", learning_rate=1.0)
        kv.init("w", np.zeros(2, np.float32))
        kv.push("w", np.ones(2, np.float32))
        kv.push("w", np.ones(2, np.float32))
        np.testing.assert_allclose(np.asarray(kv.pull("w")), -2.0)

    def test_list_keys_and_custom_updater(self):
        kv = KVStore.create("dist_sync")
        kv.init(["a", "b"], [np.zeros(2), np.ones(2)])
        kv.set_updater(lambda k, g, v: v + g)
        kv.push(["a", "b"], [np.ones(2), np.ones(2)])
        a, b = kv.pull(["a", "b"])
        np.testing.assert_allclose(np.asarray(a), 1.0)
        np.testing.assert_allclose(np.asarray(b), 2.0)

    def test_mesh_dist_sync_bucketed_one_collective(self):
        """Many keys pulled together must fuse into ONE allreduce launch
        (config 4: per-key launches can't reach bus-bandwidth targets),
        with results identical to the per-key math."""
        mesh = local_mesh()
        W = mesh.devices.size
        kv = KVStore.create("dist_sync", mesh=mesh, learning_rate=1.0)
        rng = np.random.default_rng(0)
        keys = [f"p{i}" for i in range(12)]
        vals = {k: rng.normal(size=(3 + i % 4,)).astype(np.float32)
                for i, k in enumerate(keys)}
        kv.init(list(keys), [vals[k] for k in keys])
        grads = {k: rng.normal(size=(W, *vals[k].shape)).astype(np.float32)
                 for k in keys}
        sharding = NamedSharding(mesh, P("data"))
        kv.push(list(keys), [jax.device_put(grads[k], sharding)
                             for k in keys])
        out = kv.pull(list(keys))
        assert kv.stats["sync_calls"] == 1, kv.stats
        assert kv.stats["keys_synced"] == len(keys)
        for k, o in zip(keys, out):
            np.testing.assert_allclose(
                np.asarray(o), vals[k] - grads[k].sum(axis=0),
                rtol=1e-5, atol=1e-5)

    def test_duplicate_key_in_pull_batch(self):
        kv = KVStore.create("dist_sync", learning_rate=1.0)
        kv.init("a", np.zeros(2, np.float32))
        kv.push("a", np.ones(2, np.float32))
        o1, o2 = kv.pull(["a", "a"])   # must not KeyError; one sync
        np.testing.assert_allclose(np.asarray(o1), -1.0)
        np.testing.assert_allclose(np.asarray(o2), -1.0)

    def test_dist_sync_batch_digest_check(self, monkeypatch):
        """DMLC_KVSTORE_CHECK=1 cross-checks that every worker pulled the
        same (key, shape, dtype) batch before fused reduction; a skewed
        batch must fail fast instead of silently corrupting gradients.
        Simulated two-worker world: allreduce echo = digests agree;
        perturbed max = digests differ -> fatal."""
        from dmlc_core_tpu.parallel import kvstore as kvmod

        monkeypatch.setenv("DMLC_KVSTORE_CHECK", "1")
        monkeypatch.setattr(kvmod.coll, "world_size", lambda: 2)
        calls = []

        def echo_allreduce(x, op="sum"):
            calls.append(op)
            return np.asarray(x)

        monkeypatch.setattr(kvmod.coll, "allreduce", echo_allreduce)
        kv = KVStore.create("dist_sync", learning_rate=1.0)
        kv.init("w", np.zeros(2, np.float32))
        kv.push("w", np.ones(2, np.float32))
        kv.pull("w")                      # identical batches: passes
        assert calls[:2] == ["min", "max"]

        def skewed_allreduce(x, op="sum"):
            x = np.asarray(x)
            return x + 1 if op == "max" else x   # min != max -> divergence

        monkeypatch.setattr(kvmod.coll, "allreduce", skewed_allreduce)
        kv.push("w", np.ones(2, np.float32))
        with pytest.raises(Error, match="DIFFERENT key batches"):
            kv.pull("w")

    def test_bucket_cap_splits_collectives(self):
        mesh = local_mesh()
        W = mesh.devices.size
        # 4-byte cap → every key in its own bucket
        kv = KVStore.create("dist_sync", mesh=mesh, bucket_bytes=4)
        kv.init(["a", "b", "c"], [np.zeros(2, np.float32)] * 3)
        sharding = NamedSharding(mesh, P("data"))
        kv.push(["a", "b", "c"],
                [jax.device_put(np.ones((W, 2), np.float32), sharding)] * 3)
        kv.pull(["a", "b", "c"])
        assert kv.stats["sync_calls"] == 3, kv.stats

    def test_uninitialized_key_fatal(self):
        kv = KVStore.create("local")
        with pytest.raises(Error):
            kv.push("missing", np.zeros(1))

    def test_double_init_fatal(self):
        kv = KVStore.create("local")
        kv.init("k", np.zeros(1))
        with pytest.raises(Error):
            kv.init("k", np.zeros(1))


class TestCheckpoint:
    def test_round_trip_pytree(self):
        with TemporaryDirectory() as tmp:
            uri = os.path.join(tmp.path, "ckpt.bin")
            state = {
                "params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros(3)},
                "step": 42,
            }
            checkpoint(uri, state, version=7)
            like = {
                "params": {"w": jnp.zeros((2, 3)), "b": jnp.ones(3)},
                "step": 0,
            }
            version, loaded = load_checkpoint(uri, like)
            assert version == 7
            np.testing.assert_allclose(np.asarray(loaded["params"]["w"]),
                                       np.arange(6.0).reshape(2, 3))
            assert loaded["step"] == 42

    def test_missing_returns_version_zero(self):
        like = {"x": jnp.zeros(2)}
        version, state = load_checkpoint("/nonexistent/path/ckpt", like)
        assert version == 0 and state is like

    def test_versioned_round_trip_memory_uri(self):
        """The (version, state) contract over the mem:// backend that
        the serve registry's hot-swap rides: the version number written
        round-trips EXACTLY (not approximately, not re-derived), and
        successive saves to the same URI supersede cleanly."""
        like = {"w": jnp.zeros(3), "step": 0}
        for v in (1, 2, 9):                    # monotone publish history
            checkpoint("mem:///ckpt/versioned",
                       {"w": jnp.full(3, float(v)), "step": v}, version=v)
            version, state = load_checkpoint("mem:///ckpt/versioned", like)
            assert version == v
            np.testing.assert_array_equal(np.asarray(state["w"]),
                                          np.full(3, v, np.float32))
            assert state["step"] == v

    def test_version_zero_when_absent_memory_uri(self):
        """Cold-start contract on mem:// too: no checkpoint ⇒ version 0
        and the caller's ``like`` handed back untouched."""
        like = {"w": jnp.zeros(2)}
        version, state = load_checkpoint("mem:///ckpt/never-written", like)
        assert version == 0 and state is like

    def test_sharded_arrays_preserve_sharding(self):
        with TemporaryDirectory() as tmp:
            uri = os.path.join(tmp.path, "ck.bin")
            mesh = local_mesh()
            n = len(jax.devices())
            x = jax.device_put(
                np.arange(n * 2.0, dtype=np.float32).reshape(n, 2),
                data_sharding(mesh, ndim=2),
            )
            checkpoint(uri, {"x": x}, version=1)
            like = {"x": jax.device_put(jnp.zeros((n, 2)), data_sharding(mesh, ndim=2))}
            _, loaded = load_checkpoint(uri, like)
            np.testing.assert_array_equal(
                np.asarray(loaded["x"]), np.arange(n * 2.0).reshape(n, 2)
            )
            assert loaded["x"].sharding == like["x"].sharding


class TestRabitTracker:
    def test_rank_assignment_and_topology(self):
        tracker = RabitTracker(nworker=5)
        tracker.start()
        replies = [
            RabitTracker.worker_connect("127.0.0.1", tracker.port, host=f"h{i}")
            for i in range(5)
        ]
        ranks = sorted(r["rank"] for r in replies)
        assert ranks == [0, 1, 2, 3, 4]
        links = get_link_map(5)
        for r in replies:
            assert r["parent"] == links[r["rank"]]["parent"]
            assert r["ring_next"] == links[r["rank"]]["ring_next"]
            assert r["num_worker"] == 5
        for _ in range(5):
            RabitTracker.worker_connect("127.0.0.1", tracker.port, cmd="shutdown")
        tracker.join(timeout=5)
        assert tracker._done.is_set()
        tracker.stop()

    def test_recover_keeps_rank(self):
        tracker = RabitTracker(nworker=3)
        tracker.start()
        first = RabitTracker.worker_connect("127.0.0.1", tracker.port, host="a")
        RabitTracker.worker_connect("127.0.0.1", tracker.port, host="b")
        again = RabitTracker.worker_connect(
            "127.0.0.1", tracker.port, cmd="recover", rank=first["rank"]
        )
        assert again["rank"] == first["rank"]
        tracker.stop()

    def test_too_many_workers_rejected(self):
        tracker = RabitTracker(nworker=1)
        tracker.start()
        RabitTracker.worker_connect("127.0.0.1", tracker.port)
        reply = RabitTracker.worker_connect("127.0.0.1", tracker.port)
        assert "error" in reply
        tracker.stop()

    def _wait_for(self, cond, timeout=5.0):
        import time
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.02)
        return False

    def test_dead_worker_detected_and_rank_freed(self):
        # VERDICT round-1 item 7: a worker dying mid-job (socket closes
        # without 'shutdown') must be noticed, its rank freed, and a
        # replacement worker must inherit that rank.
        tracker = RabitTracker(nworker=2)
        tracker.start()
        w0 = WorkerSession("127.0.0.1", tracker.port, host="h0")
        w1 = WorkerSession("127.0.0.1", tracker.port, host="h1")
        assert self._wait_for(lambda: tracker.alive_ranks() == [0, 1])
        dead_rank = w1.info["rank"]
        w1.close()  # simulated crash: no shutdown sent
        assert self._wait_for(lambda: tracker.dead_workers == [dead_rank])
        assert tracker.alive_ranks() == [w0.info["rank"]]
        # replacement (different host) inherits the freed rank
        w2 = WorkerSession("127.0.0.1", tracker.port, host="h2")
        assert w2.info["rank"] == dead_rank
        assert self._wait_for(lambda: tracker.alive_ranks() == [0, 1])
        w0.shutdown()
        w2.shutdown()
        assert tracker.join(timeout=5) is True
        tracker.stop()

    def test_join_timeout_on_partial_shutdown(self):
        tracker = RabitTracker(nworker=2)
        tracker.start()
        w0 = WorkerSession("127.0.0.1", tracker.port)
        WorkerSession("127.0.0.1", tracker.port)
        w0.shutdown()  # only one of two workers exits cleanly
        assert tracker.join(timeout=0.3) is False
        tracker.stop()

    def test_recover_reclaims_freed_rank_exclusively(self):
        # rank freed by death, then reclaimed via recover: a later start
        # must NOT be handed the same rank from the free list
        tracker = RabitTracker(nworker=2)
        tracker.start()
        w0 = WorkerSession("127.0.0.1", tracker.port, host="h0")
        dead = w0.info["rank"]
        w0.close()
        assert self._wait_for(lambda: dead in tracker.dead_workers)
        back = WorkerSession("127.0.0.1", tracker.port, cmd="recover", rank=dead)
        assert back.info["rank"] == dead
        other = WorkerSession("127.0.0.1", tracker.port)
        assert other.info["rank"] != dead
        tracker.stop()

    def test_garbled_line_is_not_a_death(self):
        tracker = RabitTracker(nworker=1)
        tracker.start()
        w = WorkerSession("127.0.0.1", tracker.port)
        # inject a non-JSON line on the live socket; the worker must stay alive
        w._sock.sendall(b"this is not json\n")
        w.print_msg("still here")
        assert self._wait_for(lambda: tracker.alive_ranks() == [0])
        assert tracker.dead_workers == []
        w.shutdown()
        assert tracker.join(timeout=5) is True
        tracker.stop()

    def test_clean_session_shutdown_not_counted_dead(self):
        tracker = RabitTracker(nworker=1)
        tracker.start()
        with WorkerSession("127.0.0.1", tracker.port) as ws:
            ws.print_msg("hello from worker")
            ws.shutdown()
        assert tracker.join(timeout=5) is True
        assert self._wait_for(lambda: tracker.alive_ranks() == [])
        assert tracker.dead_workers == []
        tracker.stop()


WORKER_SCRIPT = textwrap.dedent(
    """
    from dmlc_core_tpu.utils import force_cpu_devices
    force_cpu_devices(1)
    import os
    import numpy as np
    from dmlc_core_tpu.parallel import collectives as coll

    coll.init()
    r, w = coll.rank(), coll.world_size()
    assert w == int(os.environ["DMLC_NUM_WORKER"]), (w, os.environ["DMLC_NUM_WORKER"])
    out = coll.allreduce(np.full(4, float(r + 1), np.float32), "sum")
    expected = sum(range(1, w + 1))
    assert np.allclose(out, expected), (out, expected)
    mx = coll.allreduce(np.array([float(r)]), "max")
    assert mx[0] == w - 1
    got = coll.broadcast(np.array([7.5]) if r == 0 else np.array([0.0]), root=0)
    assert got[0] == 7.5, got
    # device-resident allreduce (the external-memory hist-sync path):
    # result must stay a device array and equal the host-path sum
    import jax.numpy as jnp
    dev = coll.allreduce_device(jnp.full((2, 3), float(r + 1)))
    assert hasattr(dev, "devices"), type(dev)
    assert np.allclose(np.asarray(dev), expected), np.asarray(dev)
    print(f"worker {r}/{w} OK", flush=True)
    """
)


@pytest.mark.slow
class TestMultiProcessLocal:
    def test_local_launch_allreduce(self, tmp_path):
        """The reference's local.py pattern: real processes, real collectives.

        Two CPU processes form a jax.distributed cluster via the DMLC env
        ABI and run sum/max allreduce + broadcast.
        """
        script = tmp_path / "worker.py"
        script.write_text(WORKER_SCRIPT)
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            codes.extend(
                local_backend.launch(2, [sys.executable, str(script)], env, timeout=120)
            )

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]

    def test_local_launch_histgbt_training_parity(self, tmp_path):
        """Train a bundled MODEL across real processes (VERDICT r3 #2).

        Two CPU processes form a jax.distributed cluster through the
        tracker ABI + local backend; each fits HistGBT over the
        PROCESS-SPANNING global mesh (the in-round histogram psum rides
        the cross-process Gloo backend — the rabit-allreduce seam) and
        asserts tree-for-tree parity against a single-device fit of the
        same data.  Shared explicit cuts isolate the comparison to the
        boosting engine.  This closes the last untested seam between
        the tracker env ABI and the training engines."""
        script = tmp_path / "gbt_worker.py"
        script.write_text(textwrap.dedent(
            """
            from dmlc_core_tpu.utils import force_cpu_devices
            force_cpu_devices(1)
            import numpy as np
            from dmlc_core_tpu.parallel import collectives as coll
            coll.init()
            import jax
            from jax.sharding import Mesh
            from dmlc_core_tpu.models import HistGBT
            from dmlc_core_tpu.ops.quantile import compute_cuts

            r, w = coll.rank(), coll.world_size()
            assert w == 2, w
            rng = np.random.default_rng(42)
            X = rng.normal(size=(512, 8)).astype(np.float32)
            y = (X[:, 0] * X[:, 1] + 0.3 * X[:, 2] > 0).astype(np.float32)

            cuts = compute_cuts(X, 32)
            kw = dict(n_trees=6, max_depth=3, n_bins=32, learning_rate=0.5)
            dist = HistGBT(mesh=Mesh(np.array(jax.devices()), ("data",)), **kw)
            dist.fit(X, y, cuts=cuts)
            local = HistGBT(
                mesh=Mesh(np.array(jax.local_devices()), ("data",)), **kw)
            local.fit(X, y, cuts=cuts)

            assert len(dist.trees) == len(local.trees) == 6
            for i, (td, tl) in enumerate(zip(dist.trees, local.trees)):
                assert np.array_equal(td["feat"], tl["feat"]), (r, i)
                assert np.array_equal(td["thr"], tl["thr"]), (r, i)
                np.testing.assert_allclose(td["leaf"], tl["leaf"],
                                           rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(dist.predict(X), local.predict(X),
                                       rtol=1e-4, atol=1e-5)
            acc = ((dist.predict(X) > 0.5) == y).mean()
            assert acc > 0.9, acc
            print(f"worker {r}/{w}: HistGBT parity OK", flush=True)
            """
        ))
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            codes.extend(local_backend.launch(
                2, [sys.executable, str(script)], env, timeout=240))

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]

    def test_elastic_recovery_drill(self, tmp_path):
        """The reference's distinctive distributed capability, composed
        end to end (VERDICT r4 #1): a 2-process HistGBT fit with
        per-segment checkpoints; worker 1 SIGKILLed MID-FIT on attempt
        0; the tracker notices both deaths and frees the ranks; the
        AM loop gang-kills the survivor, bumps DMLC_NUM_ATTEMPT, and
        relaunches; the restarted workers reclaim ranks via `recover`,
        resume from the last durable checkpoint, and finish.  The final
        model must be BIT-EXACT against the same 2-process job run
        uninterrupted (see examples/elastic_recovery.py, which this
        drives)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "elastic_recovery_example",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "examples", "elastic_recovery.py"))
        drill = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(drill)

        killed_dir = tmp_path / "killed"
        clean_dir = tmp_path / "clean"
        report = drill.run_drill(str(killed_dir), kill=True, timeout=300)
        # attempt 0 must actually have died (worker 1 SIGKILL -9, the
        # survivor gang-killed) and attempt 1 must have finished clean
        assert report["recovered"], report
        assert len(report["attempts"]) == 2, report
        assert -9 in report["attempts"][0]["codes"], report
        assert report["attempts"][1]["codes"] == [0, 0], report
        assert report["dead_seen"] == [0, 1], report

        clean = drill.run_drill(str(clean_dir), kill=False, timeout=300)
        assert clean["attempts"] == [{"attempt": 0, "codes": [0, 0]}]

        from dmlc_core_tpu.models import HistGBT
        recovered = HistGBT.load_model(report["final_model"])
        ref = HistGBT.load_model(clean["final_model"])
        assert (len(recovered.trees) == len(ref.trees)
                == drill.SEGS * drill.SEG_TREES)
        for i, (tr, tf) in enumerate(zip(recovered.trees, ref.trees)):
            assert np.array_equal(tr["feat"], tf["feat"]), i
            assert np.array_equal(tr["thr"], tf["thr"]), i
            np.testing.assert_array_equal(tr["leaf"], tf["leaf"])
        X, y = drill.make_data()
        np.testing.assert_array_equal(recovered.predict(X),
                                      ref.predict(X))

    def test_local_launch_sparse_histgbt_parity(self, tmp_path):
        """Distributed SparseHistGBT across real processes (r5): each
        worker holds its OWN disjoint row shard; global cuts come from
        the candidate-matrix allgather and per-level histograms / node
        totals allreduce across workers.  With the SAME injected cuts,
        the 2-shard distributed fit must match a single-process fit of
        the full data tree-for-tree (the sparse engine's rabit-allreduce
        seam, like the dense parity test)."""
        script = tmp_path / "sparse_worker.py"
        script.write_text(textwrap.dedent(
            """
            from dmlc_core_tpu.utils import force_cpu_devices
            force_cpu_devices(1)
            import numpy as np
            from dmlc_core_tpu.parallel import collectives as coll
            coll.init()
            from dmlc_core_tpu.models.histgbt_sparse import SparseHistGBT
            from dmlc_core_tpu.ops.sparse_hist import build_sparse_cuts

            r, w = coll.rank(), coll.world_size()
            assert w == 2, w
            rng = np.random.default_rng(17)
            n, F = 600, 30
            mask = rng.random((n, F)) < 0.2
            mask[:, 0] |= rng.random(n) < 0.5
            vals = rng.normal(size=(n, F)).astype(np.float32)
            score = np.where(mask[:, 0], vals[:, 0], -0.5)
            y = (score > 0).astype(np.float32)
            offset = np.concatenate([[0], np.cumsum(mask.sum(axis=1))])
            index = np.nonzero(mask)[1]
            value = vals[mask]
            # one shared cut grid isolates the histogram-allreduce seam
            cuts = build_sparse_cuts(index, value, F, 16)

            def shard(lo, hi):
                keep = slice(offset[lo], offset[hi])
                off = offset[lo:hi + 1] - offset[lo]
                return off, index[keep], value[keep], y[lo:hi]

            half = n // 2
            mine = shard(0, half) if r == 0 else shard(half, n)
            # 2 rounds at moderate lr: by round 3 this easy
            # problem's gradients shrink to near-ties, where f32
            # summation order (allreduce vs single-pass) can flip a
            # threshold or a missing-direction flag — the same property
            # as the dense engine's psum rounding (see
            # test_local_launch_histgbt_training_parity); the early
            # rounds are the exact-parity window
            kw = dict(n_trees=2, max_depth=3, n_bins=16,
                      learning_rate=0.3)
            dist = SparseHistGBT(**kw)
            dist.fit(*mine, n_features=F, cuts=cuts)
            solo = SparseHistGBT(**kw)
            solo.fit(offset, index, value, y, n_features=F, cuts=cuts,
                     distributed=False)
            assert len(dist.trees) == len(solo.trees) == 2
            for i, (td, ts) in enumerate(zip(dist.trees, solo.trees)):
                assert np.array_equal(td["feat"], ts["feat"]), (r, i)
                assert np.array_equal(td["thr"], ts["thr"]), (r, i)
                assert np.array_equal(td["dir"], ts["dir"]), (r, i)
                np.testing.assert_allclose(td["leaf"], ts["leaf"],
                                           rtol=2e-5, atol=2e-6)
            # and the distributed model scores the FULL data like
            # the solo model, well above chance
            pred = dist.predict(offset, index, value)
            np.testing.assert_allclose(
                pred, solo.predict(offset, index, value),
                rtol=1e-5, atol=1e-6)
            acc = ((pred > 0.5) == y).mean()
            assert acc > 0.85, (r, acc)

            # the DEFAULT distributed path (no injected cuts): global
            # cuts from the candidate-matrix allgather-merge; workers
            # must agree bit-for-bit (checked via allreduce min==max)
            auto = SparseHistGBT(**kw)
            auto.fit(*mine, n_features=F)
            flat = np.concatenate(
                [t[k].astype(np.float32).ravel()
                 for t in auto.trees for k in ("feat", "thr", "leaf")])
            mn = coll.allreduce(flat, op="min")
            mx = coll.allreduce(flat, op="max")
            np.testing.assert_array_equal(mn, mx)
            acc2 = ((auto.predict(offset, index, value) > 0.5)
                    == y).mean()
            assert acc2 > 0.85, (r, acc2)
            print(f"worker {r}/{w}: sparse distributed parity OK",
                  flush=True)
            """
        ))
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n_, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            codes.extend(local_backend.launch(
                2, [sys.executable, str(script)], env, timeout=240))

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]

    def test_local_launch_histgbt_missing_mode(self, tmp_path):
        """Missing-value training across real processes: NaN rows all
        land in rank 0's addressable shard, so rank 1 sees no local NaN
        on device — mode selection (allreduce-OR of NaN presence), the
        missing-aware cut allgather (fixed-shape zero-weight NaN knots),
        the missing-bin histogram psum, and per-node direction choice
        must all agree across the cluster, and both ranks must learn
        the MNAR signal (only recoverable via the learned direction).

        Feature 5 is additionally ALL-NaN on rank 0's shard (finite on
        rank 1's): its local summary is the NaN sentinel row and the
        merged cuts must come out finite from rank 1's contribution
        alone (round-4 advisor finding — this used to NaN-poison the
        feature's cuts on every worker)."""
        script = tmp_path / "gbt_missing_worker.py"
        script.write_text(textwrap.dedent(
            """
            from dmlc_core_tpu.utils import force_cpu_devices
            force_cpu_devices(1)
            import numpy as np
            from dmlc_core_tpu.parallel import collectives as coll
            coll.init()
            import jax
            from jax.sharding import Mesh
            from dmlc_core_tpu.models import HistGBT

            r, w = coll.rank(), coll.world_size()
            assert w == 2, w
            rng = np.random.default_rng(7)
            X = rng.normal(size=(512, 6)).astype(np.float32)
            y = (X[:, 0] > 0).astype(np.float32)
            # MNAR mask confined to the FIRST half = rank 0's shard
            Xm = X.copy()
            mask = np.zeros(512, bool)
            mask[:256] = X[:256, 0] > 0
            Xm[mask, 0] = np.nan
            Xm[:256, 5] = np.nan   # all-NaN on rank 0's shard only

            kw = dict(n_trees=6, max_depth=3, n_bins=32,
                      learning_rate=0.5)
            dist = HistGBT(mesh=Mesh(np.array(jax.devices()),
                                     ("data",)), **kw)
            dist.fit(Xm, y)
            assert dist._missing, "mode must be ON on every rank"
            assert np.isfinite(np.asarray(dist.cuts)).all(), \\
                "all-NaN-on-one-shard feature poisoned the merged cuts"
            local = HistGBT(
                mesh=Mesh(np.array(jax.local_devices()), ("data",)),
                **kw)
            local.fit(Xm, y)
            for i, (td, tl) in enumerate(zip(dist.trees, local.trees)):
                assert np.array_equal(td["feat"], tl["feat"]), (r, i)
                assert np.array_equal(td["thr"], tl["thr"]), (r, i)
                assert np.array_equal(td["dir"], tl["dir"]), (r, i)
            pred = dist.predict(Xm) > 0.5
            acc_masked = (pred[mask] == y[mask]).mean()
            assert acc_masked > 0.9, (r, acc_masked)
            print(f"worker {r}/{w}: missing-mode parity OK", flush=True)
            """
        ))
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            codes.extend(local_backend.launch(
                2, [sys.executable, str(script)], env, timeout=240))

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]

    def test_local_launch_bert_training_parity(self, tmp_path):
        """A bundled TRANSFORMER trained across real processes: the
        fused in-step grad psum rides the cross-process Gloo backend on
        a global mesh; three optimizer steps must match the
        single-device fit loss-for-loss and parameter-for-parameter.
        With the HistGBT twin above, both model families' training
        engines are proven over the real tracker + jax.distributed
        seam, not just the virtual mesh."""
        script = tmp_path / "bert_worker.py"
        script.write_text(textwrap.dedent(
            """
            from dmlc_core_tpu.utils import force_cpu_devices
            force_cpu_devices(1)
            import numpy as np
            from dmlc_core_tpu.parallel import collectives as coll
            coll.init()
            import jax
            from jax.sharding import Mesh
            from dmlc_core_tpu.models.bert import BERT

            r, w = coll.rank(), coll.world_size()
            assert w == 2, w
            cfg = dict(n_layers=2, d_model=32, n_heads=2, d_ff=64,
                       vocab_size=64, max_len=16, learning_rate=1e-2)
            rng = np.random.default_rng(5)
            B, S = 8, 16
            tokens = rng.integers(0, 64, size=(B, S)).astype(np.int32)
            labels = rng.integers(0, 64, size=(B, S)).astype(np.int32)
            mask = (rng.random((B, S)) < 0.3).astype(np.float32)

            dist = BERT(mesh=Mesh(np.array(jax.devices()), ("data",)),
                        **cfg)
            dist.init_params(0)
            d_losses = [dist.train_step(tokens, labels, mask)
                        for _ in range(3)]
            local = BERT(
                mesh=Mesh(np.array(jax.local_devices()), ("data",)), **cfg)
            local.init_params(0)
            l_losses = [local.train_step(tokens, labels, mask)
                        for _ in range(3)]
            np.testing.assert_allclose(d_losses, l_losses,
                                       rtol=2e-5, atol=2e-6)
            for k in dist.params:
                np.testing.assert_allclose(
                    np.asarray(dist.params[k]),
                    np.asarray(local.params[k]), rtol=2e-4, atol=2e-5)
            assert d_losses[0] > d_losses[-1], d_losses
            print(f"worker {r}/{w}: BERT parity OK", flush=True)
            """
        ))
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            codes.extend(local_backend.launch(
                2, [sys.executable, str(script)], env, timeout=300))

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]

    def test_local_launch_fit_external_sharded_parity(self, tmp_path):
        """Distributed OUT-OF-CORE training across real processes: each
        worker parses its InputSplit shard (part=rank, nparts=2) and
        fit_external syncs per-level histograms with allreduce_device
        over the cross-process backend.  With shared explicit cuts the
        distributed trees must equal a single-process fit_external over
        the full data tree-for-tree; the no-cuts run additionally
        exercises the cross-worker sketch allgather (loose oracle:
        the model still learns)."""
        import numpy as np

        rng = np.random.default_rng(17)
        X = rng.normal(size=(2000, 6)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + 0.3 * X[:, 2] > 0).astype(np.float32)
        data = tmp_path / "shard.libsvm"
        with open(data, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(6))
                f.write(f"{y[i]:.0f} {feats}\n")

        # single-process oracle over the FULL data, fixed cuts
        from dmlc_core_tpu.data.iter import RowBlockIter
        from dmlc_core_tpu.models import HistGBT
        from dmlc_core_tpu.ops.quantile import compute_cuts

        cuts = np.asarray(compute_cuts(X, 32))
        np.save(tmp_path / "cuts.npy", cuts)
        it = RowBlockIter.create(str(data), 0, 1, "libsvm")
        oracle = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                         hist_method="segment")
        oracle.fit_external(it, num_col=6, cuts=cuts)
        it.close()
        np.savez(tmp_path / "expected.npz",
                 feat=np.stack([t["feat"] for t in oracle.trees]),
                 thr=np.stack([t["thr"] for t in oracle.trees]),
                 leaf=np.stack([t["leaf"] for t in oracle.trees]))

        script = tmp_path / "ext_worker.py"
        script.write_text(textwrap.dedent(
            """
            import os
            from dmlc_core_tpu.utils import force_cpu_devices
            force_cpu_devices(1)
            import numpy as np
            from dmlc_core_tpu.parallel import collectives as coll
            coll.init()
            from dmlc_core_tpu.data.iter import RowBlockIter
            from dmlc_core_tpu.models import HistGBT

            r, w = coll.rank(), coll.world_size()
            base = os.environ["TEST_DIR"]
            cuts = np.load(os.path.join(base, "cuts.npy"))
            exp = np.load(os.path.join(base, "expected.npz"))

            it = RowBlockIter.create(
                os.path.join(base, "shard.libsvm"), r, w, "libsvm")
            m = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                        hist_method="segment")
            m.fit_external(it, num_col=6, cuts=cuts)
            it.close()
            np.testing.assert_array_equal(
                np.stack([t["feat"] for t in m.trees]), exp["feat"])
            np.testing.assert_array_equal(
                np.stack([t["thr"] for t in m.trees]), exp["thr"])
            np.testing.assert_allclose(
                np.stack([t["leaf"] for t in m.trees]), exp["leaf"],
                rtol=2e-4, atol=2e-5)

            # no-cuts path: cross-worker sketch allgather merges the
            # shard summaries; the model must still learn
            it = RowBlockIter.create(
                os.path.join(base, "shard.libsvm"), r, w, "libsvm")
            m2 = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                         hist_method="segment")
            m2.fit_external(it, num_col=6)
            it.close()
            Xl = np.load(os.path.join(base, "X.npy"))
            yl = np.load(os.path.join(base, "y.npy"))
            acc = ((m2.predict(Xl) > 0.5) == yl).mean()
            assert acc > 0.88, acc
            print(f"worker {r}/{w}: sharded fit_external parity OK "
                  f"(sketch-merged acc {acc:.3f})", flush=True)
            """
        ))
        np.save(tmp_path / "X.npy", X)
        np.save(tmp_path / "y.npy", y)
        from dmlc_core_tpu.tracker import local as local_backend

        codes = []

        def fun_submit(n, envs):
            env = dict(envs)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            env["TEST_DIR"] = str(tmp_path)
            codes.extend(local_backend.launch(
                2, [sys.executable, str(script)], env, timeout=300))

        tracker_submit(2, 0, fun_submit, host_ip="127.0.0.1")
        assert codes == [0, 0]


class TestReduceScatter:
    def test_sum_matches_allreduce_slice(self):
        import jax
        import numpy as np
        from dmlc_core_tpu.parallel import collectives as coll
        from dmlc_core_tpu.parallel.mesh import local_mesh

        mesh = local_mesh()
        k = mesh.shape["data"]
        x = jnp.asarray(np.arange(8 * k * 3, dtype=np.float32).reshape(k * 4, 6))
        out = coll.device_reduce_scatter(x, mesh, "sum")
        # replicated input ⇒ reduce over axis = k·x; each shard holds its slice
        want = np.asarray(x) * k
        got = np.asarray(out)
        np.testing.assert_allclose(got, want)

    def test_max(self):
        import numpy as np
        from dmlc_core_tpu.parallel import collectives as coll
        from dmlc_core_tpu.parallel.mesh import local_mesh

        mesh = local_mesh()
        k = mesh.shape["data"]
        x = jnp.asarray(np.random.default_rng(0).normal(size=(k * 2, 4)).astype(np.float32))
        got = np.asarray(coll.device_reduce_scatter(x, mesh, "max"))
        np.testing.assert_allclose(got, np.asarray(x))  # max of replicas = x

    def test_indivisible_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.parallel import collectives as coll
        from dmlc_core_tpu.parallel.mesh import local_mesh

        mesh = local_mesh()
        if mesh.shape["data"] == 1:
            pytest.skip("needs >1 device")
        bad = jnp.zeros((mesh.shape["data"] + 1, 2))
        with pytest.raises(Error):
            coll.device_reduce_scatter(bad, mesh)


class TestZeroAdam:
    def test_matches_replicated_adam(self):
        """ZeRO-sharded Adam must produce the same trajectory as plain
        replicated Adam on the globally-summed gradients."""

        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import local_mesh
        from dmlc_core_tpu.parallel.zero import ZeroAdam

        mesh = local_mesh()
        Pn = mesh.shape["data"]
        rng = np.random.default_rng(0)
        # parameter sizes deliberately NOT multiples of P (padding path)
        params = {"w": rng.normal(size=(13, 3)).astype(np.float32),
                  "b": rng.normal(size=(5,)).astype(np.float32)}
        # per-device local gradients: global grad = mean over devices
        gw = rng.normal(size=(Pn, 13, 3)).astype(np.float32)
        gb = rng.normal(size=(Pn, 5)).astype(np.float32)

        opt = ZeroAdam(lr=0.1)

        def train(params, gw_shard, gb_shard):
            state = opt.init(params)
            for _ in range(3):
                params, state = opt.step(
                    params, {"w": gw_shard[0], "b": gb_shard[0]}, state)
            return params

        fn = jax.jit(shard_map(
            train, mesh=mesh,
            in_specs=(P(), P("data"), P("data")), out_specs=P(),
            check_vma=False))
        out = jax.tree.map(np.asarray, fn(params, gw, gb))

        # replicated-Adam oracle on the mean gradients
        def adam_oracle(p, g, steps=3, lr=0.1, b1=0.9, b2=0.999, eps=1e-8):
            mu = np.zeros_like(p); nu = np.zeros_like(p)
            for t in range(1, steps + 1):
                mu = b1 * mu + (1 - b1) * g
                nu = b2 * nu + (1 - b2) * g * g
                p = p - lr * (mu / (1 - b1**t)) / (
                    np.sqrt(nu / (1 - b2**t)) + eps)
            return p
        want_w = adam_oracle(params["w"], gw.mean(0))
        want_b = adam_oracle(params["b"], gb.mean(0))
        np.testing.assert_allclose(out["w"], want_w, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(out["b"], want_b, rtol=1e-4, atol=1e-5)

    def test_state_is_sharded(self):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import local_mesh
        from dmlc_core_tpu.parallel.zero import ZeroAdam

        mesh = local_mesh()
        Pn = mesh.shape["data"]
        params = {"w": np.zeros((16, 4), np.float32)}
        opt = ZeroAdam()

        def init_only(params):
            st = opt.init(params)
            return st.mu["w"].shape[0]

        fn = jax.jit(shard_map(lambda p: jnp.asarray(init_only(p)),
                               mesh=mesh, in_specs=(P(),), out_specs=P(),
                               check_vma=False))
        per_dev = int(np.asarray(fn(params)))
        assert per_dev == 64 // Pn      # each device holds 1/P of the state

    def test_nested_pytree_params(self):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import local_mesh
        from dmlc_core_tpu.parallel.zero import ZeroAdam

        mesh = local_mesh()
        params = {"layer": {"w": np.ones((4, 2), np.float32)},
                  "head": np.ones(3, np.float32)}
        grads = jax.tree.map(np.ones_like, params)
        opt = ZeroAdam(lr=0.1)

        def one(p, g):
            st = opt.init(p)
            p2, _ = opt.step(p, g, st)
            return p2

        fn = jax.jit(shard_map(one, mesh=mesh, in_specs=(P(), P()),
                               out_specs=P(), check_vma=False))
        out = jax.tree.map(np.asarray, fn(params, grads))
        np.testing.assert_allclose(out["layer"]["w"], 0.9, atol=1e-5)
        np.testing.assert_allclose(out["head"], 0.9, atol=1e-5)
