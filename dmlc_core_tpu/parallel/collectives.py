"""Rabit-shaped collectives, re-founded on XLA.

Reference parity: the worker-side rabit API (``Allreduce<op>``,
``Broadcast``, ``rank``/``world_size``, ``CheckPoint``) that dmlc-core's
tracker coordinates, plus the tracker's topology math
(``tracker/dmlc_tracker/tracker.py :: get_tree / find_share_ring /
get_link_map`` — SURVEY.md §2c).

Engine replacement (the north star): there are no sockets here.

* **In-jit path (the fast path)**: ``device_allreduce`` /
  ``device_allgather`` are ``shard_map``-based XLA collectives on a named
  mesh — histogram sync, gradient sync, anything inside a train step rides
  ICI/DCN with XLA-scheduled overlap.  This is what the hist-GBT flagship
  and the KVStore shim compile onto.
* **Host path (rabit API parity)**: ``allreduce(np_array)`` etc. work on
  host values *between* steps, across processes, via the JAX runtime's
  global device set.  Coordination (rank assignment, liveness) is
  ``jax.distributed`` — bootstrapped from the ``DMLC_*`` env ABI by
  :func:`init`, keeping the reference's launch contract intact.

Topology functions are retained because (a) the tracker still serves them
to non-JAX legacy workers, and (b) they are the oracle for our tests'
parity with the reference's coordination brain.
"""

from __future__ import annotations

import contextlib
import os
import threading
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import CHECK, LOG, log_fatal
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.utils.profiler import global_tracer, tracing_enabled

__all__ = [
    "init", "finalize", "rank", "world_size", "is_distributed",
    "allreduce", "broadcast", "allgather", "barrier",
    "allreduce_device",
    "device_allreduce", "device_allgather", "device_reduce_scatter",
    "replicate_fwd_psum_bwd", "record_hist_psum",
    "set_host_transport", "get_host_transport",
    "get_tree", "find_share_ring", "get_link_map",
]

_initialized = False

# ---------------------------------------------------------------------------
# pluggable host-collective transport (rabit wire parity)
# ---------------------------------------------------------------------------
# When multi-process XLA collectives are unavailable (the CPU backend
# refuses multiprocess computations entirely) the elastic recovery layer
# (``parallel.recovery``) runs the host collectives over the tracker's
# TCP protocol instead — rabit's actual wire role.  An installed
# transport overrides rank/world_size and every HOST-path collective in
# this module; the in-jit device collectives are untouched (they stay
# mesh-local).  Storage is thread-local so in-process multi-worker
# harnesses (one worker per thread, each with its own transport+rank)
# compose — exactly how the drill tests exercise the protocol.

_HOST_TRANSPORT = threading.local()


def set_host_transport(transport: Optional[Any]) -> None:
    """Install (``None`` clears) this thread's host-collective transport.

    A transport duck-types ``rank``/``world`` attributes and
    ``allreduce(np_array, op)`` / ``allgather(np_array)`` /
    ``broadcast(value, root)`` / ``barrier(name)`` methods — see
    ``parallel.recovery.ElasticSession``.
    """
    _HOST_TRANSPORT.t = transport


def get_host_transport() -> Optional[Any]:
    """The transport installed on this thread (None = native jax path)."""
    return getattr(_HOST_TRANSPORT, "t", None)

_REDUCERS = {
    "sum": np.add.reduce,
    "max": np.maximum.reduce,
    "min": np.minimum.reduce,
    "prod": np.multiply.reduce,
    "bitor": np.bitwise_or.reduce,
}

_CM = None


def _coll_metrics():
    global _CM
    if _CM is None:
        r = _metrics.default_registry()
        _CM = {
            "calls": r.counter("collective_calls_total",
                               "collective invocations", labels=("op",)),
            "bytes": r.counter("collective_bytes_total",
                               "payload bytes entering collectives",
                               labels=("op",)),
            "seconds": r.histogram("collective_seconds",
                                   "host-path collective latency",
                                   labels=("op",)),
            "hist_psum": r.counter(
                "histogram_psum_bytes_total",
                "per-chip bytes contributed to in-step histogram-sync "
                "allreduces (analytic traffic model; XLA hides the "
                "collective itself from host instrumentation)",
                labels=("engine",)),
        }
    return _CM


def record_hist_psum(nbytes: int, engine: str = "incore") -> None:
    """Account the histogram-sync psum traffic of a dispatched round
    program.

    The per-level psum rides INSIDE the jitted shard_map program, so the
    host-path instrumentation around :func:`allreduce` /
    :func:`allreduce_device` never sees it — the training engine calls
    this with the analytic per-dispatch byte count
    (:func:`~dmlc_core_tpu.ops.histogram.hist_psum_bytes_per_round` ×
    rounds × output trees) instead.  No-op when metrics are disabled.
    """
    if nbytes > 0 and _metrics.enabled():
        _coll_metrics()["hist_psum"].inc(nbytes, engine=engine)


@contextlib.contextmanager
def _host_op_span(op: str, nbytes: int):
    """Metrics + trace span around one host-path collective.

    The host collectives run BETWEEN steps, so their wall time is real
    blocked-training time — worth a latency histogram (the in-jit device
    collectives dispatch async and are timed by the device profiler, not
    here).  Fast-exits to a bare yield when both sinks are off.
    """
    collect = _metrics.enabled()
    if not collect and not tracing_enabled():
        yield
        return
    ctx = (global_tracer().scope(f"collective.{op}", bytes=int(nbytes))
           if tracing_enabled() else contextlib.nullcontext())
    t0 = get_time()
    try:
        with ctx:
            yield
    finally:
        if collect:
            m = _coll_metrics()
            m["calls"].inc(1, op=op)
            if nbytes:
                m["bytes"].inc(nbytes, op=op)
            m["seconds"].observe(get_time() - t0, op=op)


# ---------------------------------------------------------------------------
# bootstrap: DMLC_* env ABI → jax.distributed
# ---------------------------------------------------------------------------

def init(args: Optional[Dict[str, str]] = None) -> None:
    """Initialize distributed state from the ``DMLC_*`` env ABI.

    Reference parity: rabit's ``Init(argc, argv)`` reading
    ``DMLC_TRACKER_URI``/``DMLC_TRACKER_PORT``/``DMLC_TASK_ID``/
    ``DMLC_NUM_WORKER`` (SURVEY.md §2c env-var ABI).  Here those map onto
    ``jax.distributed.initialize(coordinator, num_processes, process_id)``
    — the JAX coordination service replaces the rabit tracker protocol.

    Single-process (no env set) is a no-op: everything below degrades to
    identity collectives, so the same program runs 1-chip or pod-scale.
    """
    global _initialized
    if _initialized:
        return
    env = dict(os.environ)
    if args:
        env.update(args)
    nworker = int(env.get("DMLC_NUM_WORKER", "1"))
    if nworker <= 1:
        _initialized = True
        return
    uri = env.get("DMLC_TRACKER_URI")
    port = env.get("DMLC_TRACKER_PORT", "9091")
    task_id = int(env.get("DMLC_TASK_ID", "0"))
    CHECK(uri is not None, "DMLC_NUM_WORKER > 1 but DMLC_TRACKER_URI unset")
    jax.distributed.initialize(
        coordinator_address=f"{uri}:{port}",
        num_processes=nworker,
        process_id=task_id,
    )
    _initialized = True
    LOG("INFO", "dmlc collectives: process %d/%d online", task_id, nworker)


def finalize() -> None:
    """Reference parity: rabit ``Finalize()``."""
    global _initialized
    if _initialized and jax.process_count() > 1:
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    _initialized = False


def rank() -> int:
    """This worker's rank.  Reference: rabit ``GetRank`` = process index
    (or the installed host transport's rank)."""
    t = get_host_transport()
    if t is not None:
        return t.rank
    return jax.process_index()


def world_size() -> int:
    """Number of workers.  Reference: rabit ``GetWorldSize``."""
    t = get_host_transport()
    if t is not None:
        return t.world
    return jax.process_count()


def is_distributed() -> bool:
    """True once :func:`init` has joined a multi-process
    ``jax.distributed`` cluster (world size > 1) or a host transport
    spanning multiple workers is installed."""
    return world_size() > 1


# ---------------------------------------------------------------------------
# host-level collectives (rabit API parity, between-step granularity)
# ---------------------------------------------------------------------------

def allreduce(x: np.ndarray, op: str = "sum") -> np.ndarray:
    """Allreduce a host array across processes.

    Reference parity: rabit ``Allreduce<op>(ptr, count)``.  Implemented as
    process-allgather + local reduce through the JAX runtime (exact for
    every op incl. non-commutative-sensitive float sums: every rank reduces
    in the same rank order, so results are bitwise identical across
    workers — the determinism rabit guaranteed via its fixed tree).
    For in-step sync use :func:`device_allreduce`, which stays on ICI.
    """
    x = np.asarray(x)
    if op not in _REDUCERS:
        log_fatal(f"allreduce: unknown op {op!r}; valid: {sorted(_REDUCERS)}")
    with _host_op_span("allreduce", x.nbytes):
        t = get_host_transport()
        if t is not None:
            return t.allreduce(x, op)
        if world_size() == 1:
            return x
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(x, tiled=False)  # [world, ...]
        return _REDUCERS[op](np.asarray(gathered), axis=0)


def broadcast(x: Any, root: int = 0) -> Any:
    """Broadcast a host value from ``root``.  Reference: rabit ``Broadcast``."""
    with _host_op_span("broadcast", getattr(x, "nbytes", 0)):
        t = get_host_transport()
        if t is not None:
            return t.broadcast(x, root)
        if world_size() == 1:
            return x
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(x, is_source=rank() == root)


def allgather(x: np.ndarray) -> np.ndarray:
    """Gather arrays from all processes, stacked on axis 0 in rank order."""
    x = np.asarray(x)
    with _host_op_span("allgather", x.nbytes):
        t = get_host_transport()
        if t is not None:
            return t.allgather(x)
        if world_size() == 1:
            return x[None]
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=False))


def barrier(name: str = "dmlc") -> None:
    """Cross-process barrier (rabit's implicit sync points, made explicit)."""
    with _host_op_span("barrier", 0):
        t = get_host_transport()
        if t is not None:
            t.barrier(name)
            return
        if world_size() == 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


@lru_cache(maxsize=None)
def _world_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()), ("world",))


@lru_cache(maxsize=None)
def _jitted_world_psum(mesh: Mesh):
    @partial(shard_map, mesh=mesh, in_specs=P("world"), out_specs=P(),
             check_vma=False)
    def _ps(shard):                      # [1, ...] per device
        return jax.lax.psum(shard[0], "world")

    return jax.jit(_ps)


def allreduce_device(x: jax.Array) -> jax.Array:
    """Sum a per-process DEVICE array across all processes, returning a
    device array — no host round-trip.

    The fix for the external-memory training loop (BASELINE config 3):
    per-level page histograms accumulate on device and sync here as one
    XLA AllReduce over ICI/DCN, where :func:`allreduce` would fetch to
    host, allgather, and re-reduce in numpy every level.  Each process
    contributes its value once (staged on its first local device; other
    local devices contribute zeros), so multi-device processes are safe.

    With a host transport installed this degrades to a host round trip
    (fetch → tracker-mediated deterministic sum → device) — the rabit
    wire path for backends without multiprocess XLA collectives.
    """
    t = get_host_transport()
    if t is not None:
        return jnp.asarray(t.allreduce(np.asarray(x), "sum"))
    if world_size() == 1:
        return x
    if _metrics.enabled():
        # calls + bytes only: the result is returned un-synced, so wall
        # time here would measure dispatch, not the collective
        m = _coll_metrics()
        m["calls"].inc(1, op="allreduce_device")
        m["bytes"].inc(getattr(x, "nbytes", 0), op="allreduce_device")
    mesh = _world_mesh()
    locals_ = jax.local_devices()
    x = jnp.asarray(x)
    shards = [jax.device_put(x[None] if i == 0
                             else jnp.zeros((1, *x.shape), x.dtype), d)
              for i, d in enumerate(locals_)]
    garr = jax.make_array_from_single_device_arrays(
        (len(jax.devices()), *x.shape),
        NamedSharding(mesh, P("world")), shards)
    out = _jitted_world_psum(mesh)(garr)
    return jnp.asarray(out.addressable_data(0))


# ---------------------------------------------------------------------------
# in-jit collectives (the TPU fast path)
# ---------------------------------------------------------------------------

_LAX_REDUCE = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


@lru_cache(maxsize=None)
def _jitted_allreduce(mesh: Mesh, op: str, axis: str):
    """One stable jitted reducer per (mesh, op, axis).

    jax.jit caches compilations by function identity + input avals, so
    returning the SAME jitted callable here means repeated calls (e.g. the
    KVStore pulling every gradient key each step) hit the jit cache instead
    of retracing and recompiling per call.
    """
    lax_op = _LAX_REDUCE[op]
    local_op = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[op]

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def _reduce(shard):
        return lax_op(local_op(shard, axis=0), axis)

    return jax.jit(_reduce)


def device_allreduce(x: jax.Array, mesh: Mesh, op: str = "sum",
                     axis: str = "data") -> jax.Array:
    """Allreduce per-device shards over a mesh axis, on-device.

    ``x`` is sharded on ``axis`` along dim 0 (one shard per device); the
    result is the reduced array, replicated.  Lowers to a single XLA
    AllReduce riding ICI — this is the histogram-sync primitive
    (north star: replaces rabit's socket tree allreduce).

    Composable: call inside your own jit/shard_map too — this helper is
    just the standalone spelling.
    """
    if op not in _LAX_REDUCE:
        log_fatal(f"device_allreduce: unknown op {op!r}")
    return _jitted_allreduce(mesh, op, axis)(x)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def replicate_fwd_psum_bwd(x: jax.Array, axis: str) -> jax.Array:
    """Identity forward, ``psum`` over ``axis`` backward (Megatron's *f*).

    Marks the boundary where a replicated activation enters computation
    sharded over ``axis`` (tensor parallelism): the forward is free, and
    the backward all-reduces the partial cotangents so every shard holds
    the COMPLETE gradient.  Without it, parameters upstream of the
    boundary would see only their shard's contribution — and a blanket
    per-parameter psum instead double-counts the residual-stream path.
    Use inside shard_map.
    """
    return x


def _rfpb_fwd(x, axis):
    del axis
    return x, None


def _rfpb_bwd(axis, _res, ct):
    return (jax.lax.psum(ct, axis),)


replicate_fwd_psum_bwd.defvjp(_rfpb_fwd, _rfpb_bwd)


@lru_cache(maxsize=None)
def _jitted_allgather(mesh: Mesh, axis: str):
    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    def _gather(shard):
        return jax.lax.all_gather(shard, axis, tiled=True)

    return jax.jit(_gather)


def device_allgather(x: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """All-gather shards over a mesh axis (XLA AllGather on ICI)."""
    return _jitted_allgather(mesh, axis)(x)


@lru_cache(maxsize=None)
def _jitted_reduce_scatter(mesh: Mesh, axis: str, op: str):
    def _rs(full):
        if op == "sum":
            return jax.lax.psum_scatter(full, axis, tiled=True)
        # max/min have no fused scatter primitive: reduce then slice
        red = (jax.lax.pmax if op == "max" else jax.lax.pmin)(full, axis)
        k = mesh.shape[axis]          # static (lax.axis_size is newer jax)
        i = jax.lax.axis_index(axis)
        piece = full.shape[0] // k
        return jax.lax.dynamic_slice_in_dim(red, i * piece, piece, axis=0)

    return jax.jit(partial(shard_map, mesh=mesh, in_specs=P(),
                           out_specs=P(axis), check_vma=False)(_rs))


def device_reduce_scatter(x: jax.Array, mesh: Mesh, op: str = "sum",
                          axis: str = "data") -> jax.Array:
    """Reduce over the mesh axis, leaving each device its 1/k slice of
    dim 0 (XLA ReduceScatter on ICI) — the bandwidth-optimal half of an
    allreduce, the building block for ZeRO-style sharded optimizers.

    ``x`` is replicated input with dim 0 divisible by the axis size; the
    result is sharded over ``axis`` along dim 0.
    """
    if op not in ("sum", "max", "min"):
        log_fatal(f"reduce_scatter: unknown op {op!r}; valid: sum/max/min")
    if x.shape[0] % mesh.shape[axis]:
        log_fatal(
            f"reduce_scatter: dim 0 ({x.shape[0]}) not divisible by "
            f"axis {axis!r} size {mesh.shape[axis]}")
    return _jitted_reduce_scatter(mesh, axis, op)(x)


# ---------------------------------------------------------------------------
# topology math (tracker parity; oracle-tested)
# ---------------------------------------------------------------------------

def get_tree(n: int) -> Tuple[Dict[int, int], Dict[int, List[int]]]:
    """Binary reduction tree over ranks 0..n-1.

    Reference parity: ``tracker.py :: get_tree`` — parent(r) = (r-1)//2.
    Returns (parent_map, children_map); root's parent is -1.
    """
    parent: Dict[int, int] = {0: -1}
    children: Dict[int, List[int]] = {r: [] for r in range(n)}
    for r in range(1, n):
        p = (r - 1) // 2
        parent[r] = p
        children[p].append(r)
    return parent, children


def find_share_ring(children: Dict[int, List[int]], root: int = 0) -> List[int]:
    """Ring order as a depth-first traversal of the tree.

    Reference parity: ``tracker.py :: find_share_ring`` — DFS of the
    reduction tree yields a ring where every hop is also a tree edge or
    close to one, so the two topologies share physical links.
    """
    order: List[int] = []

    def dfs(r: int) -> None:
        order.append(r)
        for c in children[r]:
            dfs(c)

    dfs(root)
    return order


def get_link_map(n: int) -> Dict[int, Dict[str, Any]]:
    """Per-rank connection map: tree parent/children + ring prev/next.

    Reference parity: ``tracker.py :: get_link_map`` — this is the payload
    the tracker sends each worker at 'start'.
    """
    parent, children = get_tree(n)
    ring = find_share_ring(children)
    pos = {r: i for i, r in enumerate(ring)}
    out: Dict[int, Dict[str, Any]] = {}
    for r in range(n):
        i = pos[r]
        out[r] = {
            "parent": parent[r],
            "children": list(children[r]),
            "ring_prev": ring[(i - 1) % n],
            "ring_next": ring[(i + 1) % n],
        }
    return out
