"""Central registry of every ``DMLC_*`` environment knob.

The reference scatters ``dmlc::GetEnv<T>`` reads across subsystems and
documents them nowhere; after four PRs this substrate had grown ~40
``DMLC_*`` reads with exactly the same drift.  This module is the single
source of truth: every knob the codebase reads MUST be declared here
(name, default, one-line doc), and ``scripts/dmlcheck.py``'s
``knob-registry`` pass fails CI on any literal ``DMLC_*`` string in code
that has no entry — plus any entry that never shows up under ``doc/``
(``doc/configuration.md`` is generated from this registry by
``scripts/gen_api_docs.py`` and gated stale-vs-committed in CI).

Declaring a knob does not change how call sites read it (``os.environ``
/ :func:`~dmlc_core_tpu.base.parameter.get_env` stay as they are); the
registry is the contract layer, not a read path.  :func:`value` is
provided for new call sites that want the declared default applied
automatically.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional

__all__ = ["Knob", "declare", "get", "all_knobs", "names", "value"]


class Knob(NamedTuple):
    """One declared environment knob."""

    #: full environment-variable name (``DMLC_...``)
    name: str
    #: default the reading call site applies when the var is unset
    default: Any
    #: one-line description (becomes the doc/configuration.md table row)
    doc: str
    #: subsystem bucket for the generated doc table ordering
    group: str


_REGISTRY: Dict[str, Knob] = {}


def declare(name: str, default: Any, doc: str, group: str = "misc") -> Knob:
    """Register a knob; re-declaring with identical fields is a no-op,
    conflicting re-declaration raises (same discipline as the metrics
    registry)."""
    if not name.startswith("DMLC_"):
        raise ValueError(f"knob {name!r} must start with DMLC_")
    existing = _REGISTRY.get(name)
    k = Knob(name, default, doc, group)
    if existing is not None:
        if existing != k:
            raise ValueError(f"knob {name!r} re-declared with different "
                            f"fields: {existing} vs {k}")
        return existing
    _REGISTRY[name] = k
    return k


def get(name: str) -> Optional[Knob]:
    """Look up a declared knob (None when unknown)."""
    return _REGISTRY.get(name)


def names() -> List[str]:
    """All declared knob names, sorted."""
    return sorted(_REGISTRY)


def all_knobs() -> List[Knob]:
    """All declared knobs, sorted by (group, name) — the order the
    generated doc table uses."""
    return sorted(_REGISTRY.values(), key=lambda k: (k.group, k.name))


def value(name: str) -> Any:
    """Read a declared knob from the environment with its declared
    default applied (type inferred from the default, via
    :func:`~dmlc_core_tpu.base.parameter.get_env`)."""
    from dmlc_core_tpu.base.parameter import get_env

    k = _REGISTRY.get(name)
    if k is None:
        raise KeyError(f"knob {name!r} is not declared in base/knobs.py")
    return get_env(k.name, k.default)


# ---------------------------------------------------------------------------
# The declarations.  Grouped by subsystem; each ``doc`` line is exactly
# what doc/configuration.md renders.  Defaults mirror the reading call
# site — the knob-registry pass checks presence, the doc gate checks
# documentation, and drift between this default and the call site's is a
# review-visible diff in one place instead of a silent env archaeology.
# ---------------------------------------------------------------------------

# -- runtime / debugging ----------------------------------------------------
declare("DMLC_TPU_FORCE_CPU", "",
        "Force jax onto N host CPU devices before first backend init "
        "(tests/CI); empty disables.", "runtime")
declare("DMLC_TPU_NATIVE_LIB", "",
        "Explicit path to the native helper shared library (overrides "
        "the bundled lookup).", "runtime")
declare("DMLC_TPU_NATIVE_IO", "1",
        "0 disables the C fast paths (recordio/parsers/queues) in favor "
        "of pure-Python fallbacks.", "runtime")
declare("DMLC_TRACE", "0",
        "1 enables the process-wide event Tracer "
        "(utils/profiler.set_tracing).", "observability")
declare("DMLC_METRICS", "1",
        "0 disables the metrics registry: instruments become no-ops "
        "(base/metrics).", "observability")
declare("DMLC_METRICS_GBT_PHASES", "0",
        "1 adds per-phase hist/split/leaf/apply timing in the external "
        "GBT engine (adds device syncs).", "observability")
declare("DMLC_DRYRUN_NESTED", "0",
        "Internal recursion guard for the multichip dryrun harness "
        "(__graft_entry__); not user-facing.", "runtime")
declare("DMLC_LOCKCHECK", "0",
        "1 installs the dynamic lock-order verifier at import: lock "
        "acquisitions build a cross-thread order graph and cycles are "
        "reported (base/lockcheck).", "observability")
declare("DMLC_RACECHECK", "0",
        "1 installs the vector-clock happens-before race detector at "
        "import (implies lock tracing): shared-attribute accesses on "
        "the instrumented serving/tracker classes are checked for "
        "unordered cross-thread pairs (base/racecheck).", "observability")
declare("DMLC_LEAKCHECK", "0",
        "1 installs the resource-leak tracer at import: every "
        "socket/thread/subprocess/tempfile created through repo code "
        "is recorded with its creation stack, and whatever is still "
        "live at drill exit is reported (base/leakcheck).",
        "observability")
declare("DMLC_JITCHECK", "0",
        "1 installs the XLA-compile tracer at import: every "
        "compilation is recorded with its repo-frame stack and phase "
        "tag (warmup/steady), and any compile after the bench/drill "
        "declares steady state fails check() (base/jitcheck).",
        "observability")
declare("DMLC_INTERLEAVE_SCHEDULES", 200,
        "Schedule budget per model for the interleave model checker "
        "(analysis/interleave).", "observability")
declare("DMLC_METRICS_SPOOL", "",
        "Directory for the cross-process metrics spool: each process "
        "writes its registry snapshot (and trace shard) there for "
        "fleet-wide merging (base/metrics_agg); empty disables.",
        "observability")
declare("DMLC_METRICS_SPOOL_S", 2.0,
        "Seconds between periodic spool snapshot flushes; <= 0 keeps "
        "only the at-exit flush.", "observability")
declare("DMLC_TRACE_CTX", "",
        "Wire-encoded trace context a launcher injects so child "
        "processes join the parent's distributed trace "
        "(base/tracectx); empty starts fresh.", "observability")
declare("DMLC_SLO_SPEC", "",
        "Default SLO spec JSON path for scorecard evaluation "
        "(base/slo; bench.py --slo overrides); empty disables.",
        "observability")

# -- GBT / compute ----------------------------------------------------------
declare("DMLC_TPU_ROUNDS_PER_DISPATCH", 25,
        "Boosting rounds fused per device dispatch in the dense "
        "engine.", "gbt")
declare("DMLC_TPU_SPARSE_ROUNDS_PER_DISPATCH", 8,
        "Rounds fused per device dispatch in the sparse engine.", "gbt")
declare("DMLC_TPU_BIN_BACKEND", "",
        "'cpu' forces host-numpy feature binning; empty bins on "
        "device.", "gbt")
declare("DMLC_TPU_SKETCH_BACKEND", "",
        "'cpu' forces the host quantile-sketch path; empty sketches on "
        "device.", "gbt")
declare("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", 6 << 30,
        "Device-memory budget in bytes for resident bin pages in the "
        "external-memory engine.", "gbt")
declare("DMLC_INGEST_CHUNK_ROWS", 2_000_000,
        "Rows per double-buffered host-to-device ingest slab "
        "(cold-start streaming).", "gbt")
declare("DMLC_HIST_BLOCKS", 0,
        "N>0 enables the mesh-shape-invariant deterministic histogram "
        "reduction with N fixed row blocks (rounded up to a power of "
        "two >= the data-axis size): trees become bit-identical across "
        "mesh shapes; 0 keeps the faster plain psum.", "gbt")
declare("DMLC_BIN_PACK", "0",
        "1 packs narrow features two-per-byte (int4) in the transposed "
        "bin matrix: features whose OCCUPIED bin count is <= 16 are "
        "compact-remapped and nibble-paired, shrinking the HBM bin "
        "traffic every histogram pass pays; split decisions and "
        "save_model bytes are bit-identical.", "gbt")
declare("DMLC_FEATURE_BUNDLE", "0",
        "1 fuses mutually-exclusive (near-one-hot) feature blocks into "
        "one multi-bin storage feature (LightGBM's EFB with the "
        "most-frequent bin as the default): histograms build on fewer "
        "rows and are exactly unbundled at split evaluation; the "
        "default-bin cell is reconstructed as total - segment, so this "
        "lever is off by default (last-ulp float reassociation).", "gbt")

# -- compile cache ----------------------------------------------------------
declare("DMLC_COMPILE_CACHE", "1",
        "0 disables the persistent compilation cache "
        "(base/compile_cache).", "compile-cache")
declare("DMLC_COMPILE_CACHE_EXPECT", "",
        "CI drill only: scripts/check_compile_cache.py asserts this "
        "outcome ('miss' or 'hit').", "compile-cache")

# -- io ---------------------------------------------------------------------
declare("DMLC_HDFS_NAMENODE", "",
        "Default namenode host:port for hdfs:// URIs "
        "(WebHDFS).", "io")
declare("DMLC_HDFS_USER", "$USER",
        "WebHDFS user.name query parameter.", "io")
declare("DMLC_IO_NO_ENDIAN_SWAP", "0",
        "1 disables the endianness swap in the binary serializer "
        "(big-endian hosts).", "io")
declare("DMLC_ITER_PRODUCER_RESTARTS", 0,
        "Process-wide default for ThreadedIter max_restarts (bounded "
        "producer-exception absorption).", "io")

# -- resilience -------------------------------------------------------------
declare("DMLC_RETRY_MAX_ATTEMPTS", 4,
        "RetryPolicy default attempt cap.", "resilience")
declare("DMLC_RETRY_DEADLINE_S", 60.0,
        "RetryPolicy default total-deadline seconds.", "resilience")
declare("DMLC_RETRY_BASE_S", 0.05,
        "RetryPolicy default base backoff seconds (exponential + full "
        "jitter).", "resilience")
declare("DMLC_RETRY_MAX_BACKOFF_S", 5.0,
        "RetryPolicy default per-sleep backoff cap in "
        "seconds.", "resilience")
declare("DMLC_CB_THRESHOLD", 5,
        "CircuitBreaker default consecutive-failure threshold before "
        "opening.", "resilience")
declare("DMLC_CB_RESET_S", 30.0,
        "CircuitBreaker default open-to-half-open probe delay in "
        "seconds.", "resilience")
declare("DMLC_CKPT_KEEP", "",
        "How many previous checkpoint versions to retain (.prev "
        "chain); empty = 1.", "resilience")
declare("DMLC_FAULT_INJECT", "",
        "Deterministic fault-injection spec "
        "('point:kind[=v][:p=][:n=][:after=][:at=][:every=],...'); "
        "empty disables.", "resilience")
declare("DMLC_FAULT_SEED", 1234,
        "Seed for the per-rule fault-injection RNG streams.", "resilience")
declare("DMLC_PRODSIM_SECONDS", 24.0,
        "Duration of the bench.py --prodsim production-day simulation "
        "load window in seconds (the chaos schedule scales with it).",
        "resilience")
declare("DMLC_PRODSIM_CHAOS", "",
        "Override chaos schedule for bench.py --prodsim (faultinject "
        "grammar with at=/every= wall-clock triggers); empty derives "
        "the default all-tier schedule from DMLC_PRODSIM_SECONDS.",
        "resilience")
declare("DMLC_RECOVERY_STRIDE", 5,
        "Boosting rounds between round-versioned collective checkpoint "
        "commits (the elastic-recovery floor granularity).", "resilience")
declare("DMLC_ELASTIC", "0",
        "1 re-shards the surviving workers (shrunk world, re-cut row "
        "shards) once a lost worker's grace lapses; 0 holds the world "
        "for a rejoining replacement.", "resilience")
declare("DMLC_RECOVERY_DIR", "",
        "Directory for per-rank round-versioned recovery checkpoints "
        "(parallel/recovery); empty requires an explicit "
        "recovery_dir=.", "resilience")

# -- serving ----------------------------------------------------------------
declare("DMLC_SERVE_PREWARM", "0",
        "1 pre-compiles the batch-bucket ladder at ModelRunner "
        "construction (serve cold-start).", "serve")

# -- fleet serving ----------------------------------------------------------
declare("DMLC_FLEET_VNODES", 64,
        "Virtual nodes per replica on the router's consistent-hash ring "
        "(more = smoother balance, larger ring).", "fleet")
declare("DMLC_FLEET_MAX_QUEUE", 512,
        "Fleet-wide queued-request bound for router admission control; "
        "beyond it predicts are shed with 503 + Retry-After.", "fleet")
declare("DMLC_FLEET_PROBE_S", 0.5,
        "Router health-probe / membership-refresh interval in "
        "seconds.", "fleet")
declare("DMLC_FLEET_FAILOVER", 2,
        "Extra replicas the router tries after the hash-primary fails "
        "(total attempts = 1 + this).", "fleet")
declare("DMLC_FLEET_HEARTBEAT_S", 0.5,
        "Replica load-report (serve_report) interval in "
        "seconds.", "fleet")
declare("DMLC_FLEET_WAVE_SIZE", 1,
        "Replicas activated per staged-rollout wave.", "fleet")
declare("DMLC_FLEET_SCALE_OUT_S", 0.05,
        "Queue-wait p99 seconds above which the autoscale policy "
        "recommends scale-out.", "fleet")
declare("DMLC_FLEET_SCALE_IN_S", 0.005,
        "Queue-wait p99 seconds below which the autoscale policy "
        "recommends scale-in.", "fleet")
declare("DMLC_FLEET_PATIENCE", 3,
        "Consecutive out-of-band autoscale observations required before "
        "a recommendation fires (hysteresis).", "fleet")
declare("DMLC_FLEET_MIN_REPLICAS", 1,
        "Autoscale floor on replica count.", "fleet")
declare("DMLC_FLEET_MAX_REPLICAS", 8,
        "Autoscale ceiling on replica count.", "fleet")

# -- multi-tenant serving ----------------------------------------------------
declare("DMLC_TENANT_RESIDENT_CAP", 0,
        "Maximum tenant models kept warm (runner resident) per replica; "
        "beyond it the least-recently-served tenant is paged out to its "
        "retained checkpoint bytes and warm-restored on next use. "
        "0 = unlimited (no paging).", "tenancy")
declare("DMLC_TENANT_CLASSES", "",
        "Tenant SLO class map, e.g. 'gold:acme,bar;bronze:baz' — "
        "semicolon-separated class:tenant,... groups.  Unlisted tenants "
        "get DMLC_TENANT_DEFAULT_CLASS.", "tenancy")
declare("DMLC_TENANT_DEFAULT_CLASS", "silver",
        "SLO class assumed for tenants absent from "
        "DMLC_TENANT_CLASSES (gold|silver|bronze).", "tenancy")
declare("DMLC_TENANT_QUOTA", 0,
        "Per-tenant cap on concurrent in-flight predicts at the router; "
        "beyond it THAT tenant is shed with 429 (no other tenant "
        "notices).  0 = no per-tenant quota.", "tenancy")
declare("DMLC_TENANT_MAX_INFLIGHT", 64,
        "Router-wide cap on concurrent tenant-tagged predicts; the "
        "overload axis tenant shedding is graded against (bronze shed "
        "at DMLC_TENANT_SHED_FRACTION of it, everyone at it).", "tenancy")
declare("DMLC_TENANT_SHED_FRACTION", 0.5,
        "Fraction of DMLC_TENANT_MAX_INFLIGHT at which bronze tenants "
        "start shedding with 429 — the 'bronze sheds before gold "
        "queues' contract (doc/serving.md).", "tenancy")
declare("DMLC_TENANT_HEDGE_MS", 0,
        "Gold-tenant hedge delay in milliseconds: when > 0 and a second "
        "ring candidate exists, a gold predict still in flight after "
        "this long is raced against the next replica; first success "
        "wins.  0 disables hedging.", "tenancy")

# -- streaming / online learning --------------------------------------------
declare("DMLC_STREAM_POLL_S", 0.05,
        "Tailer base poll interval in seconds; idle polls back off "
        "exponentially (with jitter) from here.", "stream")
declare("DMLC_STREAM_MAX_BACKOFF_S", 1.0,
        "Cap on the tailer's jittered idle-poll backoff in "
        "seconds.", "stream")
declare("DMLC_STREAM_CURSOR", "",
        "Default cursor checkpoint URI for RecordIOTailer.commit "
        "(crash-safe resume); empty = no default.", "stream")
declare("DMLC_STREAM_CHUNK_ROWS", 2048,
        "Fresh event rows gathered per online-trainer refresh.", "stream")
declare("DMLC_STREAM_WINDOW_CHUNKS", 4,
        "Sliding training window length in chunks; steady-state window "
        "row count (and compiled shapes) stay fixed once full.", "stream")
declare("DMLC_STREAM_DECAY", 1.0,
        "Per-chunk-age sample-weight decay in (0, 1]; 1.0 = pure "
        "sliding window (no weights, warm-start parity).", "stream")
declare("DMLC_STREAM_EVAL_GATE", 0.1,
        "Publisher eval-gate relative tolerance: a refresh is rejected "
        "when holdout score exceeds the active version's by more than "
        "this fraction.", "stream")

# -- distributed ABI (set by tracker/launchers, read by workers) ------------
declare("DMLC_ROLE", "worker",
        "Process role in a distributed job: worker / server / "
        "scheduler.", "distributed")
declare("DMLC_TRACKER_URI", "",
        "Tracker host the worker handshakes with.", "distributed")
declare("DMLC_TRACKER_PORT", "",
        "Tracker TCP port.", "distributed")
declare("DMLC_LEGACY_TRACKER_PORT", "",
        "Port of the legacy one-shot tracker protocol (elastic-recovery "
        "example ABI).", "distributed")
declare("DMLC_NUM_WORKER", 1,
        "Worker count the tracker coordinates.", "distributed")
declare("DMLC_NUM_SERVER", 0,
        "Parameter-server count (PS ABI only; the engine itself is the "
        "KVStore shim).", "distributed")
declare("DMLC_TASK_ID", 0,
        "This worker's task index within the job.", "distributed")
declare("DMLC_NUM_ATTEMPT", 0,
        "Restart attempt number of this task (elastic "
        "recovery).", "distributed")
declare("DMLC_PS_ROOT_URI", "",
        "PS scheduler host (PSTracker env ABI).", "distributed")
declare("DMLC_PS_ROOT_PORT", "",
        "PS scheduler port (PSTracker env ABI).", "distributed")
declare("DMLC_WORKDIR", "",
        "Remote working directory launchers cd into before exec'ing the "
        "worker command.", "distributed")
declare("DMLC_TRACKER_GRACE_S", 0.0,
        "Reconnect grace window in seconds before a lost persistent "
        "worker is declared dead.", "distributed")
declare("DMLC_KVSTORE_CHECK", 0,
        "1 enables out-of-mesh KVStore consistency checks (debug).",
        "distributed")

# -- multi-host launch ------------------------------------------------------
declare("DMLC_LAUNCH_RESTART_LIMIT", 2,
        "Per-rank respawn budget for a supervised JobSet (spawn "
        "failures and unexpected exits both consume it; 0 disables "
        "restarts).", "launch")
declare("DMLC_LAUNCH_MONITOR_S", 0.2,
        "JobSet supervisor poll interval in seconds (liveness poll, "
        "respawn scheduling, tracker cross-check).", "launch")
declare("DMLC_LAUNCH_GRACEFUL_S", 5.0,
        "Teardown grace in seconds between SIGTERM and SIGKILL when a "
        "JobSet shuts its workers down.", "launch")
declare("DMLC_LAUNCH_LOG_DIR", "",
        "Directory for per-worker launch log files; empty uses a fresh "
        "temp dir per transport.", "launch")
declare("DMLC_LAUNCH_WEDGE_CYCLES", 25,
        "Consecutive monitor cycles a rank may stay process-alive but "
        "tracker-lost before the JobSet declares it wedged and kills "
        "it for respawn.", "launch")

# -- parameter server -------------------------------------------------------
declare("DMLC_PS_STALENESS", 4,
        "Bounded-staleness window tau for dist_async pulls: a pull at "
        "worker clock c blocks until every worker committed c - tau; "
        "0 = BSP, negative = fully async (never block).", "ps")
declare("DMLC_PS_PIPELINE", 8,
        "In-flight request window per server connection: async pushes "
        "beyond this many unacked requests block the sender.", "ps")
declare("DMLC_PS_PULL_TIMEOUT_S", 60.0,
        "Seconds a pull may wait on the server-side staleness gate "
        "before erroring out.", "ps")
declare("DMLC_PS_RECONNECT_S", 30.0,
        "Deadline in seconds for re-resolving and re-dialing a lost "
        "server connection (respawn failover window).", "ps")
declare("DMLC_PS_SNAPSHOT_DIR", "",
        "Directory for per-server shard snapshots (atomic CRC'd "
        "checkpoints); empty disables durability.", "ps")
declare("DMLC_PS_SNAPSHOT_STRIDE", 0,
        "Committed clock ticks between shard snapshots; 0 disables "
        "periodic snapshots.", "ps")
declare("DMLC_PS_SERVER_ID", -1,
        "Server shard id for DMLC_ROLE=server processes; -1 lets the "
        "scheduler assign the next free id (a respawn passes its old "
        "id to reclaim the shard).", "ps")
declare("DMLC_PS_SERVER_URI", "127.0.0.1",
        "Host/interface a DMLC_ROLE=server process binds its data "
        "plane to (advertised to the scheduler).", "ps")
