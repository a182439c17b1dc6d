"""dmlc_core_tpu — a TPU-native infrastructure substrate with the capabilities
of dmlc-core (the common library under XGBoost / MXNet / TVM).

This is NOT a port of the C++ reference.  It keeps dmlc-core's *contracts* —
URI-dispatched ``Stream`` I/O, sharded ``InputSplit`` + RecordIO, LibSVM/CSV/
LibFM parsers producing CSR ``RowBlock``s, threaded prefetch iterators,
binary/JSON serialization, the typed ``Parameter``/``Registry`` system and the
``DMLC_*`` distributed-launch ABI — while re-founding the *engines* on
JAX/XLA/Pallas:

* parsed row blocks become ``jax.Array`` device buffers on a named mesh,
* the ThreadedIter/InputSplit pipeline feeds TPU infeed (double-buffered
  ``device_put``),
* the Rabit socket allreduce/broadcast engine is replaced by XLA collectives
  (``psum`` / ``all_gather`` / ``ppermute``) over a GSPMD mesh — ICI within a
  slice, DCN across hosts,

so XGBoost-style histogram sync and an MXNet-KVStore-shaped API ride TPU
interconnect with no CUDA in the build.

Reference parity map (see SURVEY.md §2 for the full inventory):

==========================  =================================================
reference (dmlc-core)        here
==========================  =================================================
include/dmlc/logging.h       dmlc_core_tpu.base.logging
include/dmlc/timer.h         dmlc_core_tpu.base.timer
include/dmlc/parameter.h     dmlc_core_tpu.base.parameter  (+ get_env)
include/dmlc/registry.h      dmlc_core_tpu.base.registry
include/dmlc/config.h        dmlc_core_tpu.base.config
include/dmlc/io.h            dmlc_core_tpu.io.stream
include/dmlc/memory_io.h     dmlc_core_tpu.io.memory_io
include/dmlc/serializer.h    dmlc_core_tpu.io.serializer
include/dmlc/json.h          dmlc_core_tpu.io.json_io
include/dmlc/recordio.h      dmlc_core_tpu.io.recordio
include/dmlc/threadediter.h  dmlc_core_tpu.io.threaded_iter
include/dmlc/concurrency.h   dmlc_core_tpu.io.concurrency
src/io/*filesys*             dmlc_core_tpu.io.filesystem
src/io/*split*               dmlc_core_tpu.io.input_split
include/dmlc/data.h          dmlc_core_tpu.data.row_block / .iter
src/data/*parser*            dmlc_core_tpu.data.parsers (+ cpp/fastparse.cc)
tracker/dmlc_tracker/        dmlc_core_tpu.tracker
(rabit, consumer-side)       dmlc_core_tpu.parallel.collectives
(ps-lite, consumer-side)     dmlc_core_tpu.parallel.kvstore
(none — TPU-first additions) dmlc_core_tpu.ops, dmlc_core_tpu.models
==========================  =================================================
"""

__version__ = "0.3.0"          # keep in sync with pyproject.toml

import os as _os

_force_n = _os.environ.get("DMLC_TPU_FORCE_CPU", "").strip()
if _force_n and _force_n != "0":
    # opt-in env hook: pin jax to N virtual CPU devices BEFORE anything
    # touches a backend.  Lets examples/tools run on an N-device CPU
    # mesh without per-script code — CI smoke-runs every example this
    # way.  "0"/empty = disabled; anything else must be a device count.
    if not _force_n.isdigit():
        raise ValueError(
            f"DMLC_TPU_FORCE_CPU={_force_n!r}: expected a device count "
            f"(e.g. 2) or 0/unset to disable")
    from dmlc_core_tpu.utils import force_cpu_devices as _force_cpu

    _force_cpu(int(_force_n))

from dmlc_core_tpu.base import lockcheck as _lockcheck

if _lockcheck.env_enabled():
    # DMLC_LOCKCHECK=1: every threading.Lock/RLock created after this
    # point participates in the cross-thread lock-order graph; cycles
    # are reported via base.lockcheck.violations()/check() (see
    # doc/static_analysis.md).
    _lockcheck.install()

from dmlc_core_tpu.base import racecheck as _racecheck

if _racecheck.env_enabled():
    # DMLC_RACECHECK=1: vector-clock happens-before race detection over
    # the opt-in classes (tracker/router/batcher/autoscaler/registry/
    # ConcurrentBlockingQueue); implies lockcheck (traced locks are the
    # HB vocabulary).  Races are reported via base.racecheck.races()/
    # check() (see doc/static_analysis.md).
    _racecheck.install()

from dmlc_core_tpu.base import leakcheck as _leakcheck

if _leakcheck.env_enabled():
    # DMLC_LEAKCHECK=1: every socket/thread/subprocess/tempfile created
    # through repo code after this point is traced with its creation
    # stack; whatever is still live at drill exit is reported via
    # base.leakcheck.leaks()/check() (see doc/static_analysis.md).
    # Installed AFTER racecheck so the Thread.start hooks chain.
    _leakcheck.install()

from dmlc_core_tpu.base import jitcheck as _jitcheck

if _jitcheck.env_enabled():
    # DMLC_JITCHECK=1: every XLA compilation after this point is traced
    # with its repo-frame stack and phase tag (warmup until
    # base.jitcheck.steady() is called); steady-state compiles fail
    # base.jitcheck.check() (see doc/static_analysis.md).
    _jitcheck.install()

from dmlc_core_tpu.base.logging import (  # noqa: F401
    Error,
    LOG,
    CHECK,
    CHECK_EQ,
    CHECK_NE,
    CHECK_LT,
    CHECK_GT,
    CHECK_LE,
    CHECK_GE,
    CHECK_NOTNULL,
    set_log_level,
)
from dmlc_core_tpu.base.timer import get_time  # noqa: F401
from dmlc_core_tpu.base.parameter import Parameter, field, get_env  # noqa: F401
from dmlc_core_tpu.base.registry import Registry  # noqa: F401
