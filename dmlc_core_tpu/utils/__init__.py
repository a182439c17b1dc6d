"""Shared runtime utilities (platform control, profiling)."""

from dmlc_core_tpu.utils.platform import force_cpu_devices  # noqa: F401
from dmlc_core_tpu.utils.profiler import (  # noqa: F401
    Tracer,
    device_trace,
    global_tracer,
    set_tracing,
    span,
    tracing_enabled,
)
