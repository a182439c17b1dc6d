"""Platform selection helpers.

``JAX_PLATFORMS=cpu`` in the environment is all it takes to run on the
CPU backend.  :func:`force_cpu_devices` is for code that needs an
``n``-device mesh without accelerators: it asks XLA for ``n`` virtual
CPU devices, so ``shard_map`` / ``psum`` over a named mesh run on one
host.  It must be called before the first backend initialization.
"""

from __future__ import annotations

import os
import re

__all__ = ["force_cpu_devices"]

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_devices(n: int = 8) -> None:
    """Put jax on ``n`` virtual CPU devices (never an accelerator).

    Call before any jax operation.  Replaces any existing device-count in
    XLA_FLAGS (e.g. one inherited from a parent process) rather than
    keeping it.  Raises RuntimeError if jax backends were already
    initialized — at that point the platform can no longer be changed and
    silently continuing could mean running on the real chip.
    """
    import jax
    from jax._src import xla_bridge as _xb

    if _xb.backends_are_initialized():
        devs = jax.devices()
        if devs and (devs[0].platform != "cpu" or len(devs) != n):
            raise RuntimeError(
                f"force_cpu_devices({n}): jax backends already initialized "
                f"({len(devs)} {devs[0].platform} devices) — call before any "
                f"jax operation"
            )
        return

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_COUNT_FLAG}=\d+", "", flags).strip()
    os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n}").strip()
    jax.config.update("jax_platforms", "cpu")
