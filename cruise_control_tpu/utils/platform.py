"""Host-platform control for tests and driver hooks.

Tests and CI have no accelerator, so multi-device code paths
(``jax.sharding.Mesh`` over N devices) run on the *virtual host-CPU
platform*: ``--xla_force_host_platform_device_count`` splits the host
CPU into N XLA devices. This module is how tests get a mesh (used by
``tests/conftest.py`` and ``__graft_entry__.dryrun_multichip``), nothing
more.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_host_cpu_devices(n: int):
    """Force jax onto the CPU platform with at least ``n`` virtual devices.

    Must run before the jax backend is first used in this process.  Sets the
    XLA flag (raising an existing smaller count to ``n``; an existing count
    >= ``n`` is kept), pins ``JAX_PLATFORMS=cpu`` both via env var and via a
    config update after import (jax may already be imported, and then the
    env var alone is too late), then verifies the backend actually came up
    as CPU with enough devices — failing loudly here beats a confusing
    downstream mesh-construction error.

    Returns the imported ``jax`` module.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    match = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if match is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_COUNT_FLAG}={n}".strip()
    elif int(match.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            match.group(0), f"{_COUNT_FLAG}={n}")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < n:
        raise RuntimeError(
            f"force_host_cpu_devices({n}) too late: the jax backend is "
            f"already initialized as {len(devices)} {devices[0].platform!r} "
            "device(s). Call it before any jax backend use in this process "
            "(e.g. before running entry()'s step).")
    return jax
