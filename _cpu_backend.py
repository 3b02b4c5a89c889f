"""Force the CPU backend with N virtual devices.

Shared by tests/conftest.py, examples/amgx_mpi_poisson7.py and
__graft_entry__.dryrun_multichip so the XLA_FLAGS / JAX_PLATFORMS
handling exists exactly once. This module lives OUTSIDE the amgx_tpu
package on purpose: importing it must not execute any package __init__
(which imports jax submodules), so the "importable before jax
initializes" guarantee is structural.

Keeping JAX off an attached accelerator has to happen before the
backend initializes (after that every override silently no-ops), so
force_cpu verifies the resulting platform and fails loudly.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _want_host_devices(n_devices: int) -> None:
    """Ask XLA's CPU platform for `n_devices` virtual devices (only the
    CPU platform reads the flag; an accelerator is unaffected)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG in flags:
        # replace a pre-existing count (it may be smaller than n_devices;
        # silently keeping it would shrink the mesh under test)
        flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}",
                       flags)
    else:
        flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    os.environ["XLA_FLAGS"] = flags


def force_cpu(n_devices: int) -> None:
    """Force the CPU backend with `n_devices` virtual devices; raise if a
    jax backend already initialized on a different platform or with fewer
    devices."""
    _want_host_devices(n_devices)
    # the environment variable is read when jax is imported; the config
    # flag covers a jax that something imported already but that has
    # not initialized a backend yet
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise RuntimeError(
            f"force_cpu({n_devices}): jax backend was already initialized "
            f"({len(devs)} x {devs[0].platform}); call force_cpu before any "
            f"jax operation")


def ensure_devices(n_devices: int) -> str:
    """Make `n_devices` devices visible: the accelerator's own when it
    has that many, else `n_devices` virtual CPU devices. Returns a
    sentence to print when the run is on virtual CPU devices (""
    otherwise) — asking for R ranks must never quietly leave the chips.
    Call before any jax operation."""
    _want_host_devices(n_devices)
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        if len(devs) < n_devices:
            raise RuntimeError(
                f"{n_devices} ranks requested, {len(devs)} CPU device(s) "
                "visible: jax was initialized before ensure_devices")
        return (f"note: no accelerator in use; running on {n_devices} "
                "VIRTUAL CPU devices")
    if len(devs) < n_devices:
        raise RuntimeError(
            f"{n_devices} ranks requested but only {len(devs)} "
            f"{devs[0].platform} device(s) are visible; run with "
            f"JAX_PLATFORMS=cpu for {n_devices} virtual CPU devices")
    return ""
