"""Simulated multi-host device farms (CI-checkable without real hosts).

XLA can expose N virtual CPU devices in one process via
``--xla_force_host_platform_device_count=N``; combined with
``launch.mesh.make_multihost_mesh(hosts=...)`` that turns a laptop or a CI
runner into a simulated 16-host pod for lowering and HLO analysis (the
dryrun collective-contract gate, ``launch/dryrun.py --gate``).

The one sharp edge: XLA reads the flag ONCE, at first backend
initialization.  Mutating ``XLA_FLAGS`` after any jax device use silently
does nothing and the caller lowers against a 1-device mesh — historically
this module's callers clobbered the env var at import time and hoped.
``ensure_host_platform_devices`` makes the first-init constraint explicit
and idempotent instead.
"""
from __future__ import annotations

import os

FLAG = "--xla_force_host_platform_device_count"


def backend_initialized() -> bool:
    """True once jax has instantiated a backend (the flag is then inert).

    Importing jax does NOT initialize a backend — only device use does
    (``jax.devices()``, placing an array, ...), so callers that run before
    any of that can still set the flag."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:  # private-API drift: assume the worst (initialized)
        return True


def ensure_host_platform_devices(n: int) -> None:
    """Guarantee jax sees exactly ``n`` host-platform devices, or fail loudly.

      * backend not yet initialized — merge the flag into ``XLA_FLAGS``
        (preserving unrelated flags, replacing any previous count), pin
        the cpu platform (``JAX_PLATFORMS=cpu``, inherited by children),
        and verify by initializing;
      * backend already initialized with ``n`` devices — no-op, so a gate
        can run twice in one process (e.g. two tests in one pytest run);
      * backend initialized with any other count — pointed RuntimeError:
        the flag can no longer take effect, run in a fresh subprocess
        (the tests/dist_scripts pattern) instead of silently lowering
        against the wrong mesh.
    """
    import jax

    if not backend_initialized():
        flags = [t for t in os.environ.get("XLA_FLAGS", "").split()
                 if not t.startswith(FLAG + "=")]
        flags.append(f"{FLAG}={n}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
        # Simulated hosts are CPU devices.  Pin the platform before the
        # backend starts, so that neither this process nor a child that
        # inherits its environment reaches for an attached accelerator.
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"host-platform simulation needs the cpu backend, but jax "
            f"already started on {jax.default_backend()!r}; run it in a "
            "fresh process")
    have = jax.device_count()  # initializes the backend on first call
    if have != n:
        raise RuntimeError(
            f"host-platform simulation needs {n} devices but the jax "
            f"backend is already initialized with {have}: {FLAG} is read "
            "once, at first backend init, so it cannot take effect in this "
            "process anymore.  Run the gate in a fresh process (the "
            "tests/dist_scripts subprocess pattern) or call "
            "ensure_host_platform_devices() before anything touches jax "
            "devices.")
