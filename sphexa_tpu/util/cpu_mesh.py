"""Virtual-CPU-mesh bootstrap shared by the CLI (--cpu-mesh), the
multi-chip dry run and the test conftest.

Multi-device code paths are validated on hosts with one (or zero) real
accelerator by oversubscribing the CPU platform with N virtual devices —
the same strategy as the reference's oversubscribed-mpiexec integration
tests (domain/test/integration_mpi/). The rule is plain: set the platform
before jax starts a backend. ``jax.config`` is the lever used here because
it also works after ``import jax`` (the ``JAX_PLATFORMS`` variable is read
at import); ``XLA_FLAGS`` is read at the first backend init.
"""

import os
import re


def force_cpu_mesh(n_devices: int) -> None:
    """Steer this process to a CPU backend with ``n_devices`` virtual
    devices. Must run before any jax operation initializes a backend;
    raises RuntimeError if the backend is already up or if XLA_FLAGS
    pins a conflicting device count."""
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m:
        have = int(m.group(1))
        if have < n_devices:
            raise RuntimeError(
                f"XLA_FLAGS already pins xla_force_host_platform_device_count"
                f"={have} < requested {n_devices}; unset it or raise it"
            )
    else:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    # config.update silently no-ops once a backend is initialized — verify
    # the steer actually took (this also forces the lazy init NOW, on the
    # platform we just selected)
    if jax.default_backend() != "cpu" or len(jax.local_devices()) < n_devices:
        raise RuntimeError(
            f"backend is {jax.default_backend()!r} with "
            f"{len(jax.local_devices())} device(s) after the CPU-mesh "
            f"steer — jax was already initialized before force_cpu_mesh; "
            "set JAX_PLATFORMS=cpu in the environment instead"
        )
