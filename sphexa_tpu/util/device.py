"""The one place that answers "is this a TPU, and which".

Every platform-dependent choice in the package — the ``backend="auto"``
engine pick, Mosaic interpret mode, buffer donation, the CPU-mesh drain —
asks this module, and so do the entry points that must not run without a
chip (chip_smoke.py: ``require_tpu``). On a CPU host the answers
select the portable paths the tests want (XLA gather engine, interpret-mode
kernels); nothing here ever falls back silently on a path that demanded a
chip.

Also home of ``enable_compile_cache``: the persistent compilation cache
placement shared by the CLI and the smoke.
"""

import functools
import os
from typing import NamedTuple, Optional


class DeviceInfo(NamedTuple):
    """What jax reports for the default backend's devices."""

    platform: str  # jax.devices()[0].platform: "tpu" | "cpu" | "gpu"
    kind: str      # jax.devices()[0].device_kind, e.g. "TPU v5 lite"
    count: int     # len(jax.devices())


@functools.lru_cache(maxsize=None)
def device_info() -> DeviceInfo:
    """Platform, device kind and device count as jax reports them.
    Initializes the backend on first use; cached because a process cannot
    change platform once the backend is up."""
    import jax

    devices = jax.devices()
    return DeviceInfo(devices[0].platform, devices[0].device_kind,
                      len(devices))


def on_tpu() -> bool:
    return device_info().platform == "tpu"


def resolve_backend(backend: str = "auto") -> str:
    """``"auto"`` -> the fused Mosaic engine (``"pallas"``) on TPU, the
    portable gather path (``"xla"``) elsewhere; explicit names pass
    through (``"pallas"`` off-TPU runs the kernels in interpret mode)."""
    if backend == "auto":
        return "pallas" if on_tpu() else "xla"
    return backend


def require_tpu(what: str) -> DeviceInfo:
    """The demand-a-chip call: return the device info, or raise naming
    the platform found. For measurement/smoke entry points, where a CPU
    fallback under the same metric names would be a wrong record."""
    info = device_info()
    if info.platform != "tpu":
        raise RuntimeError(
            f"{what} needs a TPU; jax found platform={info.platform!r} "
            f"device_kind={info.kind!r} count={info.count}"
        )
    return info


#: <checkout>/.jax_cache — derived from the package location, so every
#: process of one checkout shares it whatever its working directory (the
#: cache key includes the path: a directory that moves never hits)
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> Optional[str]:
    """Place jax's persistent compilation cache; call before the first
    jit. Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it
    and no directory is set in code; otherwise, on a TPU, the cache goes to
    the fixed ``<checkout>/.jax_cache``. Returns the directory in effect.

    Wherever a cache is in effect its key covers the program's metadata
    too. By default jax takes the key after ``strip-debuginfo``, so a
    change of scope names alone (util/phases.py: what a capture is read
    by) would be handed the executable an older checkout left there,
    with that checkout's names on its ops.

    Off-TPU no directory is set (returns None): the cache exists to save
    chip compiles, and XLA:CPU reloads its cached AOT results with an
    error-level warning per entry ("machine type ... doesn't match ...
    could lead to SIGILL") that the CPU test tier has no use for."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir and not on_tpu():
        return None
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
