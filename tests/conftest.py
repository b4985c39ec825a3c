"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of simulating multi-node by oversubscribing
one node with mpiexec (SPH-EXA domain/test/integration_mpi/CMakeLists.txt):
here the "ranks" are XLA virtual CPU devices, and the real collectives are
the test double.
"""

import os
import shutil
import tempfile

# XLA:CPU logs two error-level lines of 3 kB for every executable it reloads
# from the run's compile cache below ("machine feature +prefer-no-scatter
# is not supported on the host": features it added itself when it compiled
# the entry, on this host, minutes before); pytest would carry them on
# every report. Read by the C++ logger at its first line: set before jax.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

# Tests are CPU-only by default: the platform and the 8 virtual devices
# are set here, before jax starts a backend (force_cpu_mesh).
#
# SPHEXA_TPU_TESTS=1 keeps the real TPU backend (for the device-equivalence
# tier, tests/test_pallas_tpu.py, run on the chip).
if not os.environ.get("SPHEXA_TPU_TESTS"):
    from sphexa_tpu.util.cpu_mesh import force_cpu_mesh

    force_cpu_mesh(8)

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run the slow tier (heavy CPU-mesh equivalence + e2e runs)",
    )


def pytest_configure(config):
    """ONE persistent compilation cache for the run, made by the process
    that starts it, shared by its xdist workers and removed when it ends
    (never read across runs). The tier compiles, it does not compute:
    interpret-mode Mosaic kernels and eager dispatch make thousands of XLA
    programs, and every worker and every test file made the same ones
    anew (PR 41: of the 1,501 programs tests/test_pair_lists.py compiles,
    800 were in the cache tests/test_pair_list_tiles.py had left; 143 s
    of compiling became 61). A half-written entry reads as a miss (jax
    warns and compiles)."""
    import jax

    workerinput = getattr(config, "workerinput", None)
    if workerinput is None:
        config._sphexa_compile_cache = tempfile.mkdtemp(
            prefix="sphexa-tpu-test-jaxcache-")
    cache_dir = (config._sphexa_compile_cache if workerinput is None
                 else workerinput["sphexa_compile_cache"])
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the programs are small and many: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """xdist: hand the run's cache directory to each worker."""
    node.workerinput["sphexa_compile_cache"] = \
        node.config._sphexa_compile_cache


def pytest_unconfigure(config):
    if getattr(config, "workerinput", None) is None:
        shutil.rmtree(getattr(config, "_sphexa_compile_cache", ""),
                      ignore_errors=True)


def pytest_generate_tests(metafunc):
    """One module per driven case: a helper module that is not collected
    (tests/mesh_gravity_case.py, pair_list_cases.py, pair_list_tile_cases.py)
    holds a case's fixtures and tests and names the fixture the case
    parametrises (``CASE_FIXTURE``); each ``test_*`` module sets ``CASE`` and
    imports the helper's names. The case is that fixture's one parameter, so
    the ids keep their ``[normal]`` / ``[sedov]`` suffix and ``--dist
    loadfile`` may run a family's cases side by side."""
    fixture = getattr(metafunc.module, "CASE_FIXTURE", None)
    if fixture in metafunc.fixturenames:
        metafunc.parametrize(fixture, [metafunc.module.CASE], indirect=True,
                             scope="module")


def pytest_collection_modifyitems(config, items):
    """Default suite = fast tier (<5 min); the slow tier (heavy 8-device
    equivalence runs, e2e shocks, hierarchical-MAC sweeps) runs with
    --runslow or SPHEXA_ALL_TESTS=1 (VERDICT r3 #9 tier split). CI
    recipe: both tiers' results are recorded in TESTS_r{N}.json."""
    if config.getoption("--runslow") or os.environ.get("SPHEXA_ALL_TESTS"):
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow (or "
                            "SPHEXA_ALL_TESTS=1) to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


#: seconds a tier-1 test waits on a mesh subprocess: twice the slowest one
#: under six workers (the ``normal`` case of tests/mesh_gravity_case.py,
#: 156-191 s in PR 41's seven whole runs, 77 s alone) and at most 300, so
#: that a hang fails one test and does not cut the run (its limit is
#: 1470 s for everything)
MESH_SUBPROCESS_TIMEOUT = 300


def run_mesh_subprocess(code: str, timeout: int = MESH_SUBPROCESS_TIMEOUT):
    """Run mesh test code in a FRESH process on a virtual 8-device CPU
    mesh (shared scaffold: after many sharded programs compile in one
    process, the oversubscribed XLA:CPU mesh can cross-route collective
    executables — a harness artifact). ``code`` must print a sentinel;
    callers assert on the returned CompletedProcess."""
    import subprocess
    import sys
    import textwrap

    preamble = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    """)
    import jax

    # the child shares the run's compile cache (pytest_configure), where
    # jax reads it by itself: from the environment
    env = dict(os.environ)
    if jax.config.jax_compilation_cache_dir:
        env.update(
            JAX_COMPILATION_CACHE_DIR=jax.config.jax_compilation_cache_dir,
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    return subprocess.run(
        [sys.executable, "-c", preamble + textwrap.dedent(code)],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=timeout, env=env,
    )
