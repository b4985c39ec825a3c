"""Native C++ host-runtime tests: bit-equality with the jax SFC codec and
the numpy accounting helpers (the native analog of the reference's
CPU/GPU equivalence tier). If the library cannot build, the fallback path
is exercised instead — both paths must produce identical results.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from sphexa_tpu import native
from sphexa_tpu.dtypes import KEY_BITS
from sphexa_tpu.sfc.box import Box, BoundaryType
from sphexa_tpu.sfc.keys import compute_sfc_keys


@pytest.fixture(scope="module")
def cloud(rng_module=np.random.default_rng(3)):
    n = 5000
    x, y, z = rng_module.uniform(-0.5, 0.5, (3, n)).astype(np.float32)
    return x, y, z


def test_library_builds_and_loads():
    # the image ships g++; the library must build (fallback is for
    # environments without a toolchain)
    assert native.available()


@pytest.mark.parametrize("curve", ["hilbert", "morton"])
def test_keys_match_jax_codec(cloud, curve):
    x, y, z = cloud
    lo = np.array([-0.5] * 3, np.float32)
    ln = np.array([1.0] * 3, np.float32)
    kn = native.compute_keys(x, y, z, lo, ln, curve=curve)
    box = Box.create(-0.5, 0.5, boundary=BoundaryType.open)
    kj = np.asarray(
        compute_sfc_keys(jnp.asarray(x), jnp.asarray(y), jnp.asarray(z),
                         box, curve=curve)
    )
    np.testing.assert_array_equal(kn, kj)


def test_argsort_matches_numpy(cloud):
    x, y, z = cloud
    keys = native.compute_keys(
        x, y, z, np.array([-0.5] * 3, np.float32), np.array([1.0] * 3, np.float32)
    )
    np.testing.assert_array_equal(
        native.argsort_keys(keys), np.argsort(keys, kind="stable")
    )


def test_occupancy_matches_bincount(cloud):
    x, y, z = cloud
    keys = native.compute_keys(
        x, y, z, np.array([-0.5] * 3, np.float32), np.array([1.0] * 3, np.float32)
    )
    sk = np.sort(keys)
    for level in (1, 2, 3, 5):
        shift = 3 * (KEY_BITS - level)
        expect = int(np.bincount((sk >> np.uint32(shift)).astype(np.int64)).max())
        assert native.max_cell_occupancy(sk, level) == expect


def test_group_extents_match_numpy(cloud):
    x, y, z = cloud
    keys = native.compute_keys(
        x, y, z, np.array([-0.5] * 3, np.float32), np.array([1.0] * 3, np.float32)
    )
    order = native.argsort_keys(keys)
    ext = native.group_extents(x, y, z, order, 128)
    n = len(x)
    ng = -(-n // 128)
    pad = ng * 128 - n
    for d, a in enumerate((x, y, z)):
        s = a[order]
        if pad:
            s = np.concatenate([s, np.repeat(s[-1], pad)])
        g = s.reshape(ng, 128)
        assert ext[d] == pytest.approx(float((g.max(1) - g.min(1)).max()), rel=1e-6)


def test_config_pipeline_uses_native(cloud):
    """make_propagator_config runs through the native sizing path and
    produces a working config."""
    from sphexa_tpu.init import init_sedov
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_sedov(8)
    sim = Simulation(state, box, const, prop="std", block=256)
    d = sim.step()
    assert np.isfinite(d["dt"])


def test_loader_rebuilds_library_older_than_source(tmp_path, monkeypatch):
    """A leftover library older than sfc_runtime.cpp (a copied working
    tree) must be rebuilt, not dlopen'ed as it stands."""
    import os
    import shutil

    src = tmp_path / "sfc_runtime.cpp"
    lib = tmp_path / "libsfc_runtime.so"
    shutil.copy(native._SRC_PATH, src)
    lib.write_bytes(b"stale build")  # dlopen of THIS would fail
    old = os.path.getmtime(src) - 3600
    os.utime(lib, (old, old))
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried_build", False)
    monkeypatch.setattr(native, "_fallback_reason", "")
    assert native.available(), native.describe()
    assert os.path.getmtime(lib) >= os.path.getmtime(src)
    assert native.describe().startswith("native")
