"""Init-case tests: field/geometry invariants for every built-in test case
plus short propagator runs. Mirrors the reference's main/test/init/grid.cpp
and the per-case settings in main/src/init/*.hpp.
"""

import numpy as np
import pytest

from sphexa_tpu.init import (
    CASES,
    init_evrard,
    init_gresho_chan,
    init_isobaric_cube,
    init_kelvin_helmholtz,
    init_noh,
    init_wind_shock,
    make_initializer,
    split_case_spec,
)
from sphexa_tpu.sfc.box import BoundaryType
from sphexa_tpu.simulation import Simulation


def _np(state, f):
    return np.asarray(getattr(state, f))


class TestFactory:
    def test_all_cases_registered(self):
        assert set(CASES) == {
            "sedov", "noh", "evrard", "gresho-chan", "isobaric-cube",
            "kelvin-helmholtz", "wind-shock", "turbulence", "evrard-cooling",
        }

    def test_unknown_case_raises(self):
        with pytest.raises(ValueError):
            make_initializer("nope")

    @pytest.mark.parametrize("spec,case,settings", [
        ("noh", "noh", None),
        ("noh+list-lifecycle", "noh", None),
        ("sedov+list-lifecycle:s.json", "sedov", "s.json"),
        ("evrard+mesh-gravity", "evrard", None),
        ("sedov:s.json", "sedov", "s.json"),
        ("dump.h5:3", "dump.h5:3", None),
    ])
    def test_run_spec_grammar(self, spec, case, settings):
        """'case[+need...][:settings.json]': a capability this program has
        (init.CAPABILITIES) leaves the case as it is."""
        assert split_case_spec(spec) == (case, settings)
        if settings is None and case in CASES:
            assert make_initializer(spec).__wrapped__ is CASES[case]

    @pytest.mark.parametrize("spec", ["noh+warp-drive",
                                      "noh+list-lifecycle+warp-drive",
                                      "sedov+warp-drive:s.json"])
    def test_missing_capability_is_refused_while_parsing(self, spec):
        """A spec that asks for what the program lacks fails before any
        particle is made, as a program from before ``list-lifecycle``
        fails on benchmarks/configs/noh-std-1m.json's ``init``."""
        with pytest.raises(ValueError, match="warp-drive"):
            make_initializer(spec)

    def test_benchmark_configs_name_capabilities_this_program_has(self):
        """Every benchmark configuration's ``init`` resolves here, and
        observables key on its bare case."""
        import glob
        import json
        import os

        from sphexa_tpu.observables import make_observable_spec

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        files = glob.glob(os.path.join(root, "benchmarks", "configs", "*.json"))
        assert files
        for f in files:
            init = json.load(open(f))["init"]
            case, _ = split_case_spec(init)
            assert make_initializer(init).__wrapped__ is CASES[case], f
            assert make_observable_spec(init) == make_observable_spec(case)

    def test_settings_file_overrides(self, tmp_path):
        """'case:settings.json' applies JSON overrides to the case defaults
        (the reference's --init sedov:file path, factory.hpp:47-48)."""
        import json

        path = tmp_path / "s.json"
        path.write_text(json.dumps({"gamma": 1.4, "mTotal": 2.0}))
        state, box, const = make_initializer(f"sedov:{path}")(6)
        assert const.gamma == pytest.approx(1.4)
        np.testing.assert_allclose(np.asarray(state.m).sum(), 2.0, rtol=1e-5)

    def test_sedov_derived_energy_override(self, tmp_path):
        """Overriding energyTotal must re-derive the spike amplitude
        (ener0), not keep the default blast energy."""
        import json

        path = tmp_path / "e.json"
        path.write_text(json.dumps({"energyTotal": 2.0}))
        s1, _, c1 = make_initializer(f"sedov:{path}")(6)
        s0, _, c0 = make_initializer("sedov")(6)
        u1 = (np.asarray(s1.temp) * c1.cv * np.asarray(s1.m)).sum()
        u0 = (np.asarray(s0.temp) * c0.cv * np.asarray(s0.m)).sum()
        assert u1 / u0 == pytest.approx(2.0, rel=1e-3)


class TestNoh:
    def test_geometry_and_velocity(self):
        state, box, const = init_noh(12)
        x, y, z = _np(state, "x"), _np(state, "y"), _np(state, "z")
        r = np.sqrt(x**2 + y**2 + z**2)
        assert state.n > 0.4 * 12**3  # sphere cut keeps pi/6 of the cube
        assert np.all(r <= 0.5 + 1e-6)
        # unit radial inflow
        vdotr = (_np(state, "vx") * x + _np(state, "vy") * y + _np(state, "vz") * z)
        speed = np.sqrt(
            _np(state, "vx") ** 2 + _np(state, "vy") ** 2 + _np(state, "vz") ** 2
        )
        assert np.all(vdotr < 0)
        np.testing.assert_allclose(speed, 1.0, rtol=1e-5)
        assert box.boundaries[0] == BoundaryType.open
        # total mass = mTotal
        np.testing.assert_allclose(_np(state, "m").sum(), 1.0, rtol=1e-5)


class TestEvrard:
    def test_profile_and_h(self):
        state, box, const = init_evrard(12)
        x, y, z = _np(state, "x"), _np(state, "y"), _np(state, "z")
        r = np.sqrt(x**2 + y**2 + z**2)
        assert np.all(r <= 1.0 + 1e-6)
        assert const.g == 1.0
        # rho ~ 1/r: shell mass within r grows ~ r^2 => N(<0.5) ~ 4x N(<0.25)
        n_inner = (r < 0.25).sum()
        n_mid = (r < 0.5).sum()
        assert 2.5 < n_mid / max(n_inner, 1) < 6.0
        # h grows with radius (h ~ r^(1/3))
        h = _np(state, "h")
        assert h[r > 0.8].mean() > h[r < 0.2].mean()


class TestGreshoChan:
    def test_velocity_profile(self):
        state, box, const = init_gresho_chan(12)
        x, y = _np(state, "x"), _np(state, "y")
        psi = np.sqrt(x**2 + y**2) / 0.2
        v = np.sqrt(_np(state, "vx") ** 2 + _np(state, "vy") ** 2)
        np.testing.assert_allclose(v[psi <= 1.0], psi[psi <= 1.0], rtol=1e-4)
        assert np.all(v[psi > 2.0] < 1e-6)
        assert np.all(_np(state, "vz") == 0)
        # azimuthal: v . r == 0
        vdotr = _np(state, "vx") * x + _np(state, "vy") * y
        np.testing.assert_allclose(vdotr, 0.0, atol=1e-5)

    def test_short_run_stays_finite(self):
        state, box, const = init_gresho_chan(10)
        sim = Simulation(state, box, const, prop="std", block=256)
        for _ in range(3):
            sim.step()
        for f in ("x", "vx", "temp", "h"):
            assert np.all(np.isfinite(_np(sim.state, f))), f


class TestIsobaricCube:
    def test_density_contrast(self):
        state, box, const = init_isobaric_cube(14)
        x, y, z = _np(state, "x"), _np(state, "y"), _np(state, "z")
        r = 0.25
        inner = (np.abs(x) < r) & (np.abs(y) < r) & (np.abs(z) < r)
        v_in = (2 * r) ** 3
        v_out = 1.0 - v_in
        ratio = (inner.sum() / v_in) / ((~inner).sum() / v_out)
        assert 5.0 < ratio < 11.0, ratio  # target 8
        # isobaric: temp_in/temp_ext = rhoExt/rhoInt
        t = _np(state, "temp")
        np.testing.assert_allclose(
            t[inner].mean() / t[~inner].mean(), 1.0 / 8.0, rtol=0.05
        )


class TestKelvinHelmholtz:
    def test_band_contrast_and_shear(self):
        state, box, const = init_kelvin_helmholtz(12)
        y = _np(state, "y")
        inner = (y > 0.25) & (y < 0.75)
        ratio = (inner.sum() / 0.5) / ((~inner).sum() / 0.5)
        assert 1.6 < ratio < 2.4, ratio  # target 2
        vx = _np(state, "vx")
        assert vx[(y > 0.35) & (y < 0.65)].mean() < -0.3  # band flows -x
        assert vx[(y < 0.15) | (y > 0.85)].mean() > 0.3  # outside flows +x
        # seeded vy perturbation has the right amplitude
        assert 0.001 < np.abs(_np(state, "vy")).max() <= 0.011


class TestWindShock:
    def test_blob_and_wind(self):
        state, box, const = init_wind_shock(10)
        x, y, z = _np(state, "x"), _np(state, "y"), _np(state, "z")
        r, rs = 0.125, 0.025
        rpos = np.sqrt((x - r) ** 2 + (y - r) ** 2 + (z - r) ** 2)
        cloud = rpos <= rs
        assert cloud.sum() > 5
        vx = _np(state, "vx")
        assert np.all(vx[cloud] == 0)
        np.testing.assert_allclose(vx[~cloud], 2.7, rtol=1e-5)
        # number-density contrast ~ 10
        v_cloud = 4 / 3 * np.pi * rs**3
        v_tot = (8 * r) * (2 * r) * (2 * r)
        ratio = (cloud.sum() / v_cloud) / ((~cloud).sum() / (v_tot - v_cloud))
        assert 5.0 < ratio < 15.0, ratio


class TestEvrardRun:
    def test_gravity_hydro_run(self):
        state, box, const = init_evrard(10)
        sim = Simulation(state, box, const, prop="std", block=256, theta=0.5)
        for _ in range(3):
            sim.step()
        st = sim.state
        for f in ("x", "vx", "temp", "h"):
            assert np.all(np.isfinite(_np(st, f))), f
        # cold sphere must start collapsing: net radial velocity < 0
        x, y, z = _np(st, "x"), _np(st, "y"), _np(st, "z")
        rr = np.maximum(np.sqrt(x**2 + y**2 + z**2), 1e-9)
        vr = (_np(st, "vx") * x + _np(st, "vy") * y + _np(st, "vz") * z) / rr
        assert vr.mean() < 0


def test_generate_glass_template(tmp_path):
    """generate-once + tile: the damped relaxation reduces density
    fluctuations, the saved block round-trips through --glass tiling
    (init/utils.hpp:100-168 pipeline, generation included)."""
    import numpy as np

    from sphexa_tpu.init.glass import (
        generate_glass_template,
        jittered_lattice,
        read_template_block,
        set_glass_template,
        write_template_block,
    )

    x, y, z = generate_glass_template(side=8, relax_steps=8)
    assert len(x) == 512
    assert (x >= 0).all() and (x < 1).all()

    # density uniformity: nearest-neighbor distance spread tightens vs
    # the jittered lattice it started from
    def nn_spread(xs, ys, zs):
        p = np.stack([xs, ys, zs], 1)
        d2 = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        nn = np.sqrt(d2.min(1))
        return nn.std() / nn.mean()

    x0, y0, z0 = jittered_lattice((0, 0, 0), (1, 1, 1), (8, 8, 8))
    assert nn_spread(x, y, z) < nn_spread(x0, y0, z0)

    path = str(tmp_path / "glass.h5")
    write_template_block(path, x, y, z)
    set_glass_template(path)
    try:
        gx, gy, gz = jittered_lattice((0, 0, 0), (2, 2, 2), (16, 16, 16))
        assert len(gx) == 8 * 512  # 2x2x2 tiles of the 8^3 block
        assert (gx >= 0).all() and (gx < 2).all()
    finally:
        set_glass_template(None)
