"""App-layer long tail: file-split up-sampling, glass-block templates,
chemistry checkpointing, the evrard/gresho-chan comparators, and the
restart bookkeeping fixes (dump naming, constants.txt truncation,
float -w catch-up)."""

import json
import os

import numpy as np
import pytest

from sphexa_tpu.app.main import main as app_main
from sphexa_tpu.init import make_initializer
from sphexa_tpu.init.file_init import init_file_split, parse_split_spec
from sphexa_tpu.init.glass import (
    assemble_glass_cuboid,
    read_template_block,
    set_glass_template,
)
from sphexa_tpu.init.sedov import init_sedov
from sphexa_tpu.io import write_snapshot


@pytest.fixture
def small_dump(tmp_path):
    state, box, const = init_sedov(8)
    path = str(tmp_path / "dump_small.h5")
    write_snapshot(path, state, box, const, iteration=3, case="sedov")
    return path, state, box, const


class TestFileSplit:
    def test_parse(self):
        assert parse_split_spec("dump.h5,4") == ("dump.h5", 4)
        assert parse_split_spec("dump.h5") is None
        assert parse_split_spec("dump.h5,0") is None
        assert parse_split_spec("dump.h5,x") is None

    def test_split_conserves_mass_and_scales_h(self, small_dump):
        path, state, _, _ = small_dump
        new_state, box, const = init_file_split(path, 4)
        assert new_state.n == 4 * state.n
        np.testing.assert_allclose(
            float(np.sum(np.asarray(new_state.m))),
            float(np.sum(np.asarray(state.m))), rtol=1e-5,
        )
        # h scaled by N^(-1/3) (file_init.hpp:222)
        np.testing.assert_allclose(
            np.asarray(new_state.h).max(),
            np.asarray(state.h).max() * 4 ** (-1 / 3), rtol=1e-5,
        )
        # clock restarted, dt reduced 100*N
        assert float(new_state.ttot) == 0.0
        assert float(new_state.min_dt) == pytest.approx(
            float(state.min_dt) / 400.0
        )
        # interpolated positions stay inside the box
        for a, d in (("x", 0), ("y", 1), ("z", 2)):
            v = np.asarray(getattr(new_state, a))
            assert v.min() >= float(box.lo[d]) - 1e-6
            assert v.max() <= float(box.hi[d]) + 1e-6

    def test_split_factory_and_steps(self, small_dump):
        from sphexa_tpu.simulation import Simulation

        path, state, _, _ = small_dump
        init = make_initializer(f"{path},2")
        new_state, box, const = init(None)
        assert new_state.n == 2 * state.n
        sim = Simulation(new_state, box, const, prop="std", block=512)
        d = sim.step()
        assert np.isfinite(d["dt"]) and d["dt"] > 0


class TestGlass:
    def _template(self, tmp_path, n=5):
        import h5py

        from sphexa_tpu.init.glass import jittered_lattice

        x, y, z = jittered_lattice((0, 0, 0), (1, 1, 1), (n, n, n), seed=7)
        path = str(tmp_path / "glass.h5")
        with h5py.File(path, "w") as f:
            f["x"], f["y"], f["z"] = x, y, z
        return path

    def test_read_and_tile(self, tmp_path):
        path = self._template(tmp_path)
        tpl = read_template_block(path)
        for v in tpl:
            assert v.min() >= 0.0 and v.max() < 1.0
        x, y, z = assemble_glass_cuboid(tpl, (-1, -1, -1), (1, 1, 1),
                                        (10, 10, 10))
        assert len(x) == 125 * 8  # 5^3 template tiled 2x2x2
        assert x.min() >= -1.0 and x.max() < 1.0

    def test_template_drives_cases(self, tmp_path):
        path = self._template(tmp_path)
        set_glass_template(path)
        try:
            state, box, const = init_sedov(10)
        finally:
            set_glass_template(None)
        assert state.n == 1000  # 5^3 x 2^3
        # and the clean lattice returns without the template
        state2, _, _ = init_sedov(10)
        assert state2.n == 1000


class TestChemistryCheckpoint:
    def test_round_trip(self):
        from sphexa_tpu.physics.cooling import (
            ChemistryData,
            chemistry_from_fields,
            chemistry_to_fields,
        )

        chem = ChemistryData.ionized(32)
        fields = chemistry_to_fields(chem)
        assert set(fields) == {
            "chem_hi", "chem_hii", "chem_hei", "chem_heii", "chem_heiii",
            "chem_e", "chem_metal",
        }
        back = chemistry_from_fields(fields)
        np.testing.assert_array_equal(np.asarray(back.hii),
                                      np.asarray(chem.hii))


class TestComparators:
    def test_gresho_profile_zero_error_on_exact(self):
        from sphexa_tpu.analysis.gresho_chan import (
            gresho_chan_l1,
            gresho_chan_vphi,
        )

        rng = np.random.default_rng(0)
        x = rng.uniform(-0.5, 0.5, 4000)
        y = rng.uniform(-0.5, 0.5, 4000)
        r = np.sqrt(x * x + y * y)
        vphi = gresho_chan_vphi(r)
        vx = -vphi * y / np.maximum(r, 1e-12)
        vy = vphi * x / np.maximum(r, 1e-12)
        assert gresho_chan_l1(x, y, vx, vy) < 1e-12

    def test_gresho_ic_matches_analytic(self):
        from sphexa_tpu.analysis.gresho_chan import gresho_chan_l1
        from sphexa_tpu.init.gresho_chan import init_gresho_chan

        state, box, const = init_gresho_chan(16)
        l1 = gresho_chan_l1(state.x, state.y, state.vx, state.vy)
        assert l1 < 1e-5, l1

    def test_evrard_norms(self):
        from sphexa_tpu.analysis.evrard import (
            evrard_normalized_profiles,
            evrard_norms,
        )

        n = evrard_norms(R=1.0, M=1.0, G=1.0)
        assert n["time"] == pytest.approx(np.sqrt(np.pi**2 / 8.0))
        assert n["rho"] == pytest.approx(3.0 / (4 * np.pi))
        fields = {
            "r": np.linspace(0.01, 1.0, 500),
            "rho": np.full(500, n["rho"]),
            "u": np.full(500, 0.05),
            "vel": np.zeros(500),
        }
        prof = evrard_normalized_profiles(fields, time=0.0)
        assert prof["t_norm"] == 0.0
        mask = prof["rho_profile"] > 0
        np.testing.assert_allclose(prof["rho_profile"][mask], 1.0, rtol=1e-6)


class TestRestartBookkeeping:
    def test_restart_appends_to_case_dump_and_truncates_constants(
        self, tmp_path
    ):
        import h5py

        out = str(tmp_path)
        rc = app_main(["--init", "sedov", "-n", "8", "-s", "4", "-w", "2",
                       "-o", out, "--quiet"])
        assert rc in (0, None)
        dump = f"{out}/dump_sedov.h5"
        assert os.path.exists(dump)
        with h5py.File(dump, "r") as f:
            steps_before = sorted(f.keys())

        rows_before = open(f"{out}/constants.txt").readlines()

        # restart from step 0 (iteration 2): the dump must gain Step#n
        # groups under the SAME name, and constants.txt must drop rows
        # beyond the restart point
        rc = app_main(["--init", f"{dump}:0", "-s", "6", "-w", "2",
                       "-o", out, "--quiet"])
        assert rc in (0, None)
        with h5py.File(dump, "r") as f:
            steps_after = sorted(f.keys())
        assert len(steps_after) > len(steps_before)
        assert not [p for p in os.listdir(out)
                    if p.startswith("dump_") and p != "dump_sedov.h5"
                    and not p.endswith(".txt")]

        rows = [ln for ln in open(f"{out}/constants.txt")
                if not ln.startswith("#")]
        its = [int(float(ln.split()[0])) for ln in rows]
        assert its == sorted(its), "constants.txt iterations not monotonic"

    def test_float_w_schedule_catches_up(self, tmp_path):
        # a single step crossing several -w intervals must advance the
        # schedule past t_now (one dump, not a burst of redundant ones)
        out = str(tmp_path)
        rc = app_main(["--init", "sedov", "-n", "8", "-s", "3",
                       "-w", "1e-9", "-o", out, "--quiet"])
        assert rc in (0, None)
        import h5py

        with h5py.File(f"{out}/dump_sedov.h5", "r") as f:
            # every step crosses many 1e-9 intervals; exactly one dump per
            # iteration (3) + none extra
            assert len([k for k in f.keys() if k.startswith("Step#")]) <= 4


def test_profile_series(tmp_path):
    """--profile writes the per-iteration series (the reference's
    --profile dump, ipropagator.hpp:83-87): one row per iteration with
    the loop's laps, and no split-program substep scalars."""
    import numpy as np

    from sphexa_tpu.app.main import main

    rc = main(["--init", "sedov", "-n", "10", "-s", "2", "--quiet",
               "--profile", "-o", str(tmp_path)])
    assert rc == 0
    data = np.load(str(tmp_path / "profile.npz"))
    np.testing.assert_array_equal(data["iteration"], [1.0, 2.0])
    assert {"step", "observables", "output", "dt"} <= set(data.files)
    assert not [k for k in data.files if k.startswith("substep_")]


@pytest.mark.slow
def test_sharded_dump_restart_cli(tmp_path):
    """CLI round trip of the parallel file-per-shard snapshots: a mesh
    run dumps P part files (no base file), a restart from the BASE path
    reassembles them, CONTINUES the iteration count and appends new
    part dumps; a fresh run into the same out_dir removes the stale
    part set. Fresh subprocess via conftest.run_mesh_subprocess."""
    from conftest import run_mesh_subprocess

    out = str(tmp_path)
    code = f"""
        import glob, os
        from sphexa_tpu.app.main import main as app_main
        from sphexa_tpu.io.snapshot import read_step_attrs

        out = {out!r}
        rc = app_main(["--init", "sedov", "-n", "16", "-s", "2", "-w", "1",
                       "-o", out, "--devices", "8", "--quiet"])
        assert rc in (0, None), rc
        base = f"{{out}}/dump_sedov.h5"
        parts = sorted(glob.glob(f"{{out}}/dump_sedov.part*of*.h5"))
        assert len(parts) == 8 and not os.path.exists(base), parts

        # restart from the sharded BASE path: continues the iteration
        # count and appends new part dumps (verified via the snapshot
        # attrs, not just the exit code)
        rc = app_main(["--init", base, "-s", "4", "-w", "1", "-o", out,
                       "--devices", "8", "--quiet"])
        assert rc in (0, None), rc
        attrs = read_step_attrs(base, step=-1)
        assert int(attrs["iteration"]) == 4, attrs["iteration"]

        # a FRESH (non-restart) run must clear the stale part set first
        rc = app_main(["--init", "sedov", "-n", "16", "-s", "1", "-w", "1",
                       "-o", out, "--devices", "8", "--quiet"])
        assert rc in (0, None), rc
        import h5py
        with h5py.File(sorted(glob.glob(
                f"{{out}}/dump_sedov.part*of*.h5"))[0], "r") as f:
            # fresh run: exactly the new dumps, no appended old steps
            assert len([k for k in f.keys() if k.startswith("Step#")]) <= 2
        print("SHARDED-DUMP-OK")
    """
    r = run_mesh_subprocess(code)
    assert "SHARDED-DUMP-OK" in r.stdout, r.stderr[-2000:]
