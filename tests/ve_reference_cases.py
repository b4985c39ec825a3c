"""The plain VE reference benchmarks/reference_sph_ve.py against the program's
VE force stage on ONE of its three paths, in a periodic box: the fixtures and
the tests that tests/test_ve_reference.py (``streamed``),
tests/test_ve_reference_lists.py (``lists``) and
tests/test_ve_reference_mesh.py (``mesh``) each run under their own ``CASE``.
Not collected itself: one module per driven case, so that ``--dist loadfile``
runs the three side by side (conftest.pytest_generate_tests).

The reference is all-pairs ``jax.numpy`` with the analytic kernel and the
minimum image, and imports nothing of ``sphexa_tpu/sph``; the program's
stage (``propagator._ve_forces``, the call ``_step_hydro_ve`` and
``_step_turb_ve`` make) runs

- ``streamed``: the streamed engine on one device (what the one-chip
  gravity cells ran until PR 44 and every dump runs), 12^3, every particle a
  target;
- ``lists``: the list walk on one device (``sedov-ve-4m``, ``turb-ve-8m``,
  ``evrard-ve-1m`` since PR 44), 29^3, the smallest periodic box whose grid
  takes persistent lists, at seeded targets (their rings are nearly the
  whole box);
- ``mesh``: ``_ve_forces_sharded`` on a 4-device CPU mesh (``evrard-ve-4m-x4``,
  ``turb-ve-8m-x4``), 12^3, every particle a target, in a fresh process

on a ``turbulence`` box given a velocity field with shear and compression
and a spread of viscosity switches, one step in (so the lists are live and
the state is the program's own). The comparison is the one
benchmarks/check_turb_mesh.py makes on the chip (``compare_forces``), under
the limits ``turb-ve-8m-x4.json`` states, with its control: the reference's
kernel values rounded to bf16 on their bits must be refused.

Pallas kernels run in interpret mode here; nothing in this file is a speed.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)
BENCH = os.path.join(ROOT, "benchmarks")
for p in (TESTS, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = os.path.join(BENCH, "configs", "turb-ve-8m-x4.json")
SEED = 4500000045
SIDE, SIDE_LISTS = 12, 29
LIST_TARGETS, CONTROL_TARGETS = 16, 4

#: What this CPU tier reads (reference_sph_std.errors' keys; float32 sums of
#: ~100 terms in another order, a polynomial fit of the kernel against the
#: analytic one): rho 5e-7 to 7e-7, acceleration rms 6e-7 to 9e-7 / max
#: 1.2e-6 to 4e-6, du 8e-7 to 3e-6 of the sample's rms, alpha 2e-7 absolute.
#: Held five to ten times over that; the configuration's limits are wider
#: (the chip's reading sets them) and the control reads a thousand times
#: more
LIMITS = {"rho_rel_max": 5e-6, "acc_rel_rms": 5e-6, "acc_rel_max": 2e-5,
          "du_rel_max": 2e-5, "alpha_abs_max": 2e-6}

#: conftest.pytest_generate_tests: the importing module's ``CASE`` is the
#: one parameter of this fixture
CASE_FIXTURE = "path"


@pytest.fixture(scope="module")
def path(request):
    return request.param


@pytest.fixture(scope="module")
def limits():
    with open(CONFIG) as f:
        return json.load(f)["guarantees"]["forces_ve_rel_max"]


def stirred(side):
    """A ``turbulence`` box with a velocity field of shear and compression
    at Mach 0.3 (c = 1), a little noise, a spread of viscosity switches
    and a last step of 1e-4."""
    import jax.numpy as jnp

    from sphexa_tpu.init import make_initializer

    state, box, const = make_initializer("turbulence")(side)
    rng = np.random.default_rng(45)
    x, y, z = (np.asarray(a, np.float64) for a in (state.x, state.y, state.z))
    k = 2.0 * np.pi
    wave = lambda a, b: 0.3 * (np.sin(k * a) + 0.5 * np.sin(2 * k * b))
    noise = lambda: 0.02 * rng.standard_normal(state.n)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    state = dataclasses.replace(
        state, vx=f32(wave(y, x) + noise()), vy=f32(wave(z, y) + noise()),
        vz=f32(wave(x, z) + noise()),
        alpha=f32(0.05 + 0.4 * rng.random(state.n)),
        min_dt=f32(1e-4), min_dt_m1=f32(1e-4))
    return state, box, const


def driven(side, **kw):
    from sphexa_tpu.simulation import Simulation

    state, box, const = stirred(side)
    sim = Simulation(state, box, const, prop="turb-ve", backend="pallas",
                     check_every=2, **kw)
    sim.step()
    sim.flush()
    return sim, const


def compared(sim, const, count):
    """check_turb_mesh's comparison and its control on ``sim``'s live
    state: ``count`` seeded targets (None: every particle)."""
    import check_turb_mesh as ctm
    import reference

    n = int(sim.state.n)
    targets = (np.arange(n) if count is None
               else reference.seeded_targets(SEED, n, count))
    stage = ctm.system_forces(sim)
    return {
        "sound": ctm.compare_forces(sim, const, targets, stage=stage),
        "control": ctm.compare_forces(
            sim, const, targets[:CONTROL_TARGETS], stage=stage,
            product_dtype="bfloat16"),
        "lists": sim.pair_lists is not None,
        "particles": n,
    }


MESH_RUNNER = """
    import json, sys
    sys.path[:0] = [{bench!r}, {tests!r}]
    import ve_reference_cases as t

    sim, const = t.driven({side}, num_devices=4)
    out = t.compared(sim, const, None)
    out["halo"] = sim._halo_info["mode"]
    out["devices"] = int(sim._mesh.size)
    print("VE-REFERENCE-MESH " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result(path):
    """The path's comparison, driven once for the whole module."""
    if path == "streamed":
        return compared(*driven(SIDE, use_lists=False), None)
    if path == "lists":
        return compared(*driven(SIDE_LISTS, use_lists=True), LIST_TARGETS)
    from conftest import run_mesh_subprocess

    done = run_mesh_subprocess(MESH_RUNNER.format(
        bench=BENCH, tests=TESTS, side=SIDE))
    lines = [ln for ln in done.stdout.splitlines()
             if ln.startswith("VE-REFERENCE-MESH ")]
    assert lines, done.stderr[-3000:]
    return json.loads(lines[-1].split(" ", 1)[1])


def test_the_path_ran_its_engine(result, path):
    assert result["lists"] == (path == "lists")
    assert result["particles"] == (SIDE_LISTS if path == "lists"
                                   else SIDE) ** 3
    if path == "mesh":
        assert (result["halo"], result["devices"]) == ("sparse", 4)


def test_targets_see_images_across_a_face(result, path):
    """Every ring is found by all-pairs distance tests under the minimum
    image; the targets include rows whose support crosses a periodic face,
    and nothing the comparison read lay outside its ring (NaN there)."""
    r = result["sound"]
    assert r["finite"]
    assert 0 < r["face_targets"] <= r["targets"]
    a, b, c, d = r["rings"]
    assert r["targets"] <= a <= b <= c <= d <= result["particles"]
    assert 50 <= r["nc_mean_targets"] <= 150
    # the switches moved: some rows grew past their start, some decay
    assert r["alpha_range"][0] < 0.1 < r["alpha_range"][1]
    assert r["acc_rms"] > 0.0 and r["du_rms"] > 0.0


@pytest.mark.parametrize("key", sorted(LIMITS))
def test_force_stage_is_the_reference(result, limits, path, key):
    got = result["sound"]["errors"][key]
    assert got < LIMITS[key], result["sound"]["errors"]
    stated = {"rho_rel_max": "rho", "acc_rel_rms": "acc_rms",
              "acc_rel_max": "acc_max", "du_rel_max": "du"}
    if key in stated:
        assert got < limits[stated[key]]


def test_configuration_limits_admit_it(result, limits, path):
    import check_turb_mesh as ctm

    assert ctm.forces_inside(result["sound"]["errors"], limits)


def test_bit_mask_control_is_refused(result, limits, path):
    """One precision down in the kernel values alone: refused by the
    stated limits, by each of the four and by a factor over twenty."""
    import check_turb_mesh as ctm

    err = result["control"]["errors"]
    assert not ctm.forces_inside(err, limits)
    for key, name in (("rho_rel_max", "rho"), ("acc_rel_rms", "acc_rms"),
                      ("acc_rel_max", "acc_max"), ("du_rel_max", "du")):
        assert err[key] > 20 * limits[name], (key, err)
