"""The ``streamed`` case of tests/ve_reference_cases.py (which see), and what
holds of benchmarks/reference_sph_ve.py by itself: its bit-mask rounding is
bfloat16's, it imports nothing of the program, and its minimum image is a
translation of the periodic box."""

CASE = "streamed"

import re  # noqa: E402

from ve_reference_cases import *  # noqa: E402,F401,F403  (the case's tests)
from ve_reference_cases import BENCH, SEED, SIDE, np, os, stirred  # noqa: E402


def test_bit_mask_rounding_is_bfloat16():
    """The control rounds on the bits (an ``astype`` round trip is taken
    out by the chip's compiler): to the value ``bfloat16`` holds, ties to
    even, for every sign and size."""
    import ml_dtypes

    import reference_sph_ve as rv

    rng = np.random.default_rng(7)
    a = np.concatenate([
        rng.standard_normal(4096) * 10.0 ** rng.integers(-20, 20, 4096),
        [0.0, -0.0, 1.0, 1.00390625, 1.01171875, 3.0e38, -1.5e-38]])
    a = a.astype(np.float32)
    got = np.asarray(rv._round_bits(a, "bfloat16"))
    want = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.any(got != a)
    np.testing.assert_array_equal(np.asarray(rv._round_bits(a, None)), a)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_sph_ve.py")) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:import|from)\s+([\w.]+)", source, re.M)
    # (its two siblings are the benchmark's other plain references)
    assert sorted(set(imports)) == ["functools", "jax", "jax.numpy", "numpy",
                                    "reference", "reference_sph_std"]
    assert "sphexa_tpu" not in "".join(
        ln for ln in source.splitlines() if "import" in ln)
    assert "Departures from the published form" in source


def test_minimum_image_is_a_translation_of_the_box():
    """Independent of the program: shifting every particle by one vector
    and wrapping it back into the periodic box moves other rows across the
    faces and leaves every target's forces what they were."""
    import reference
    import reference_sph_ve as rv

    state, box, const = stirred(SIDE)
    lengths = np.asarray(box.lengths)
    lo = np.asarray(box.lo)
    targets = reference.seeded_targets(SEED, int(state.n), 12)
    kw = dict(lengths=lengths, periodic=(True, True, True),
              gamma=const.gamma, cv=const.cv, sinc_index=const.sinc_index)
    fields = lambda xyz: (*xyz, state.vx, state.vy, state.vz, state.h,
                          state.m, state.temp, state.alpha, 1e-4)
    xyz = [np.asarray(a) for a in (state.x, state.y, state.z)]
    here = rv.ve_forces(targets, *fields(xyz), **kw)
    shift = np.array([0.37, 0.61, 0.13]) * lengths
    moved = [(lo[a] + np.mod(c - lo[a] + shift[a], lengths[a])).astype(
        np.float32) for a, c in enumerate(xyz)]
    there = rv.ve_forces(targets, *fields(moved), **kw)
    assert [here[k] for k in ("ring_a", "ring_b", "ring_c", "ring_d")] == [
        there[k] for k in ("ring_a", "ring_b", "ring_c", "ring_d")]
    scale = np.sqrt(np.mean(here["ax"] ** 2 + here["ay"] ** 2
                            + here["az"] ** 2))
    for k in ("ax", "ay", "az"):
        assert np.abs(here[k] - there[k]).max() < 1e-5 * scale, k
    np.testing.assert_allclose(there["rho"], here["rho"], rtol=2e-6)
    np.testing.assert_allclose(there["alpha"], here["alpha"], atol=1e-6)
    # and with the image off, a target at a face loses its neighbours
    open_box = rv.ve_forces(targets, *fields(xyz), **dict(
        kw, periodic=(False, False, False)))
    assert np.abs(open_box["rho"] / here["rho"] - 1.0).max() > 0.05
