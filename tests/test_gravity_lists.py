"""Persistent pair lists under self-gravity on one device (PR 44): the force
stage keeps the lists' frozen order and the tree solve sorts a copy of its
five inputs (``propagator._add_gravity``), at CPU sizes with the Mosaic
kernels interpreted.

Small Evrard spheres (``-n 10``: 534 particles), every particle its own mass
(1 % spread: a label that rides every permutation). Held here: a list-mode
force stage under gravity against the streamed stage of the SAME live state
for ``std``, ``ve`` and ``std-cooling`` (``nc`` equal for every particle,
the sums to the tolerances tests/pair_list_cases.py holds lists to against
streaming, ``egrav`` and the dt candidates); the solve's ``gx, gy, gz`` of a
shuffled state in that state's order; the gate (``use_lists=False`` streams
under gravity; the mesh's half is tests/mesh_gravity_list_cases.py); and the
driver's list lifecycle under gravity with ``chem`` as the aux state:
rebuilds keep the chemistry row-aligned, a forced ``list-expiry`` rolls back
and replays, a forced gravity ``overflow`` reconfigure rebuilds the lists."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sphexa_tpu import propagator as prop_mod
from sphexa_tpu.init import make_initializer
from sphexa_tpu.physics.cooling import ChemistryData, CoolingConfig
from sphexa_tpu.simulation import Simulation
from sphexa_tpu.telemetry import Telemetry
from sphexa_tpu.telemetry.sinks import MemorySink

SIDE = 10
PROPS = ["std", "ve", "std-cooling"]


def labelled(prop):
    init = "evrard-cooling" if prop == "std-cooling" else "evrard"
    state, box, const = make_initializer(init)(SIDE)
    label = np.arange(state.n) / state.n
    m0 = float(state.m[0])
    state = dataclasses.replace(
        state, m=jnp.asarray(m0 * (1.0 + 0.01 * label), jnp.float32))
    return state, box, const, m0


def label_of(m, m0):
    return (np.asarray(m, np.float64) / m0 - 1.0) / 0.01


def probe_chem(label):
    """A chemistry that is a function of the row's label alone (the CIE
    table passes the fractions through: any row astray shows)."""
    x, y = 0.76, 1.0 - 0.76 - 0.0122
    chem = {"hi": x * 0.2 * label, "hii": x * (1 - 0.2 * label),
            "hei": y * 0.1 * label, "heii": y * 0.3 * (1 - label),
            "heiii": y * (1 - 0.1 * label - 0.3 * (1 - label)),
            "metal": 0.005 + 0.01 * label}
    chem["e"] = chem["hii"] + chem["heii"] / 4.0 + chem["heiii"] / 2.0
    return chem


def make_sim(prop, sink=None, **kw):
    state, box, const, m0 = labelled(prop)
    if prop == "std-cooling":
        seeded = probe_chem(np.arange(state.n) / state.n)
        kw.setdefault("chem", ChemistryData(**{
            k: jnp.asarray(v, jnp.float32) for k, v in seeded.items()}))
        kw.setdefault("cooling_cfg", CoolingConfig(gamma=const.gamma,
                                                   evolve_species=False))
    if sink is not None:
        kw["telemetry"] = Telemetry(sinks=[sink])
    kw.setdefault("backend", "pallas")
    sim = Simulation(state, box, const, prop=prop, theta=0.5, **kw)
    return sim, m0


@pytest.fixture(scope="module", params=PROPS)
def stepped(request):
    """Three checked steps on lists under gravity (the first list, the
    ``h-relax`` re-size of the rim and its rebuild are in them), then the
    force stage of the live state twice: on the live lists, and streamed."""
    prop = request.param
    sink = MemorySink()
    sim, m0 = make_sim(prop, sink)
    diags = [sim.step() for _ in range(3)]
    assert sim.pair_lists is not None
    cfg, lists = sim._cfg, sim.pair_lists
    if prop == "ve":
        stage = lambda ls: prop_mod._ve_forces(
            sim.state, sim.box, cfg, sim._gtree, lists=ls, raw_dts=True)
        names = ("state", "box", "ax", "ay", "az", "du", "dts", "alpha",
                 "nc", "occ", "rho", "c", "gdiag")
    else:
        stage = lambda ls: prop_mod._std_forces(
            sim.state, sim.box, cfg, sim._gtree, aux=sim.chem, lists=ls)
        names = ("state", "box", "ax", "ay", "az", "du", "dt_courant",
                 "extra_dts", "nc", "occ", "rho", "c", "gdiag", "aux")
    out = {"lists": dict(zip(names, jax.jit(lambda: stage(lists))())),
           "streamed": dict(zip(names, jax.jit(lambda: stage(None))()))}
    return sim, sink, m0, diags, out


def by_label(out, m0, key):
    order = np.argsort(label_of(out["state"].m, m0))
    return np.asarray(out[key])[order]


class TestForceStageOnListsUnderGravity:
    def test_lists_are_on_and_say_so(self, stepped):
        sim, sink, _, diags, _ = stepped
        assert sim.gravity_on and sim._use_lists
        assert all("list_slack" in d and "egrav" in d for d in diags)
        assert all(int(d["list_ok"]) == 1 for d in diags)
        engine = sink.of_kind("reconfigure")[-1]["engine"]
        assert engine["lists"] is True and engine["gravity"] is not None
        built = sink.of_kind("rebuild_lists")
        assert built and built[0]["reason"] == "first"
        # sized at construction for where the rim's h is heading: no
        # ``h-relax`` re-size (a second set of programs) in the start-up
        assert [e["reason"] for e in sink.of_kind("reconfigure")] == [
            "initial"]
        assert sim._h_sized > 1.15 * float(jnp.max(labelled("std")[0].h))
        # every verified step's solver diagnostics are inside their caps
        assert not any(sim._gravity_overflowed(d) for d in diags)

    def test_the_frozen_order_is_kept_and_streaming_sorts(self, stepped):
        sim, _, m0, _, out = stepped
        np.testing.assert_array_equal(np.asarray(out["lists"]["state"].m),
                                      np.asarray(sim.state.m))
        # the list stage leaves the hydro grid's box alone
        np.testing.assert_array_equal(np.asarray(out["lists"]["box"].lo),
                                      np.asarray(sim.box.lo))

    def test_nc_is_the_streamed_steps_for_every_particle(self, stepped):
        _, _, m0, _, out = stepped
        np.testing.assert_array_equal(by_label(out["lists"], m0, "nc"),
                                      by_label(out["streamed"], m0, "nc"))

    def test_the_sums_are_the_streamed_steps_to_rounding(self, stepped):
        _, _, m0, _, out = stepped
        a, b = out["lists"], out["streamed"]
        np.testing.assert_allclose(by_label(a, m0, "rho"),
                                   by_label(b, m0, "rho"), rtol=2e-6)
        scale = max(np.abs(by_label(b, m0, k)).max()
                    for k in ("ax", "ay", "az"))
        for k in ("ax", "ay", "az"):
            np.testing.assert_allclose(by_label(a, m0, k),
                                       by_label(b, m0, k),
                                       rtol=1e-4, atol=1e-5 * scale)
        du = by_label(b, m0, "du")
        np.testing.assert_allclose(by_label(a, m0, "du"), du, rtol=1e-4,
                                   atol=1e-5 * max(np.abs(du).max(), 1e-30))
        np.testing.assert_allclose(by_label(a, m0, "c"),
                                   by_label(b, m0, "c"), rtol=2e-6)

    def test_egrav_dt_and_solver_diagnostics_are_order_free(self, stepped):
        _, _, _, _, out = stepped
        a, b = out["lists"], out["streamed"]
        ga, gb = a["gdiag"], b["gdiag"]
        assert float(ga["egrav"]) == pytest.approx(float(gb["egrav"]),
                                                   rel=2e-6)
        for k in ("m2p_max", "p2p_max", "leaf_occ"):
            assert int(ga[k]) == int(gb[k])
        assert "list_slack" in ga and "list_slack" not in gb
        flat = lambda o: jax.tree.leaves(
            o["dts"] if "dts" in o else (o["dt_courant"], o["extra_dts"]))
        for x, y in zip(flat(a), flat(b)):
            assert float(x) == pytest.approx(float(y), rel=1e-5)

    def test_chem_stays_with_its_rows(self, stepped):
        sim, _, m0, _, out = stepped
        for o in out.values():
            if sim.prop_name != "std-cooling":
                assert o.get("aux") is None  # no aux state to carry
                continue
            want = probe_chem(label_of(o["state"].m, m0))["metal"]
            np.testing.assert_allclose(np.asarray(o["aux"].metal), want,
                                       atol=5e-6)


class TestTheSolveSortsItsOwnCopy:
    @pytest.fixture(scope="class")
    def solved(self):
        """The solve on the key-sorted state, and in list mode (no keys)
        on a shuffle of it."""
        sim, _ = make_sim("std", use_lists=False)
        sim.step()  # a sorted state in a regrown box
        s, cfg = sim.state, sim._cfg
        keys = prop_mod.compute_sfc_keys(s.x, s.y, s.z, sim.box,
                                         curve=cfg.curve)
        assert bool(jnp.all(keys[1:] >= keys[:-1]))
        perm = np.random.default_rng(7).permutation(s.n)
        shuffled = jax.tree.map(
            lambda a: a[perm] if getattr(a, "ndim", 0) == 1 else a, s)
        zero = jnp.zeros_like(s.x)
        solve = jax.jit(lambda st, k: prop_mod._add_gravity(
            st, sim.box, k, cfg, sim._gtree, zero, zero, zero))
        return solve(s, keys), solve(shuffled, None), perm

    def test_accelerations_come_back_in_the_states_order(self, solved):
        sorted_out, shuffled_out, perm = solved
        scale = max(float(jnp.max(jnp.abs(g))) for g in sorted_out[:3])
        for g_sorted, g_shuffled in zip(sorted_out[:3], shuffled_out[:3]):
            np.testing.assert_allclose(np.asarray(g_shuffled),
                                       np.asarray(g_sorted)[perm],
                                       rtol=1e-5, atol=1e-6 * scale)
        # a solve left in key order would be rows astray by O(1)
        assert np.abs(np.asarray(sorted_out[0])[perm]
                      - np.asarray(sorted_out[0])).max() > 0.1 * scale

    def test_egrav_dt_and_diagnostics_do_not_know_the_order(self, solved):
        sorted_out, shuffled_out, _ = solved
        assert float(shuffled_out[3]) == pytest.approx(float(sorted_out[3]),
                                                       rel=2e-6)
        assert float(shuffled_out[4]) == pytest.approx(float(sorted_out[4]),
                                                       rel=1e-5)
        for k in ("m2p_max", "p2p_max", "leaf_occ"):
            assert int(shuffled_out[5][k]) == int(sorted_out[5][k])


@pytest.mark.parametrize("init", ["evrard", "noh", "sedov"])
def test_hull_h_relax_is_what_the_first_step_shows(init):
    """The host count of where an open box's largest ``h`` are heading
    against the estimate the driver reads from one step's growth
    (``kernels.h_fixed_point``); a periodic box has no hull."""
    from sphexa_tpu.simulation import hull_h_relax
    from sphexa_tpu.sph.kernels import h_fixed_point

    state, box, const = make_initializer(init)(12)
    counted = hull_h_relax(state, box, const.ng0)
    if init == "sedov":
        assert counted == 1.0
        return
    sim = Simulation(state, box, const, prop="std", backend="xla",
                     **({"theta": 0.5} if const.g else {}))
    h0 = float(jnp.max(sim.state.h))
    shown = h_fixed_point(h0, float(sim.step()["h_max"])) / h0
    assert 1.15 < counted < 1.45
    assert counted == pytest.approx(shown, rel=0.05)


@pytest.mark.parametrize("prop,kw,lists", [
    ("std", {"use_lists": False}, False),
    ("std", {"backend": "xla"}, False),
    ("nbody", {}, False),
    ("std", {}, True),
], ids=["lists-off", "xla", "nbody", "std"])
def test_the_gate(prop, kw, lists):
    """Under gravity on one device the default is the walk; ``use_lists``
    False, the XLA engine and ``nbody`` stream (and sort) every step."""
    sim, _ = make_sim(prop, **kw)
    assert sim.gravity_on and sim._use_lists is lists
    d = sim.step()
    assert ("list_slack" in d) is lists
    assert np.isfinite(float(d["egrav"])) and float(d["egrav"]) < 0.0


class TestListLifecycleUnderGravity:
    """``std-cooling`` under gravity on lists, check windows of 4: a forced
    gravity ``overflow`` reconfigure at iteration 4 (the lists are dropped
    and rebuilt), a forced expiry at iteration 8 (the live list's skin cut
    to nothing: the window's first step reads ``list_ok`` 0, the driver
    rolls back, rebuilds and replays), a near-field cap cut under the
    lists' need at iteration 12 (an ``overflow`` rollback under lists)."""

    @pytest.fixture(scope="class")
    def driven(self):
        sink = MemorySink()
        sim, m0 = make_sim("std-cooling", sink, check_every=4)
        for i in range(16):
            if i == 4:
                sim.flush()
                sim._configure(grav_margin=2.0, reason="overflow")
                assert sim.pair_lists is None  # dropped: rebuilt at launch
            if i == 8:
                sim.flush()
                sim._lists = sim._lists._replace(skin=jnp.float32(1e-12))
            if i == 12:
                sim.flush()
                sim._cfg = dataclasses.replace(
                    sim._cfg, gravity=dataclasses.replace(
                        sim._cfg.gravity, p2p_cap=4))
            sim.step()
        sim.flush()
        return sim, sink, m0

    def test_reconfigure_rebuilds_the_lists(self, driven):
        sim, sink, _ = driven
        assert sim.iteration == 16 and sim.pair_lists is not None
        assert "overflow" in [e["reason"]
                              for e in sink.of_kind("reconfigure")]
        built = sink.of_kind("rebuild_lists")
        assert "reconfigure" in [e["reason"] for e in built]
        assert [e for e in built if e["reason"] == "reconfigure"
                and e["it"] == 4]

    def test_forced_expiry_rolls_back_and_replays(self, driven):
        _, sink, _ = driven
        rollbacks = sink.of_kind("rollback")
        assert [e["reason"] for e in rollbacks] == ["list-expiry",
                                                     "overflow"]
        expiry = rollbacks[0]
        assert (expiry["to_it"], expiry["bad_index"]) == (8, 0)
        rebuilt = [e for e in sink.of_kind("rebuild_lists")
                   if e["reason"] == "rollback"]
        assert len(rebuilt) == 1 and rebuilt[0]["slack"] < 0.0
        replays = sink.of_kind("replay")
        assert [e["steps"] for e in replays] == [4, 4]

    def test_gravity_overflow_under_lists_resizes_and_rebuilds(self, driven):
        sim, sink, _ = driven
        assert sim._cfg.gravity.p2p_cap > 4
        last = sink.of_kind("rollback")[-1]
        assert (last["reason"], last["to_it"]) == ("overflow", 12)
        assert [e for e in sink.of_kind("rebuild_lists")
                if e["it"] == 12 and e["reason"] == "reconfigure"]

    def test_chem_is_row_aligned_through_every_rebuild(self, driven):
        sim, sink, m0 = driven
        assert len(sink.of_kind("rebuild_lists")) >= 4
        label = label_of(sim.state.m, m0)
        # the rows are no longer in label order, and chem went with them
        assert np.any(np.diff(label) < 0)
        want = probe_chem(label)
        for k, v in want.items():
            np.testing.assert_allclose(
                np.asarray(getattr(sim.chem, k), np.float64), v, atol=5e-6,
                err_msg=k)
        stale = probe_chem(np.arange(label.size) / label.size)["metal"]
        assert np.abs(stale - want["metal"]).max() > 1e-3

    def test_the_run_stayed_physical(self, driven):
        sim, sink, _ = driven
        assert np.isfinite(float(sim.state.ttot)) and sim.state.ttot > 0
        assert all(np.isfinite(np.asarray(a)).all()
                   for a in (sim.state.x, sim.state.vx, sim.state.temp))
        # sixteen verified steps, the two rolled-back windows replayed
        assert sum(e["steps"] for e in sink.of_kind("window")) == 8
        assert len(sink.of_kind("step")) == 8
