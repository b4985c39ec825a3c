"""Persistent pair lists on a mesh UNDER SELF-GRAVITY: the tier-1 cases of
the list step of the two mesh gravity cells (``evrard-ve-4m-x4.steady``,
``evrard-cooling-4m-x4.steady``). Not collected itself:
``test_mesh_gravity_lists.py`` (``ve``) and
``test_mesh_gravity_lists_cooling.py`` (``std-cooling``, with ``chem`` as the
aux state) set ``CASE`` and import these tests, one module a step family
(``--dist loadfile`` may run them side by side).

``Simulation(num_devices=4, backend="pallas", theta=0.5)`` on a small Evrard
sphere (``-n 16``: 2,160 particles, 540 a slab), in a fresh process on a
virtual CPU mesh (conftest.run_mesh_subprocess). Every particle carries its
own mass (1 % spread over the rows of the IC): the id a row is followed by
through the sorts, the solve's key-sorted copy among them.

Held here, per family:

- the driver walks lists on the mesh under gravity (``engine.lists``,
  ``rebuild_lists`` events, the ``exchange`` events' ``layout_age_steps``,
  and an ``exchange`` event of stage ``sort`` with ``migrant_rows`` at every
  check boundary);
- the list force stage against the streamed mesh force stage ON THE SAME
  STATE, two steps after a rebuild, with the last row of slab 0 moved across
  the slab boundary of the key order: ``nc`` equal for every particle,
  ``rho``, the summed accelerations (hydro + gravity) and ``du`` to 1e-5 of
  their largest value, ``egrav`` and the acceleration's dt candidate to
  1e-5, and ``sort_migrant_rows`` >= 1 (the hydro serves that row over the
  frozen layout; the solve's copy has it on slab 1);
- ``_add_gravity`` on the mesh: a shuffled state with ``keys=None`` against
  the key-sorted state, the accelerations back in the state's order (the
  ``ve`` module: the solve knows no step family);
- ``chem`` row-aligned through every rebuild (``std-cooling``);
- a near-field cap cut under the lists' need: the window rolls back, the
  caps are re-sized and the lists rebuilt (the ``std-cooling`` module, as
  tests/test_gravity_lists.py holds it on one device);
- the steady step's lowered text: under phase ``sort`` exactly one payload
  sort (seven operands) and one way-back sort (four), no row gather of the
  state, and no cell table, coverage, localizing or cell-range scope.

``backend="pallas"`` is this file's steering: on the CPU ``auto`` is the XLA
path, which has no sharded stage. Kernels run in interpret mode; nothing here
is a speed.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
if TESTS not in sys.path:
    sys.path.insert(0, TESTS)

from mesh_list_cases import (  # noqa: E402
    FIELD_RTOL, P, _crossing, _per_device, _place, _xyz)
# the mass label and the chemistry that is a function of it alone, as the
# one-device cases use them
from test_gravity_lists import label_of, probe_chem  # noqa: E402

SIDE = 16
#: the sphere of SIDE, trimmed to the mesh
ROWS = 2160
FAMILIES = {"ve": ("evrard", "ve"),
            "std-cooling": ("evrard-cooling", "std-cooling")}

RUNNER = """
    import json, sys
    sys.path[:0] = [{tests!r}]
    from mesh_gravity_list_cases import drive
    print("MESH-GRAVITY-LISTS-RESULT " + json.dumps(drive({family!r})))
"""


def _make_sim(family, sink):
    import jax
    import jax.numpy as jnp

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.physics.cooling import ChemistryData, CoolingConfig
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry

    init, pname = FAMILIES[family]
    state, box, const = make_initializer(init)(SIDE)
    n_full, keep = state.n, (state.n // P) * P
    state = jax.tree.map(
        lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
        and a.shape[0] == n_full else a, state)
    m0 = float(state.m[0])
    label = np.arange(state.n) / state.n
    state = dataclasses.replace(
        state, m=jnp.asarray(m0 * (1.0 + 0.01 * label), jnp.float32))
    kw = {}
    if family == "std-cooling":
        kw = {"chem": ChemistryData(**{
                  k: jnp.asarray(v, jnp.float32)
                  for k, v in probe_chem(label).items()}),
              "cooling_cfg": CoolingConfig(gamma=const.gamma,
                                           evolve_species=False)}
    sim = Simulation(state, box, const, prop=pname, theta=0.5,
                     num_devices=P, backend="pallas", check_every=2,
                     telemetry=Telemetry(sinks=[sink]), **kw)
    return sim, m0


def _stage(sim, family, lists):
    """The force stage the next step would run, on ``sim``'s live state
    (under gravity): fields by id, the solve's scalars, the diagnostics."""
    import jax

    from sphexa_tpu import propagator as prop

    cfg, gtree = sim.active_cfg, sim._gtree
    if family == "ve":
        out = jax.jit(lambda s, b, ls: prop._ve_forces(
            s, b, cfg, gtree, lists=ls, raw_dts=True))(
            sim.state, sim.box, lists)
        (state, _, ax, ay, az, du, dts, _, nc, _, rho, _, gdiag) = out
        dt_acc = dts[2][0]
    else:
        out = jax.jit(lambda s, b, c, ls: prop.std_forces(
            s, b, cfg, gtree, aux=c, lists=ls))(
            sim.state, sim.box, sim.chem, lists)
        (state, _, ax, ay, az, du, _, extra, nc, _, rho, _, gdiag, _) = out
        dt_acc = extra[0]
    by_id = np.argsort(np.asarray(state.m))
    fields = {k: np.asarray(v)[by_id] for k, v in (
        ("nc", nc), ("rho", rho), ("ax", ax), ("ay", ay), ("az", az),
        ("du", du))}
    scalars = {"egrav": float(gdiag["egrav"]), "dt_acc": float(dt_acc)}
    return fields, scalars, gdiag


def _compare(sim, family):
    """List stage against streamed stage on the live state."""
    got, gs, gdiag = _stage(sim, family, sim.pair_lists)
    ref, rs, rdiag = _stage(sim, family, None)
    rel = {k: float(np.max(np.abs(got[k] - ref[k]))
                    / max(np.max(np.abs(ref[k])), 1e-30))
           for k in ("rho", "du")}
    amax = max(np.max(np.abs(ref[k])) for k in ("ax", "ay", "az"))
    rel["a"] = float(max(np.max(np.abs(got[k] - ref[k]))
                         for k in ("ax", "ay", "az")) / max(amax, 1e-30))
    rel.update({k: abs(gs[k] - rs[k]) / abs(rs[k]) for k in gs})
    return {"nc_mismatch": int(np.sum(got["nc"] != ref["nc"])),
            "nc_mean": float(got["nc"].mean()), "rel": rel,
            "egrav": gs["egrav"],
            "list_ok": _per_device(gdiag["list_ok"]),
            "migrant_rows": int(gdiag["sort_migrant_rows"]),
            "streamed_keys": sorted(
                k for k in ("sort_migrant_rows", "list_slack")
                if k in rdiag)}


def _solve_of_a_shuffle(sim):
    """``_add_gravity`` on the key-sorted state with its keys, and with
    ``keys=None`` on a shuffle of it: what comes back, row for row."""
    import jax
    import jax.numpy as jnp

    from sphexa_tpu import propagator as prop
    from sphexa_tpu.parallel import shard_state
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    s, cfg = sim.state, sim.active_cfg
    keys = compute_sfc_keys(s.x, s.y, s.z, sim.box, curve=cfg.curve)
    assert bool(jnp.all(keys[1:] >= keys[:-1]))
    perm = np.random.default_rng(7).permutation(s.n)
    shuffled = shard_state(jax.tree.map(
        lambda a: a[perm] if getattr(a, "ndim", 0) == 1 else a, s),
        sim._mesh)
    zero = jnp.zeros_like(s.x)
    solve = jax.jit(lambda st, k: prop._add_gravity(
        st, sim.box, k, cfg, sim._gtree, zero, zero, zero))
    a, b = solve(s, keys), solve(shuffled, None)
    scale = max(float(jnp.max(jnp.abs(g))) for g in a[:3])
    back = max(float(np.max(np.abs(np.asarray(gb) - np.asarray(ga)[perm])))
               for ga, gb in zip(a[:3], b[:3])) / scale
    astray = float(np.max(np.abs(np.asarray(a[0])[perm]
                                 - np.asarray(a[0])))) / scale
    return {"back_in_order": back, "left_in_key_order": astray,
            "egrav_rel": abs(float(b[3]) - float(a[3])) / abs(float(a[3])),
            "dt_acc_rel": abs(float(b[4]) - float(a[4])) / float(a[4]),
            "caps_equal": all(int(a[5][k]) == int(b[5][k])
                              for k in ("m2p_max", "p2p_max", "leaf_occ")),
            "migrant_rows": int(b[5]["sort_migrant_rows"]),
            "sharded": [str(g.sharding.spec) for g in b[:3]]}


def _lowering(sim):
    """The scopes of the lowered steady list step and the ops under its
    ``sort`` phase (each location's path ends in its primitive)."""
    import io
    import re

    ss = sim.sim_state
    buf = io.StringIO()
    sim._stepper._jitted.lower(
        ss.particles, ss.box, sim._gtree, sim.chem,
        sim.pair_lists).compiler_ir(dialect="stablehlo").operation.print(
        file=buf, enable_debug_info=True)
    text = buf.getvalue()
    paths = set(re.findall(r'loc\("([^"]*sphexa/[^"]*)"', text))
    gone = ("halo-exchange~cover", "halo-exchange~localize",
            "halo-exchange~table", "neighbors~cell-ranges", "sort~aux")
    in_sort = sorted(p[p.index("sphexa/sort"):] for p in paths
                     if "sphexa/sort" in p)
    # (the near field's exchange opens the same stages under its own
    # first phase, ``gravity-exchange``)
    hydro = [p for p in paths if "sphexa/gravity" not in p]
    return {
        "scopes_left": sorted(g for g in gone if any(g in p for p in hydro)),
        "sorts": [p for p in in_sort if p.endswith("/sort")],
        "gathers": [p for p in in_sort if p.endswith("/gather")],
        "sort_operands": sorted(
            len(m.split(",")) for m in re.findall(
                r'"stablehlo\.sort"\(([^)]*)\)', text)
            if len(m.split(",")) in (4, 7)),
        "ppermute": len(re.findall(r"stablehlo\.collective_permute", text)),
    }


def drive(family):
    """Everything the tests below assert on, as plain numbers (runs in the
    mesh subprocess)."""
    from sphexa_tpu.telemetry.sinks import MemorySink

    sink = MemorySink()
    sim, m0 = _make_sim(family, sink)
    S = sim.state.n // P
    out = {"eligible": bool(sim._lists_eligible),
           "use_lists": bool(sim._use_lists),
           "gravity_on": bool(sim.gravity_on)}

    def steps(k):
        for _ in range(k):
            sim.step()
        sim.flush()

    def row_of(tag):
        return int(np.flatnonzero(
            np.asarray(sim.state.m) == np.float32(tag))[0])

    # the last row of slab 0 is put beside the first row of slab 1 and a
    # list is built with it there...
    sim._rebuild_lists("first")
    if family == "ve":
        out["solve"] = _solve_of_a_shuffle(sim)
    start, end = _crossing(sim, SIDE)
    tag = float(sim.state.m[S - 1])
    _place(sim, S - 1, start)
    sim._rebuild_lists("proactive")
    before = row_of(tag)
    # ...two steps on it steps over the boundary, inside the skin: the
    # hydro's frozen layout still serves it, the solve's copy has it on
    # slab 1 (the stage on a list two steps old)
    steps(2)
    _place(sim, before, _xyz(sim, before) + (end - start))
    out["crossed"] = _compare(sim, family)
    out["crossed"]["row_before"] = before
    out["lowering"] = _lowering(sim)

    if family == "std-cooling":
        steps(2)
        # the driver's near-field cap cut under the lists' need (the
        # launched program keeps its own: the boundary's check is what
        # reads an overflow): the window rolls back, the caps are
        # re-sized and the lists rebuilt
        mark, it = len(sink.events), sim.iteration
        sim._cfg = dataclasses.replace(
            sim._cfg, gravity=dataclasses.replace(sim._cfg.gravity,
                                                  p2p_cap=4))
        steps(2)
        out["overflow"] = {
            "it": it, "p2p_cap": int(sim._cfg.gravity.p2p_cap),
            "stepper_cap": int(sim._stepper.cfg.gravity.p2p_cap),
            "lists_after": sim.pair_lists is not None,
            "events": [(e["kind"], e.get("reason"),
                        e.get("to_it", e.get("it")))
                       for e in sink.events[mark:]
                       if e["kind"] in ("rollback", "replay",
                                        "rebuild_lists", "reconfigure")]}

    label = label_of(sim.state.m, m0)
    out["rows"] = {"shuffled": bool(np.any(np.diff(label) < 0)),
                   "ids_conserved": bool(np.allclose(
                       np.sort(label), np.arange(label.size) / label.size,
                       atol=1e-4))}
    if family == "std-cooling":
        want = probe_chem(label)
        out["chem"] = {
            "worst": float(max(np.abs(np.asarray(
                getattr(sim.chem, k), np.float64) - v).max()
                for k, v in want.items())),
            "stale": float(np.abs(probe_chem(
                np.arange(label.size) / label.size)["metal"]
                - want["metal"]).max())}
    events = sink.events
    sort_ev = [e for e in events
               if e["kind"] == "exchange" and e["stage"] == "sort"]
    out["events"] = {
        "engine_lists": [e["engine"]["lists"] for e in events
                         if e["kind"] == "reconfigure"],
        "rebuilds": [e["reason"] for e in events
                     if e["kind"] == "rebuild_lists"],
        "layout_age": [e["layout_age_steps"] for e in events
                       if e["kind"] == "exchange" and e["stage"] == "sph"],
        "boundaries": len([e for e in events
                           if e["kind"] in ("window", "step")]),
        "sort_events": len(sort_ev),
        "sort_rows": sorted({e["rows"] for e in sort_ev}),
        "sort_migrants": [e["migrant_rows"] for e in sort_ev],
        "trips": [e["trips"] for e in events if e["kind"] == "exchange"
                  and "trips" in e],
        "finite": bool(np.isfinite(np.asarray(sim.state.x)).all()
                       and np.isfinite(np.asarray(sim.state.temp)).all()),
    }
    return out


CASE_FIXTURE = "run"


@pytest.fixture(scope="module")
def run(request):
    from conftest import run_mesh_subprocess

    family = request.param
    proc = run_mesh_subprocess(RUNNER.format(tests=TESTS, family=family))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("MESH-GRAVITY-LISTS-RESULT ")]
    assert lines, proc.stderr[-3000:]
    return family, json.loads(lines[-1].split(" ", 1)[1])


def test_driver_walks_lists_on_the_mesh_under_gravity(run):
    family, out = run
    assert out["gravity_on"] and out["eligible"] and out["use_lists"]
    ev = out["events"]
    assert ev["engine_lists"] and all(ev["engine_lists"])
    assert ev["rebuilds"][:2] == ["first", "proactive"]
    # the layout ages with the list (windows of two)...
    assert ev["layout_age"][0] == 1
    if family == "std-cooling":
        # ...and starts again at a rebuild (this family drives on)
        assert 0 in ev["layout_age"]
    assert set(ev["trips"]) == {0} and ev["finite"]


def test_sort_exchange_event_at_every_check_boundary(run):
    family, out = run
    ev = out["events"]
    assert ev["sort_events"] == ev["boundaries"] > 0
    assert ev["sort_rows"] == [ROWS]
    assert all(m >= 0 for m in ev["sort_migrants"])


def test_list_stage_equals_streamed_stage_under_gravity(run):
    family, out = run
    got = out["crossed"]
    assert got["list_ok"] == [1] * P
    assert got["nc_mismatch"] == 0 and got["nc_mean"] > 40
    assert got["egrav"] < 0.0
    assert max(got["rel"].values()) < FIELD_RTOL, got["rel"]
    # the streamed stage sorts the state and counts migrants only where
    # an aux state rides that sort
    want = ["sort_migrant_rows"] if family == "std-cooling" else []
    assert got["streamed_keys"] == want


def test_the_copy_has_the_crossed_row_on_the_other_slab(run):
    family, out = run
    got = out["crossed"]
    # built as the last row of slab 0, stepped over the boundary of the
    # key order: the frozen row stays, the solve's copy has it on slab 1
    assert got["row_before"] == ROWS // P - 1
    assert 1 <= got["migrant_rows"] <= 4


def check_add_gravity_sorts_its_own_copy_on_the_mesh(run):
    family, out = run
    got = out["solve"]
    assert got["back_in_order"] < 1e-5
    # a solve left in key order would be rows astray by O(1)
    assert got["left_in_key_order"] > 0.1
    assert got["egrav_rel"] < 2e-6 and got["dt_acc_rel"] < 1e-5
    assert got["caps_equal"]
    # a shuffle leaves three rows in four on another slab
    assert got["migrant_rows"] > ROWS // 2
    assert got["sharded"] == ["PartitionSpec('p',)"] * 3


def check_gravity_overflow_under_lists_resizes_and_rebuilds(run):
    family, out = run
    got = out["overflow"]
    assert got["p2p_cap"] > 4 and got["lists_after"]
    assert got["stepper_cap"] == got["p2p_cap"]
    events = [tuple(e) for e in got["events"]]
    assert ("rollback", "overflow", got["it"]) in events
    assert ("reconfigure", "overflow", got["it"]) in events
    assert ("rebuild_lists", "reconfigure", got["it"]) in events
    assert "replay" in [e[0] for e in events]


def test_rows_and_chem_stay_aligned_through_every_rebuild(run):
    family, out = run
    assert len(out["events"]["rebuilds"]) >= 2
    assert out["rows"]["ids_conserved"] and out["rows"]["shuffled"]
    if family == "std-cooling":
        assert out["chem"]["worst"] < 5e-6 and out["chem"]["stale"] > 1e-3
    else:
        assert "chem" not in out


def test_lowered_list_step_under_gravity(run):
    family, out = run
    low = out["lowering"]
    assert low["scopes_left"] == []
    assert len(low["sorts"]) == 2, low["sorts"]
    assert [s.split("/")[-2] for s in low["sorts"]] == [
        "sort~order", "sort~permute"]
    assert low["gathers"] == []
    assert low["sort_operands"].count(7) == 1
    assert low["sort_operands"].count(4) >= 1
