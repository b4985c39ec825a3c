"""Persistent pair lists on a mesh: the tier-1 cases of the list step of the
two hydro mesh cells (``sedov-std-8m-x4.steady``, ``turb-ve-8m-x4.steady``).
Not collected itself: ``test_mesh_lists.py`` (``std`` on the periodic Sedov
lattice) and ``test_mesh_lists_ve.py`` (``ve`` on the jittered periodic
lattice of the ``turbulence`` case) set ``CASE`` and import these tests, one
module a step family, so that ``--dist loadfile`` may run them side by side.

``Simulation(num_devices=4, backend="pallas")`` on a small periodic box, in a
fresh process on a virtual CPU mesh (conftest.run_mesh_subprocess). Every
particle carries its own mass (1 % spread over the rows of the IC): the mass
is the id a row is followed by through the sorts.

Held here, per family:

- the driver walks lists on the mesh (``engine.lists``, ``rebuild_lists``
  events, the ``exchange`` events' ``layout_age_steps``);
- a particle that crosses a slab boundary of the key order between rebuilds
  (the last row of slab 0, put beside the first row of slab 1 before a
  rebuild, steps over it two steps later, well inside the skin) is served by
  the frozen layout until the next rebuild (``list_ok`` 1 on every device)
  and is a row of slab 1 after it, every id still there once;
- the list force stage against the streamed mesh force stage ON THE SAME
  STATE, over a stretch that holds rebuilds: on that list, two steps old,
  with the row across the boundary; and on the list the driver built in a
  rollback, after its replay: ``nc`` equal for every particle, ``rho``, the
  accelerations and ``du`` to 1e-5 of their largest value (the same pairs,
  summed in another order);
- a kick past the skin on ONE slab: ``list_ok`` reads 0 on every device, and
  the driver rolls the window back, rebuilds and replays;
- the lowered steady list step: no sort, no cell table, coverage, localizing
  or cell-range scope, exactly the ppermute rounds of its serves and the tail
  reductions, and no JXA201 over it.

``backend="pallas"`` is this file's steering: on the CPU ``auto`` is the XLA
path, which has no sharded stage. Kernels run in interpret mode; nothing here
is a speed.
"""

import functools
import json
import os
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
if TESTS not in sys.path:
    sys.path.insert(0, TESTS)

P = 4
SIDE = 30  # 27,000 particles, 6,750 a slab: the smallest grids that take lists
FAMILIES = {"std": ("sedov", "std"), "ve": ("turbulence", "ve")}
#: serves of one force stage (propagator.exchange_fields_per_step's rounds)
SERVES = {"std": 3, "ve": 5}
#: pmin / pmax of one list force stage: dt (and VE's dt_rho), the occupancy,
#: the list's slack; and its one all_gather, the shard metrics
ALL_REDUCES = {"std": 3, "ve": 4}
FIELD_RTOL = 1e-5

RUNNER = """
    import json, sys
    sys.path[:0] = [{tests!r}]
    from mesh_list_cases import drive
    print("MESH-LISTS-RESULT " + json.dumps(drive({family!r})))
"""


def _tagged_state(init):
    """The case's IC with a mass of its own on every row (the id)."""
    import dataclasses

    import jax.numpy as jnp

    from sphexa_tpu.init import make_initializer

    state, box, const = make_initializer(init)(SIDE)
    tag = 1.0 + 0.01 * jnp.arange(state.n, dtype=jnp.float32) / state.n
    return dataclasses.replace(state, m=state.m * tag), box, const


@functools.lru_cache(maxsize=None)
def _stage_program(family):
    """The force stage as the steps call it, jitted once a process (a
    trace with ``lists`` and one without)."""
    import jax

    from sphexa_tpu import propagator as prop

    forces = prop.std_forces if family == "std" else prop._ve_forces
    return jax.jit(
        lambda state, box, cfg, lists: forces(state, box, cfg, None,
                                              lists=lists),
        static_argnums=(2,))


def _stage(sim, family, lists):
    """The force stage the next step would run, on ``sim``'s live state:
    ``(fields by id, list diagnostics)``; ``lists`` None streams."""
    out = _stage_program(family)(sim.state, sim.box, sim.active_cfg, lists)
    if family == "std":
        (state, _, ax, ay, az, du, _, _, nc, _, rho, _, gdiag, _) = out
    else:
        (state, _, ax, ay, az, du, _, _, nc, _, rho, _, gdiag) = out
    by_id = np.argsort(np.asarray(state.m))
    fields = {k: np.asarray(v)[by_id] for k, v in (
        ("nc", nc), ("rho", rho), ("ax", ax), ("ay", ay), ("az", az),
        ("du", du))}
    return fields, gdiag


def _per_device(scalar):
    """A replicated scalar as every device holds it."""
    return [int(np.asarray(s.data)) for s in scalar.addressable_shards]


def _compare(sim, family):
    """List stage against streamed stage on the live state: ``nc``
    mismatches, the largest relative field difference, ``list_ok``."""
    got, gdiag = _stage(sim, family, sim.pair_lists)
    ref, _ = _stage(sim, family, None)
    rel = {k: float(np.max(np.abs(got[k] - ref[k]))
                    / max(np.max(np.abs(ref[k])), 1e-30))
           for k in ("rho", "du")}
    amax = max(np.max(np.abs(ref[k])) for k in ("ax", "ay", "az"))
    rel["a"] = float(max(np.max(np.abs(got[k] - ref[k]))
                         for k in ("ax", "ay", "az")) / max(amax, 1e-30))
    ok = _per_device(gdiag["list_ok"])
    return {"nc_mismatch": int(np.sum(got["nc"] != ref["nc"])),
            "nc_mean": float(got["nc"].mean()), "rel": rel, "list_ok": ok}


def _place(sim, row, xyz):
    """Put one row of the live state at ``xyz`` (3,)."""
    import dataclasses

    from sphexa_tpu.parallel import shard_state

    s = sim.state
    sim.state = shard_state(dataclasses.replace(
        s, x=s.x.at[row].set(xyz[0]), y=s.y.at[row].set(xyz[1]),
        z=s.z.at[row].set(xyz[2])), sim._mesh)


def _xyz(sim, row):
    s = sim.state
    return np.asarray([s.x[row], s.y[row], s.z[row]], np.float32)


def _crossing(sim, side=SIDE):
    """Where to put the last row of slab 0 so that a small move takes it
    across the slab boundary of the key order: ``(start, end)``, either
    side of the first row of slab 1 along an axis on which the curve runs
    forward there, 0.1 of a particle spacing apart (no other key lies
    between). The state is key-sorted (a rebuild has just run); ``side``:
    the lattice's cells along the box."""
    import jax.numpy as jnp

    from sphexa_tpu.sfc.keys import compute_sfc_keys

    S = sim.state.n // P
    eps = 0.05 * float(sim.box.lengths[0]) / side
    rows = np.stack([_xyz(sim, r) for r in (S - 2, S, S + 1)])
    for axis in range(3):
        for sign in (1.0, -1.0):
            step = np.zeros(3, np.float32)
            step[axis] = sign * eps
            pts = np.concatenate([rows, [rows[1] - step, rows[1] + step]])
            before, at, after, start, end = (int(k) for k in np.asarray(
                compute_sfc_keys(*(jnp.asarray(pts[:, d]) for d in range(3)),
                                 sim.box, curve=sim.curve)))
            if before < start < at < end < after:
                return pts[3], pts[4]
    raise AssertionError("the curve runs forward along no axis here")


def _lowering(sim, family):
    """Scopes and collectives of the lowered steady list step, and the
    collective-order audit over it."""
    import io
    import re

    from sphexa_tpu import propagator as prop
    from sphexa_tpu.devtools.audit.core import (
        Auditor, EntryCase, EntryPoint)

    ss = sim.sim_state
    buf = io.StringIO()
    sim._stepper._jitted.lower(
        ss.particles, ss.box, None, None, sim.pair_lists).compiler_ir(
        dialect="stablehlo").operation.print(file=buf, enable_debug_info=True)
    text = buf.getvalue()
    paths = set(re.findall(r'loc\("([^"]*sphexa/[^"]*)"', text))
    gone = ("sphexa/sort", "halo-exchange~cover", "halo-exchange~localize",
            "halo-exchange~table", "neighbors~cell-ranges")
    step = prop.step_hydro_std if family == "std" else prop.step_hydro_ve
    cfg = sim.active_cfg
    entry = EntryPoint(
        name=f"step_{family}_lists_sharded", mesh_axes=("p",),
        build=lambda: EntryCase(
            fn=lambda s, b, l: step(s, b, cfg, None, lists=l),
            args=(ss.particles, ss.box, sim.pair_lists)))
    active, _, errors, skipped = Auditor(select=["JXA201"]).run_entries(
        [entry])
    return {
        "scopes_left": sorted(g for g in gone if any(g in p for p in paths)),
        "sort_ops": len(re.findall(r"stablehlo\.sort", text)),
        "ppermute": len(re.findall(r"stablehlo\.collective_permute", text)),
        "all_reduce": len(re.findall(r"stablehlo\.all_reduce", text)),
        "all_gather": len(re.findall(r"stablehlo\.all_gather", text)),
        "all_to_all": len(re.findall(r"stablehlo\.all_to_all", text)),
        "audit": [f.format() for f in active + errors] + skipped,
    }


def drive(family):
    """Everything the tests below assert on, as plain numbers (runs in the
    mesh subprocess)."""
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    init, pname = FAMILIES[family]
    state, box, const = _tagged_state(init)
    sink = MemorySink()
    sim = Simulation(state, box, const, prop=pname, num_devices=P,
                     backend="pallas", check_every=2,
                     telemetry=Telemetry(sinks=[sink]))
    S = sim.state.n // P
    out = {"eligible": bool(sim._lists_eligible),
           "use_lists": bool(sim._use_lists)}

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
    start, end = _crossing(sim)
    tag = float(sim.state.m[S - 1])
    _place(sim, S - 1, start)
    sim._rebuild_lists("proactive")
    skin = float(sim.pair_lists.skin)
    before = row_of(tag)
    # ...two steps on it steps over the boundary, inside the skin: the
    # frozen layout still serves it (the stage on a list two steps old)
    steps(2)
    _place(sim, before, _xyz(sim, before) + (end - start))
    out["crossed"] = _compare(sim, family)
    ids = np.sort(np.asarray(sim.state.m))

    # past the skin on ONE slab: discarded on all; the driver rolls back,
    # rebuilds (the crossed row changes slab here) and replays
    kick = 2 * S + 17
    _place(sim, kick, _xyz(sim, kick) + np.asarray([0.0, 1.5 * skin, 0.0],
                                                   np.float32))
    mark = len(sink.events)
    _, gdiag = _stage(sim, family, sim.pair_lists)
    list_ok = _per_device(gdiag["list_ok"])
    steps(2)
    out["kicked"] = {
        "list_ok": list_ok,
        "events": [(e["kind"], e.get("reason")) for e in sink.events[mark:]
                   if e["kind"] in ("rollback", "replay", "rebuild_lists")]}
    out["replayed"] = _compare(sim, family)
    out["crossing"] = {
        "row_before": before, "row_after": row_of(tag),
        "moved_over_skin": float(np.linalg.norm(end - start) / skin),
        "ids_conserved": bool(np.array_equal(
            np.sort(np.asarray(sim.state.m)), ids))}

    out["lowering"] = _lowering(sim, family)
    events = sink.events
    out["events"] = {
        "engine_lists": [e["engine"]["lists"] for e in events
                         if e["kind"] == "reconfigure"],
        "rebuilds": [e["reason"] for e in events
                     if e["kind"] == "rebuild_lists"],
        "rebuild_fields": sorted(next(
            e for e in events if e["kind"] == "rebuild_lists")),
        "layout_age": [e["layout_age_steps"] for e in events
                       if e["kind"] == "exchange" and e["stage"] == "sph"],
        "shard_trips": [e["trips"] for e in events
                        if e["kind"] == "exchange"],
    }
    return out


CASE_FIXTURE = "run"


@pytest.fixture(scope="module")
def run(request):
    from conftest import run_mesh_subprocess

    family = request.param
    proc = run_mesh_subprocess(RUNNER.format(tests=TESTS, family=family))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("MESH-LISTS-RESULT ")]
    assert lines, proc.stderr[-3000:]
    return family, json.loads(lines[-1].split(" ", 1)[1])


def test_driver_walks_lists_on_the_mesh(run):
    family, out = run
    assert out["eligible"] and out["use_lists"]
    ev = out["events"]
    assert ev["engine_lists"] and all(ev["engine_lists"])
    assert ev["rebuilds"][0] == "first" and "rollback" in ev["rebuilds"]
    for field in ("age_steps", "attempts", "chunks_live", "reason",
                  "runs_live", "slot_cap", "slot_need", "slots_cap",
                  "slots_live"):
        assert field in ev["rebuild_fields"]
    # the layout ages with the list and starts again at a rebuild
    # a window of two on the list, then the replay on the rollback's
    assert ev["layout_age"] == [1, 0, 1]
    assert set(ev["shard_trips"]) == {0}


@pytest.mark.parametrize("point", ["crossed", "replayed"])
def test_list_stage_equals_streamed_stage(run, point):
    family, out = run
    got = out[point]
    assert got["list_ok"] == [1] * P
    assert got["nc_mismatch"] == 0 and got["nc_mean"] > 40
    assert max(got["rel"].values()) < FIELD_RTOL, got["rel"]


def test_slab_crossing_is_served_then_owned(run):
    family, out = run
    got, cross = out["crossed"], out["crossing"]
    S = SIDE ** 3 // P
    # the list was built with the row the last of slab 0; it stepped over
    # the boundary well inside the skin, and the frozen layout serves it...
    assert cross["row_before"] == S - 1
    assert 0 < cross["moved_over_skin"] < 0.4
    assert got["list_ok"] == [1] * P and got["nc_mismatch"] == 0
    # ...until the rebuild hands it to the slab its key now lies in
    assert cross["row_after"] == S
    assert cross["ids_conserved"]


def test_kick_past_the_skin_is_replayed(run):
    family, out = run
    kicked = out["kicked"]
    assert kicked["list_ok"] == [0] * P
    kinds = [k for k, _ in kicked["events"]]
    assert kinds[:2] == ["rollback", "rebuild_lists"] and "replay" in kinds
    assert ("rollback", "list-expiry") in [tuple(e) for e in kicked["events"]]


def test_lowered_list_step(run):
    family, out = run
    low = out["lowering"]
    assert low["scopes_left"] == [] and low["sort_ops"] == 0
    assert low["ppermute"] == (P - 1) * SERVES[family]
    assert low["all_reduce"] == ALL_REDUCES[family]
    assert low["all_gather"] == 1 and low["all_to_all"] == 0
    assert low["audit"] == []
