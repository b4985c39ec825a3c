"""Self-gravity on the mesh through the normal path, against the direct sum:
the driven run, the limits and the tests of ONE case. Not collected itself:
test_mesh_gravity_cell.py (``normal``), test_mesh_gravity_cell_tripped.py and
test_mesh_gravity_cell_bitmask.py each set ``CASE`` and import everything
here, so that every case is a file of its own and ``--dist loadfile`` may run
the three side by side (one file held them until PR 41: one worker's 752 s).

``Simulation(prop="ve", num_devices=4)`` on a small Evrard sphere for two
4-step check windows, as the cell ``evrard-ve-4m-x4.steady`` drives it on the
chip, one driven run per case in a fresh process on a virtual CPU mesh
(conftest.run_mesh_subprocess), shared by the tests of the case's module:

- ``normal``: every choice the program's own (at this size the per-block
  sort compaction, LET per shard, the MAC-sized sparse near-field serve);
- ``tripped``: the same with the near-field caps undersized once, so that
  the escape sentinel fires inside the first deferred window, the driver
  rolls back, regrows the margin and replays;
- ``bitmask``: the solver shape the program picks at the cell's real size
  (``gravity_tuning`` over 500k: blocks of 256, supers of 8 classified
  against the LET list, the Mosaic bitmask compaction), steered here by
  answering "big" for it.

Then the live state's accelerations, evaluated by ``compute_gravity`` under
the run's own resolved configuration (on a mesh: the step's sharded stage,
``GravityConfig.on_mesh``), against
benchmarks/reference.py's float32 direct sum at seeded targets (the
comparison benchmarks/check_gravity_mesh.py makes on the chip at 4.19M) and
against the one-chip Simulation after the same steps; the trajectory against
the one-chip run; the energy drift; the sentinel's bookkeeping.

``backend="pallas"`` is this file's steering: on the CPU ``auto`` is the XLA
path, which has no sharded stage. Kernels run in interpret mode; nothing here
is a speed.
"""

import json
import os
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(TESTS), "benchmarks")
for p in (TESTS, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

#: ``normal`` at 32 (17,196 particles): the first size whose start-up
#: relaxation keeps the drift under 1e-3 (24: 2.0e-3), which
#: test_energy_drift_over_the_windows holds it to. ``tripped`` and
#: ``bitmask`` pin the sentinel's bookkeeping and the solver shape, which
#: no size decides: 24 (7,248), under LIMITS read at that size
SIDES = {"normal": 32, "tripped": 24, "bitmask": 24}
STEPS = 8
SEED = 2900000029
TARGETS = 256
#: near-field margin of the ``tripped`` case: caps of 0.75 x need escape,
#: one regrowth (x 1.5) holds the replay
TRIP_MARGIN = 0.75

#: Limits of mesh solve vs direct sum (relative error of the acceleration
#: vector over the targets: rms, p99), per case, read at the case's size.
#: What the tree leaves at theta 0.5 with quadrupoles is MAC truncation,
#: not rounding. At 32, blocks of 64 (``normal``) read rms 1.13e-4 / p99
#: 4.1e-4 (the chip at 1.1M on one device: 6.7-8.1e-5 / 1.6-2.7e-4); the
#: same state solved at theta 0.7 reads 4.3e-4 / 1.37e-3, and against a
#: direct sum over bf16-rounded positions 1.10e-3 / 2.4e-3. At 24 (read
#: in PR 41): blocks of 64 after the trip and the regrowth (``tripped``)
#: 9.65e-5 / 3.02e-4, theta 0.7 5.05e-4 / 2.08e-3, bf16 1.15e-3 / 2.26e-3;
#: blocks of 256 with supers (``bitmask``) 5.56e-5 / 1.91e-4, theta 0.7
#: 3.86e-4 / 1.31e-3, bf16 1.15e-3 / 2.28e-3. Each limit lies midway (in
#: ratio) between the reading it admits and the nearest it refuses:
#: 1.8-1.95 x from both at 32, 2.3-2.7 x at 24. (The chip script's 8e-4 /
#: 2.5e-3 are the one-chip cell's guarantees and admit theta 0.7; they
#: refuse PR 21's bf16-pass compaction, which read 2.55e-1.)
LIMITS = {"normal": (2.2e-4, 7.5e-4), "tripped": (2.2e-4, 7.9e-4),
          "bitmask": (1.45e-4, 5.0e-4)}
#: Mesh against one chip after the same steps: the same pairs and nodes
#: summed in another order (psum of per-slab leaf payloads, slab-local
#: blocks), so MAC-marginal nodes may flip. Trajectory (dt, etot, ecin,
#: eint, egrav per verified step): read 0 / 7.5e-7 / 9.7e-7 / 1.5e-7 /
#: 6.9e-7. Accelerations at the targets' particles: two tree solves, each
#: within LIMITS of the direct sum, differ by at most the sum of both.
TRAJECTORY_RTOL = {"dt": 1e-6, "etot": 1e-5, "ecin": 1e-5, "eint": 1e-5,
                   "egrav": 1e-5}
ONE_CHIP_LIMITS = (3e-4, 1e-3)
#: the cell's guarantee, held where the size allows it (32). At 24 the
#: start-up relaxation alone reads 2.04e-3 on one chip and on the mesh
#: alike: there the run is held to the one-chip run's own drift
DRIFT_MAX = 1e-3
DRIFT_AS_ONE_CHIP = 2e-5

RUNNER = """
    import json, os, sys
    sys.path[:0] = [{bench!r}]
    import numpy as np
    import jax.numpy as jnp

    import check_gravity_mesh as cgm
    import reference
    from sphexa_tpu.init import init_evrard
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    case = {case!r}
    if case == "bitmask":
        import sphexa_tpu.gravity.traversal as tr
        tuning = tr.gravity_tuning
        tr.gravity_tuning = (lambda n, use_pallas, telemetry=None:
                             tuning(max(n, 500_000), use_pallas))
    state, box, const = init_evrard({side})
    n4 = (state.n // 4) * 4
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) >= 1 else a, state)
    sink = MemorySink()
    sim = Simulation(state, box, const, prop="ve", theta=0.5, num_devices=4,
                     check_every=4, backend="pallas",
                     obs_spec=make_observable_spec("evrard"),
                     science_rows=True, telemetry=Telemetry(sinks=[sink]),
                     workload="evrard")
    sized = list(sim._grav_cells)
    # the SPH halo's caps as the run sized them over its own mesh, against
    # the same sizing asked afresh (host copies, a mesh of its own)
    from sphexa_tpu.parallel.sizing import device_sparse_halo
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    s0 = sim.state
    gbox = make_global_box(s0.x, s0.y, s0.z, sim.box)
    keys0 = compute_sfc_keys(s0.x, s0.y, s0.z, gbox, curve=sim.curve)
    h0, pad = s0.h, 0.0
    if sim._use_lists:
        # on lists the halo is sized for the windows their rebuild
        # negotiates over: the relaxed h, the skin's pad
        # (make_propagator_config)
        h0 = s0.h * jnp.float32(sim._h_relax)
        pad = jnp.float32(sim._cfg.list_skin_rel * 2.0
                          * float(jnp.max(s0.h)) * sim._h_relax)
    halo_caps = dict(
        run=list(sim._halo_info["caps"]),
        fresh=list(device_sparse_halo(
            *(np.asarray(a) for a in (s0.x, s0.y, s0.z, h0, keys0)), gbox,
            sim._cfg.nbr, P=4, margin=sim._halo_margin,
            radius_pad=pad)[0]))
    if case == "tripped":
        sim._grav_halo_margin = {trip_margin}
        sim._configure(reason="test-undersize")
    started = list(sim._grav_cells)
    mark = len(sink.events)
    for _ in range({steps}):
        sim.step()
    sim.flush()
    rows = sim.drain_science()
    # the mesh solve vs the direct sum, with both controls: the same state
    # solved at a looser MAC, and the reference computed one precision
    # down, must each read over the limits
    out = cgm.compare(sim, const, {seed}, {targets}, theta_control=0.7)
    # the benchmark's own check of a gravity cell, as a driver run makes it
    # on this cell's live mesh state, under the cell's stated limits
    import correct
    with open(os.path.join({bench!r}, "configs",
                           "evrard-ve-4m-x4.json")) as f:
        stated = json.load(f)["guarantees"]
    said, rec = [], dict(particles=out["particles"])
    correct._gravity_check(lambda ok, what: said.append([bool(ok), what]),
                           rec, sim, const, stated, {seed})
    out["harness"] = dict(said=said, rms=rec["gravity_rel_rms"],
                          p99=rec["gravity_rel_p99"],
                          limits=[stated["gravity_rel_rms_max"],
                                  stated["gravity_rel_p99_max"]])
    # the stage's three list fills on the live state, and the same counted
    # in numpy over every block and superblock each slab forms (geometry
    # from a one-device upsweep of the same rows); and the verified
    # windows' events, which carry the steps' fills
    import dataclasses
    sys.path[:0] = [{tests!r}]
    from gravity_counts import counted_chunk_live, counted_fills
    from sphexa_tpu.gravity import traversal as tv
    s1 = sim.state
    gb = make_global_box(s1.x, s1.y, s1.z, sim.box)
    k1 = compute_sfc_keys(s1.x, s1.y, s1.z, gb, curve=sim.curve)
    o1 = jnp.argsort(k1)
    srt = [a[o1] for a in (s1.x, s1.y, s1.z, s1.m, s1.h)] + [k1[o1]]
    g = sim._cfg.gravity
    meta = sim._cfg.grav_meta
    diag = jax.device_get(tv.compute_gravity(
        *srt, gb, sim._gtree, meta, dataclasses.replace(g, G=const.g))[-1])
    one = [jnp.asarray(np.asarray(a)) for a in srt]
    fill_keys = ("cand_fill", "m2p_fill", "p2p_fill")
    out["fills"] = dict(
        stage=[float(diag[k]) for k in fill_keys],
        counted=[float(v) for v in counted_fills(
            *one[:4], one[5], gb, sim._gtree, meta, g, shards=4)],
        windows=[[e.get(k) for k in fill_keys]
                 for e in sink.events[mark:] if e["kind"] == "window"])
    # likewise the compaction kernel's live chunks over the chunks its two
    # walks visit (the bitmask case alone runs the kernel)
    live_keys = ("prepass_chunk_live", "compact_chunk_live")
    out["chunk_live"] = dict(
        stage=[float(diag[k]) for k in live_keys],
        counted=[float(v) for v in counted_chunk_live(
            *one[:4], one[5], gb, sim._gtree, meta, g, shards=4)]
        if g.compaction == "bitmask" else [0.0, 0.0],
        windows=[[e.get(k) for k in live_keys]
                 for e in sink.events[mark:] if e["kind"] == "window"])
    # the near field's run axis: the same stage with the runs' slots at
    # the full p2p_cap (what a caller that sizes none runs), and with the
    # near field in the order it had before (the full-slab windowed serve:
    # every leaf localized on its own, the runs merged afterwards)
    solve = lambda cfg: [np.asarray(a) for a in tv.compute_gravity(
        *srt, gb, sim._gtree, meta, dataclasses.replace(cfg, G=const.g))[:3]]
    sized_acc = np.stack(solve(g), axis=1).astype(np.float64)
    full_acc = np.stack(solve(dataclasses.replace(g, p2p_run_cap=0)), axis=1)
    leaf_first = np.stack(solve(dataclasses.replace(
        g, on_mesh=g.on_mesh[:2] + ((),))), axis=1).astype(np.float64)
    rel = (np.linalg.norm(sized_acc - leaf_first, axis=1)
           / np.linalg.norm(leaf_first, axis=1))
    out["run_axis"] = dict(
        p2p_cap=g.p2p_cap, p2p_run_cap=g.p2p_run_cap,
        full_width_equal=bool((sized_acc == full_acc).all()),
        leaf_first_rel=[float(np.sqrt(np.mean(rel ** 2))), float(rel.max())],
        live=int(diag["gshard_runs"].max()),
        sph=[[e.get("run_slots"), e.get("live_runs_max")]
             for e in sink.events[mark:]
             if e["kind"] == "exchange" and e.get("stage") == "sph"],
        sph_slots=[sim._halo_info["run_slots"], sim._cfg.nbr.window ** 3])
    # the scopes of the step this run launched, from its lowered IR
    import io, re
    ss = sim.sim_state
    buf = io.StringIO()
    sim._stepper._jitted.lower(
        ss.particles, ss.box, sim._gtree, None).compiler_ir(
        dialect="stablehlo").operation.print(file=buf,
                                             enable_debug_info=True)
    paths = set(re.findall(r'loc\\("([^"]*sphexa/[^"]*)"', buf.getvalue()))
    first = lambda p: re.search(r"sphexa/([a-z0-9-]+)", p).group(1)
    out["scopes"] = dict(
        serve_under_gravity_exchange=sum(
            "sphexa/gravity-exchange/sphexa/halo-exchange/" in p
            for p in paths),
        psum_first_scopes=sorted({{first(p) for p in paths
                                  if p.endswith("/psum")}}),
        collective_first_scopes=sorted({{
            first(p) for p in paths
            if p.rsplit("/", 1)[-1] in ("ppermute", "all_gather",
                                        "all_to_all")}}))
    events = sink.events[mark:]
    out.update(
        rows=[{{k: r[k] for k in ("dt", "etot", "ecin", "eint", "egrav")}}
              for r in rows],
        sized=sized, started=started, ended=list(sim._grav_cells),
        halo_caps=halo_caps,
        trips=int(sim.telemetry.counters.get("grav_halo_trips", 0)),
        kinds={{k: sum(1 for e in events if e["kind"] == k)
               for k in ("reconfigure", "rollback", "replay", "retrace")}},
        gravity_exchange_events=[
            {{k: e[k] for k in ("mode", "shipped_rows", "rows", "occ",
                               "steps", "trips", "run_slots",
                               "live_runs_max")}}
            for e in events
            if e["kind"] == "exchange" and e.get("stage") == "gravity"],
        slab=out["particles"] // 4, iteration=int(sim.iteration))
    print("MESH-GRAVITY-RESULT " + json.dumps(out))
"""


#: conftest.pytest_generate_tests: the importing module's ``CASE`` is the
#: one parameter of this fixture (ids ``[normal]`` / ``[tripped]`` /
#: ``[bitmask]``, as when one module held the three)
CASE_FIXTURE = "mesh_run"


@pytest.fixture(scope="module")
def mesh_run(request):
    from conftest import run_mesh_subprocess

    code = RUNNER.format(bench=BENCH, tests=TESTS, case=request.param,
                         side=SIDES[request.param],
                         steps=STEPS, seed=SEED, targets=TARGETS,
                         trip_margin=TRIP_MARGIN)
    out = run_mesh_subprocess(code)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("MESH-GRAVITY-RESULT ")]
    assert lines, out.stderr[-3000:]
    return request.param, json.loads(lines[-1].split(" ", 1)[1])


@pytest.fixture(scope="module")
def one_chip(request):
    """The one-chip Simulation after the same steps: its verified rows, and
    its own tree solve on its live state (SFC-sorted positions and
    accelerations), as correct.py's gravity check evaluates it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from sphexa_tpu.gravity.traversal import compute_gravity
    from sphexa_tpu.init import init_evrard
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    from sphexa_tpu.simulation import Simulation

    state, box, const = init_evrard(SIDES[request.module.CASE])
    n4 = (state.n // 4) * 4
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) >= 1 else a, state)
    # the portable XLA engine: the same sums as the interpreted Mosaic one
    # (etot equal to the last printed digit over these steps) in a third
    # of its time on the CPU
    sim = Simulation(state, box, const, prop="ve", theta=0.5, check_every=4,
                     backend="xla",
                     obs_spec=make_observable_spec("evrard"),
                     science_rows=True, workload="evrard")
    for _ in range(STEPS):
        sim.step()
    sim.flush()
    rows = sim.drain_science()
    s = sim.state
    gbox = make_global_box(s.x, s.y, s.z, sim.box)
    keys = compute_sfc_keys(s.x, s.y, s.z, gbox, curve=sim.curve)
    order = jnp.argsort(keys)
    xs, ys, zs, ms, hs = (a[order] for a in (s.x, s.y, s.z, s.m, s.h))
    gcfg = dataclasses.replace(sim._cfg.gravity, G=const.g)
    out = compute_gravity(xs, ys, zs, ms, hs, keys[order], gbox, sim._gtree,
                          sim._cfg.grav_meta, gcfg)
    assert not sim._gravity_overflowed(jax.device_get(out[-1]))
    return {"rows": rows,
            "pos": np.stack([np.asarray(a) for a in (xs, ys, zs)], axis=1),
            "acc": np.stack([np.asarray(a) for a in out[:3]], axis=1)}


def test_mesh_solve_matches_direct_sum(mesh_run):
    case, r = mesh_run
    rms_max, p99_max = LIMITS[case]
    assert r["finite"] and r["targets"] == TARGETS
    assert r["within_caps"] and not r["window_blown"], r
    assert r["rel_rms"] < rms_max and r["rel_p99"] < p99_max, \
        (r["rel_rms"], r["rel_p99"])
    shape = ("bitmask", 8) if case == "bitmask" else ("sort", 0)
    assert (r["compaction"], r["super_factor"]) == shape
    assert 0 < r["let_max"] <= r["let_cap"]


def test_benchmark_gravity_check_runs_the_mesh_solve(mesh_run):
    """correct.py's ``_gravity_check`` calls ``compute_gravity`` on the
    globally sorted live state under ``sim._cfg.gravity``. On a mesh that
    config carries ``on_mesh`` and the call is the step's own stage (before,
    Mosaic kernels over sharded operands: NotImplementedError at lowering on
    the chip, so the cell stated no gravity limits). Held to the limits the
    cell's configuration states, which lie between this reading and the
    controls'."""
    case, r = mesh_run
    h = r["harness"]
    assert len(h["said"]) == 2 and all(ok for ok, _ in h["said"]), h["said"]
    assert "within caps" in h["said"][0][1]
    assert f"{TARGETS} seeded targets x {r['particles']} sources" \
        in h["said"][1][1]
    # the config's copy of the near field's caps is the stepper's, after
    # any regrowth (Simulation._configure_gravity sets both)
    assert r["grav_cells"] == r["ended"]
    # the same solve as the comparison above: the same readings
    np.testing.assert_allclose([h["rms"], h["p99"]],
                               [r["rel_rms"], r["rel_p99"]], rtol=1e-3)
    rms_max, p99_max = h["limits"]
    assert h["rms"] < rms_max and h["p99"] < p99_max
    for reading in ("theta_control", "bf16_ref"):
        *_, rms, p99 = r[reading]
        assert rms > rms_max or p99 > p99_max, (reading, rms, p99)


def test_list_fills_are_the_fullest_slabs_counts(mesh_run):
    """cand_fill / m2p_fill / p2p_fill of the sharded stage (pmax over
    shards) against numpy counts over the slabs' own blocks; and every
    verified window's event carries the steps' fills (schema v13)."""
    case, r = mesh_run
    f = r["fills"]
    # the stage's multipoles are a psum of per-slab sums: a node at the
    # MAC's edge may flip against the one-device geometry counted here
    np.testing.assert_allclose(f["stage"], f["counted"], rtol=2e-3,
                               atol=1e-7)
    assert (f["stage"][0] > 0) == (case == "bitmask")
    assert 0 < f["stage"][1] < 1 and 0 < f["stage"][2] < 1
    assert len(f["windows"]) >= 1
    for got in f["windows"]:
        np.testing.assert_allclose(got, f["stage"], rtol=0.1, atol=1e-7)


def test_chunk_live_shares_are_the_fullest_slabs_counts(mesh_run):
    """prepass_chunk_live / compact_chunk_live of the sharded stage (pmax
    over shards) against numpy counts over the slabs' own LET lists,
    superblocks and blocks; 0 where the solve runs no compaction kernel;
    and every verified window's event carries the steps' shares (schema
    v17)."""
    case, r = mesh_run
    f = r["chunk_live"]
    if case != "bitmask":
        assert f["stage"] == f["counted"] == [0.0, 0.0]
    else:
        # a node at the MAC's edge that flips (see above) moves one chunk
        # of a few hundred
        np.testing.assert_allclose(f["stage"], f["counted"], rtol=2e-2)
        # (a LET list of a few chunks has no dead one: the shares with
        # dead chunks in them are test_gravity.py's, on one device)
        assert 0 < f["stage"][0] <= 1 and 0 < f["stage"][1] <= 1
    assert len(f["windows"]) >= 1
    for got in f["windows"]:
        np.testing.assert_allclose(got, f["stage"], rtol=0.1, atol=1e-7)


def test_limits_refuse_looser_mac_and_lower_precision(mesh_run):
    case, r = mesh_run
    rms_max, p99_max = LIMITS[case]
    for reading in ("theta_control", "bf16_ref"):
        *_, rms, p99 = r[reading]
        assert rms > rms_max and p99 > p99_max, (reading, rms, p99)


def test_mesh_matches_one_chip_after_the_same_steps(mesh_run, one_chip):
    case, r = mesh_run
    assert r["iteration"] == STEPS
    assert len(r["rows"]) == len(one_chip["rows"]) == STEPS
    for key, rtol in TRAJECTORY_RTOL.items():
        np.testing.assert_allclose(
            [row[key] for row in r["rows"]],
            [row[key] for row in one_chip["rows"]], rtol=rtol,
            err_msg=f"{case}: {key}")
    # the targets' particles in the one-chip state, by position (the two
    # runs' SFC orders may differ where keys tie)
    pos = np.asarray(r["target_pos"], np.float64).T
    d2 = ((pos[:, None, :] - one_chip["pos"][None, :, :]) ** 2).sum(-1)
    twin = d2.argmin(axis=1)
    assert float(np.sqrt(d2[np.arange(len(twin)), twin]).max()) < 1e-5
    got = np.asarray(r["target_acc"], np.float64).T
    ref = one_chip["acc"][twin].astype(np.float64)
    rel = np.linalg.norm(got - ref, axis=1) / np.linalg.norm(ref, axis=1)
    rms, p99 = np.sqrt(np.mean(rel ** 2)), np.percentile(rel, 99)
    assert rms < ONE_CHIP_LIMITS[0] and p99 < ONE_CHIP_LIMITS[1], (rms, p99)


def test_energy_drift_over_the_windows(mesh_run, one_chip):
    case, r = mesh_run

    def drift(rows):
        e0 = rows[0]["etot"]
        return max(abs(row["etot"] - e0) for row in rows) / abs(e0)

    got = drift(r["rows"])
    if SIDES[case] == 32:
        assert got < DRIFT_MAX, got
    # through a trip and a replay, and under the other solver shape, too
    assert abs(got - drift(one_chip["rows"])) < DRIFT_AS_ONE_CHIP, got


def test_sentinel_bookkeeping_and_exchange_events(mesh_run):
    case, r = mesh_run
    slab = r["slab"]
    assert len(r["sized"]) == 3 and max(r["sized"]) <= slab
    # the program's own MAC-need sizing is partial at this size: the serve
    # ships less than full peer slabs, or the case proves nothing
    assert sum(r["sized"]) < 3 * slab, (r["sized"], slab)
    # the SPH halo sized on the mesh the way the step takes its needs: the
    # same caps as the one-device form, and not the 256-row floor a sizing
    # that read zero would give (the cell's second chip run, PR 29)
    assert r["halo_caps"]["run"] == r["halo_caps"]["fresh"]
    assert min(r["halo_caps"]["run"]) > 256
    ev = r["gravity_exchange_events"]
    assert ev and all(e["mode"] == "sparse" for e in ev)
    last = ev[-1]
    assert last["shipped_rows"] == sum(r["ended"])
    assert len(last["rows"]) == 4 and max(last["occ"]) <= 1.0
    if case == "tripped":
        assert max(a / b for a, b in zip(r["started"], r["sized"])) < 1.0
        assert r["trips"] == 1 and r["kinds"]["rollback"] == 1, r["kinds"]
        assert r["kinds"]["replay"] == 1
        assert all(e >= s for e, s in zip(r["ended"], r["started"]))
        assert sum(r["ended"]) > sum(r["started"])
    else:
        assert r["trips"] == 0 and r["started"] == r["ended"] == r["sized"]
        assert r["kinds"]["rollback"] == 0 and r["kinds"]["replay"] == 0
        # one verified event per 4-step window
        assert [e["steps"] for e in ev] == [4, 4] and last["trips"] == 0


def test_merged_first_near_field_is_the_leaf_first_one(mesh_run):
    """The mesh's sparse near field merges a block's leaves into runs
    BEFORE the exchange and cuts the runs to their sized high-water. Same
    work: bitwise the solve over the full ``p2p_cap`` slots; and, against
    the order the near field had before (leaves localized one by one,
    merged afterwards: what the full-slab windowed serve still runs), the
    same pairs summed in other chunks, f32 rounding apart. The direct-sum
    readings of the tests above are the merged-first solve's."""
    case, r = mesh_run
    a = r["run_axis"]
    assert a["full_width_equal"]
    rms, worst = a["leaf_first_rel"]
    assert rms < 1e-6 and worst < 1e-5, a["leaf_first_rel"]


def test_run_axis_is_sized_and_reported(mesh_run):
    """``p2p_run_cap`` and ``halo_runs``: sized under the full width, over
    the live runs of every verified step (no trip of theirs in any case:
    the ``tripped`` case undersizes the row caps), and on the ``exchange``
    events of both stages (schema v14)."""
    case, r = mesh_run
    a = r["run_axis"]
    assert 0 < a["live"] <= a["p2p_run_cap"] < a["p2p_cap"]
    for e in r["gravity_exchange_events"]:
        assert e["run_slots"] == a["p2p_run_cap"]
        assert 0 < e["live_runs_max"] <= e["run_slots"]
    slots, w3 = a["sph_slots"]
    assert 0 < slots < w3 and a["sph"]
    for got_slots, live in a["sph"]:
        assert got_slots == slots and 0 < live <= slots


def test_gravity_exchange_scope_is_the_first_of_its_ops(mesh_run):
    """benchmarks/trace_reduce.py takes the FIRST ``sphexa/<phase>`` of an
    op's path: the near field's serve must carry ``gravity-exchange``
    before the exchange layer's own ``halo-exchange``, and the upsweep's
    psums ``gravity-exchange`` and not ``gravity-upsweep``."""
    _, r = mesh_run
    sc = r["scopes"]
    assert sc["serve_under_gravity_exchange"] > 0
    assert "gravity-exchange" in sc["psum_first_scopes"]
    assert "gravity-upsweep" not in sc["psum_first_scopes"]
    # both wires are told apart: the SPH halo keeps its own first scope
    assert {"halo-exchange", "gravity-exchange"} <= set(
        sc["collective_first_scopes"])


