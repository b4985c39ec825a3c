"""The cooling collapse on the mesh through the normal path: the tier-1 case of
the cell ``evrard-cooling-4m-x4.steady``.

``Simulation(prop="std-cooling", num_devices=4)`` on a small ``evrard-cooling``
sphere for two 4-step check windows, as the cell drives it on the chip, in a
fresh process on a virtual CPU mesh (conftest.run_mesh_subprocess): ONE
jitted mesh step holding the std pair ops under ``shard_map`` with their
halo, the sharded tree solve (psum upsweep, LET, the MAC-sized near-field
serve) and the chemistry's seven fields as an aux state through the global
sort, the cooling limiter's cross-shard min and the radiated-energy counter's
cross-shard sum. A file of its own, so that ``--dist loadfile`` gives it a
worker (tests/mesh_gravity_case.py is the VE twin).

Held here:

- the trajectory, the chemistry (row by row, matched by a label that rides
  every permutation) and ``e_cool`` against the ONE-DEVICE Simulation after
  the same steps;
- the live mesh state's next step (benchmarks/check_collapse_step.py's
  ``system_step`` under the mesh's own configuration: the std force stage,
  the tree solve's part, the cooling source, the step's dt) against the
  coupled plain reference benchmarks/reference_collapse_step.py with every
  particle a target, under the limits benchmarks/configs/
  evrard-cooling-4m-x4.json states, with both of that comparison's controls;
- the chemistry's row alignment after the mesh's sorts, with PR 38's
  misaligned-row control (the reference handed the chemistry in the order it
  was seeded in must fail ``cooling_fraction_abs_max``), and
  benchmarks/check_collapse_mesh.py's shuffled probe;
- no ``retrace`` once the first step has compiled; the energy drift less the
  radiated counter; the ``exchange`` events of stage ``sort`` (schema v19).

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
ROOT = os.path.dirname(TESTS)
BENCH = os.path.join(ROOT, "benchmarks")
for p in (TESTS, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = os.path.join(BENCH, "configs", "evrard-cooling-4m-x4.json")
CELL = "evrard-cooling-4m-x4.steady"
#: 7,248 particles, 1,812 a slab: the first size at which the next step's
#: targets hold rows of the core (rho > 3: 20 of them) and the near field's
#: sized caps stay under the slab for one distance (1812, 1792, 1812); the
#: driven run takes 50 s interpreted, the one-device run 20 s (16: 2,160
#: particles, 35 s, no core row, full-slab caps)
SIDE = 24
STEPS = 8
SEED = 4200000042

#: Mesh against one device after the same steps. The same pairs and nodes
#: summed in another order (psum of per-slab leaf payloads, slab-local
#: blocks, per-slab partial sums of every ledger reduction): float32
#: rounding, with a MAC-marginal node free to flip. Read (PR 42, relative,
#: the worst of eight steps): dt 0, etot 7.0e-7, ecin 2.2e-7 (1.0e-6 at
#: side 16), eint 1.5e-7, egrav 6.5e-7; tests/mesh_gravity_case.py reads 0 /
#: 7.5e-7 / 9.7e-7 / 1.5e-7 / 6.9e-7 for the VE twin and holds the same
TRAJECTORY_RTOL = {"dt": 1e-6, "etot": 1e-5, "ecin": 1e-5, "eint": 1e-5,
                   "egrav": 1e-5}
#: the counter is sum(m du_cool) over all rows, on the mesh a sum of four
#: slab sums, and du_cool follows a rho that differs by float32 rounding.
#: Read 1.4e-8 to 3.0e-8 a step: held thirty times over that, a hundred
#: thousand times under a slab lost from the sum
E_COOL_RTOL = 1e-6
#: chemistry row by row (matched by label): the network's fractions after
#: eight steps of a rho and u that differ by float32 rounding between the
#: runs. Absolute, over fractions of order 0.01-0.76; read 1.2e-7 (``e``;
#: an ulp of 0.87) and 0 (``metal``, which no mode evolves). The
#: configuration's ``cooling_fraction_abs_max`` (5e-6) is the limit a row
#: astray must break (it reads 0.15 to 0.22)
CHEM_ATOL = 1e-6
#: (etot - e_cool)'s change over the steps, mesh against one device, over
#: |etot|: both read 2.040e-3 at this size (the softened potential follows h
#: while 7,248 particles relax; the cell's 1e-3 needs 17k), the same to
#: 1.0e-7
BALANCE_AS_ONE_DEVICE = 2e-6

RUNNER = """
    import json, sys
    sys.path[:0] = [{bench!r}, {tests!r}]
    import numpy as np
    import jax.numpy as jnp

    import check_collapse_mesh as ccm
    import check_collapse_step as ccs
    import check_cooling
    from test_collapse_cooling_reference import (
        label_of, labelled, seeded_chem)
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.physics.cooling import ChemistryData
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    with open({config!r}) as f:
        config = json.load(f)
    g = config["guarantees"]
    state, box, const, m0 = labelled({side})
    n4 = (state.n // 4) * 4
    label0 = (np.arange(state.n) / state.n)[:n4]
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) >= 1 else a, state)
    chem = ChemistryData(**{{k: jnp.asarray(v, jnp.float32)
                            for k, v in seeded_chem(label0).items()}})
    sink = MemorySink()
    sim = Simulation(state, box, const, prop="std-cooling", theta=0.5,
                     num_devices=4, check_every=4, backend="pallas",
                     chem=chem, obs_spec=make_observable_spec("evrard-cooling"),
                     science_rows=True, telemetry=Telemetry(sinks=[sink]),
                     workload="evrard-cooling")
    for _ in range({steps}):
        sim.step()
    sim.flush()
    rows = sim.drain_science()
    events = list(sink.events)
    label = label_of(sim, m0)
    out = dict(
        particles=int(sim.state.n), iteration=int(sim.iteration),
        rows=[{{k: r[k] for k in ("it", "dt", "etot", "ecin", "eint",
                                  "egrav", "e_cool")}} for r in rows],
        e_cool=sim.e_cool, energy_drift=sim.energy_drift,
        label=label.tolist(),
        chem={{k: np.asarray(getattr(sim.chem, k), np.float64).tolist()
              for k in check_cooling.CHEM_FIELDS}},
        chem_sharding=str(sim.chem.hi.sharding.spec),
        engine=sim._engine_facts(), halo=sim._halo_info["mode"],
        grav_cells=list(sim._grav_cells),
        kinds={{k: sum(1 for e in events if e["kind"] == k)
               for k in ("reconfigure", "rollback", "replay")}},
        retraces=[e["it"] for e in events if e["kind"] == "retrace"],
        sort_events=[{{k: e.get(k) for k in ("it", "steps", "mode", "rows",
                                            "shipped_rows", "migrant_rows")}}
                     for e in events
                     if e["kind"] == "exchange" and e.get("stage") == "sort"],
        stages=sorted({{e.get("stage") for e in events
                       if e["kind"] == "exchange"}}),
        e_cool_by_iteration={{str(k): v for k, v in
                             ccm.e_cool_by_iteration(events).items()}})
    # the next step of the live mesh state against the coupled reference,
    # every particle a target
    step = ccs.system_step(sim, const)
    result = ccs.compare(step, const, config["cooling"], {seed}, None)
    out["step"] = dict(result, judged=list(ccs.judge(result, g)))
    # PR 38's misaligned-row control: the reference handed the chemistry in
    # the order it was SEEDED in
    stale = ccs.compare(step, const, config["cooling"], {seed}, None,
                        chem_for_reference=seeded_chem(label0))
    out["stale"] = dict(fractions=stale["fractions"], du=stale["du"],
                        judged=list(ccs.judge(stale, g)))
    out["aligned_metal"] = float(np.abs(
        np.asarray(sim.chem.metal, np.float64)
        - seeded_chem(label)["metal"]).max())
    out["probe"] = ccm.alignment_probe(sim, {seed})
    print("MESH-COOLING-RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh_run():
    from conftest import run_mesh_subprocess

    out = run_mesh_subprocess(RUNNER.format(
        bench=BENCH, tests=TESTS, config=CONFIG, side=SIDE, steps=STEPS,
        seed=SEED))
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("MESH-COOLING-RESULT ")]
    assert lines, out.stderr[-3000:]
    return json.loads(lines[-1].split(" ", 1)[1])


@pytest.fixture(scope="module")
def one_device():
    """The same labelled particles and seeded chemistry on one device for
    the same steps (the portable XLA engine: the same sums as the
    interpreted Mosaic one in a third of its time on the CPU)."""
    import jax
    import jax.numpy as jnp

    import check_cooling
    from test_collapse_cooling_reference import (
        label_of, labelled, seeded_chem)

    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.physics.cooling import ChemistryData
    from sphexa_tpu.simulation import Simulation

    state, box, const, m0 = labelled(SIDE)
    n4 = (state.n // 4) * 4
    label0 = (np.arange(state.n) / state.n)[:n4]
    state = jax.tree.map(
        lambda a: a[:n4] if getattr(a, "ndim", 0) >= 1 else a, state)
    chem = ChemistryData(**{k: jnp.asarray(v, jnp.float32)
                            for k, v in seeded_chem(label0).items()})
    sim = Simulation(state, box, const, prop="std-cooling", theta=0.5,
                     check_every=4, backend="xla", chem=chem,
                     obs_spec=make_observable_spec("evrard-cooling"),
                     science_rows=True, workload="evrard-cooling")
    for _ in range(STEPS):
        sim.step()
    sim.flush()
    return {"rows": sim.drain_science(), "e_cool": sim.e_cool,
            "label": label_of(sim, m0),
            "chem": {k: np.asarray(getattr(sim.chem, k), np.float64)
                     for k in check_cooling.CHEM_FIELDS}}


def test_cell_and_metrics_are_declared(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evrard-cooling-4m-x4", "steady", 4)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"] == ["side", "ranks"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert (config["prop"], config["side"], config["devices"],
            config["particles"], config["theta"]) == (
        "std-cooling", 200, 4, 4189076, 0.5)
    assert config["init"].split("+")[0] == "evrard-cooling"
    listed = [m["name"] for m in bench["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]]
    assert {"sort_migrant_share", "sort_aux_ms_step", "gravity_ms_step",
            "grav_exchange_ms_step", "halo_ms_step", "cooling_ms_step",
            "cooling_radiated_share", "grav_slab_imbalance"} <= set(listed)
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    rates = next(m for m in bench["end_to_end"]
                 if m["name"] == "updates_per_s_chip")
    assert CELL in rates["workloads"]
    # at most half of a benchmark's cells may ask for four chips (three of
    # eleven with this one)
    assert (2 * sum(w["chips"] == 4 for w in bench["workloads"])
            <= len(bench["workloads"]))


def test_configuration_states_the_one_chip_cells_widths(config):
    """No width is cut: the case's constants, the cooler's units and table
    and the assumptions are ``evrard-cooling-1m.json``'s to the letter; the
    mesh limits are ``evrard-ve-4m-x4.json``'s."""
    with open(os.path.join(BENCH, "configs", "evrard-cooling-1m.json")) as f:
        one = json.load(f)
    with open(os.path.join(BENCH, "configs", "evrard-ve-4m-x4.json")) as f:
        ve = json.load(f)
    for block in ("evrard", "cooling", "assumed"):
        assert config[block] == one[block], block
    g = config["guarantees"]
    for key in ("cooling_rel_rms_max", "cooling_rel_max",
                "cooling_fraction_abs_max", "cooling_dt_rel_max",
                "cooling_refuse_differenced", "forces_rel_max",
                "energy_drift_max", "state_dtype", "nc_band"):
        assert g[key] == one["guarantees"][key], key
    for key in ("gravity_rel_rms_max", "gravity_rel_p99_max",
                "gravity_direct_targets", "device_balance_max",
                "halo_trips_max"):
        assert g[key] == ve["guarantees"][key], key
    assert (config["side"], config["particles"], config["ranks"]) == (
        ve["side"], ve["particles"], ve["ranks"])
    assert set(config["reduced_why"]) == {"side", "ranks"}
    assert "time limit" in config["memory_why"]


def test_one_mesh_step_holds_all_three(mesh_run):
    r = mesh_run
    assert r["iteration"] == STEPS and r["particles"] % 4 == 0
    # (since PR 48 a mesh under self-gravity walks pair lists too)
    assert r["engine"]["backend"] == "pallas" and r["engine"]["lists"]
    assert r["engine"]["gravity"]["use_pallas"]
    # the SPH halo and the near field are the sized sparse serves
    assert r["halo"] == "sparse" and len(r["grav_cells"]) == 3
    assert set(r["stages"]) == {"sph", "gravity", "sort"}
    assert r["chem_sharding"] == "PartitionSpec('p',)"
    # the construction's configure and no other: no cap was undersized
    assert r["kinds"] == {"reconfigure": 1, "rollback": 0, "replay": 0}


def test_no_retrace_once_the_first_step_compiled(mesh_run):
    """The stepper commits ``chem`` to its slab sharding before the first
    call (parallel/mesh.py): an uncommitted aux would compile a second
    executable at step 2, which correct.py's ``retrace`` check refuses."""
    assert [it for it in mesh_run["retraces"] if it > 1] == []


@pytest.mark.parametrize("key", sorted(TRAJECTORY_RTOL))
def test_trajectory_matches_one_device(mesh_run, one_device, key):
    got = [row[key] for row in mesh_run["rows"]]
    want = [row[key] for row in one_device["rows"]]
    assert len(got) == len(want) == STEPS
    np.testing.assert_allclose(got, want, rtol=TRAJECTORY_RTOL[key])


def test_radiated_energy_counter_matches_one_device(mesh_run, one_device):
    got = [row["e_cool"] for row in mesh_run["rows"]]
    want = [row["e_cool"] for row in one_device["rows"]]
    assert want[-1] < 0.0
    np.testing.assert_allclose(got, want, rtol=E_COOL_RTOL)
    assert mesh_run["e_cool"] == pytest.approx(one_device["e_cool"],
                                               rel=E_COOL_RTOL)
    # the events carry the same counter, step by step
    by_it = {int(k): v for k, v in mesh_run["e_cool_by_iteration"].items()}
    assert sorted(by_it) == list(range(1, STEPS + 1))
    np.testing.assert_allclose([by_it[i][0] for i in sorted(by_it)], got,
                               rtol=1e-9, atol=1e-15)


def test_chem_matches_one_device_row_by_row(mesh_run, one_device, config):
    """Matched by the mass label, which rides every permutation on both
    sides: after eight sorts on the mesh every row's chemistry is the one
    the one-device run gives that particle."""
    order_m = np.argsort(mesh_run["label"])
    order_o = np.argsort(one_device["label"])
    np.testing.assert_allclose(np.asarray(mesh_run["label"])[order_m],
                               one_device["label"][order_o], atol=1e-5)
    # the rows are no longer in label order on the mesh
    assert np.any(np.diff(mesh_run["label"]) < 0)
    for k, want in one_device["chem"].items():
        got = np.asarray(mesh_run["chem"][k])[order_m]
        np.testing.assert_allclose(got, want[order_o], atol=CHEM_ATOL,
                                   err_msg=k)
    assert CHEM_ATOL < config["guarantees"]["cooling_fraction_abs_max"]


def test_live_mesh_step_is_the_coupled_references(mesh_run, config):
    """The std force stage, the tree solve's part and the cooling source of
    the live mesh state, with every particle a target (core and envelope
    alike), under the limits the configuration states; both controls of
    the comparison refused by a wide margin."""
    s = mesh_run["step"]
    assert s["finite"] and s["targets"] == s["particles"]
    within, refused = s["judged"]
    assert within, s
    assert refused, s
    f = config["guarantees"]["forces_rel_max"]
    assert s["hydro"]["rho_rel_max"] < f["rho"]
    assert s["hydro"]["acc_rel_rms"] < f["acc_rms"]
    assert s["hydro"]["du_rel_max"] < f["du"]
    assert s["gravity"][0] < config["guarantees"]["gravity_rel_rms_max"]
    assert s["gravity"][1] < config["guarantees"]["gravity_rel_p99_max"]
    assert s["du_without_cooling"][1] > 0.1
    assert s["acceleration_without_gravity"][0] > 0.1
    assert s["dt"]["limiter"] == s["dt"]["reference_limiter"]


def test_chem_is_row_aligned_after_the_mesh_sorts(mesh_run, config):
    limit = config["guarantees"]["cooling_fraction_abs_max"]
    # the metal fraction, which no mode evolves, is the seeded function of
    # the label of the row it sits on (the label comes back from a float32
    # mass to 6e-6)
    assert mesh_run["aligned_metal"] < limit
    assert mesh_run["step"]["fractions"] < limit
    # the shuffled probe: every row finds its particle, three in four of
    # them on another slab
    p = mesh_run["probe"]
    assert p["same_particles"] and p["aligned"] == 0.0
    assert 0.7 < p["moved_slab_share"] < 0.8


def test_misaligned_chem_is_refused(mesh_run, config):
    """PR 38's control on the mesh: an aux permuted by another order than
    the particles' must fail ``cooling_fraction_abs_max``."""
    limit = config["guarantees"]["cooling_fraction_abs_max"]
    stale = mesh_run["stale"]
    assert not stale["judged"][0]
    assert stale["fractions"] > 1e-3 > limit
    assert mesh_run["probe"]["misaligned"] > 1e-3 > limit


def test_energy_drift_less_the_radiated_counter(mesh_run, one_device):
    e0 = abs(one_device["rows"][0]["etot"])
    balance = lambda rows: ((rows[-1]["etot"] - rows[-1]["e_cool"])
                            - (rows[0]["etot"] - rows[0]["e_cool"])) / e0
    assert abs(balance(mesh_run["rows"]) - balance(one_device["rows"])) \
        < BALANCE_AS_ONE_DEVICE
    assert mesh_run["e_cool"] < 0.0


def test_sort_exchange_events(mesh_run):
    """Schema v19: one ``exchange`` event of stage ``sort`` a verified
    window, the rows sorted, what each device receives for the gather, and
    the rows that changed slab in the window's last step (from the IC
    nothing has moved a slab's width)."""
    ev = mesh_run["sort_events"]
    n = mesh_run["particles"]
    assert [e["it"] for e in ev] == [4, 8]
    for e in ev:
        assert (e["rows"], e["shipped_rows"], e["mode"], e["steps"]) == (
            n, 3 * (n // 4), "gspmd", 4)
        assert 0 <= e["migrant_rows"] <= 0.05 * n


def test_recorded_chip_check_under_the_stated_limits(config):
    """PR 42's chip run of benchmarks/check_collapse_mesh.py at the timed
    size (four v5e chips, 4,189,076 particles, iteration 26), its result as
    recorded, under the limits the configuration states: inside every one,
    every control refused. And the two things the chip taught the judging:
    the coupled step's targets are half of the core, where the tree's part
    reads several times the uniform sample's error (so they have the
    one-chip cell's limits, ``forces_gravity_rel_*``, not the mesh's
    ``gravity_rel_*``); and at iteration 26 the cooling limiter is under
    ``check_cooling.DT_LONG``, a dt at which the network's fractions move by
    more than the alignment limit in float32 (reported, not judged)."""
    import check_collapse_mesh as ccm
    import check_collapse_step as ccs

    with open(os.path.join(BENCH, "tests", "fixtures",
                           "evrard_cooling_4m_x4_steady.check.json")) as f:
        rec = json.load(f)
    g = config["guarantees"]
    assert rec["correct"] and rec["cell"] == CELL
    assert (rec["particles"], rec["iteration"], rec["targets"],
            rec["core_targets"]) == (4189076, 26, g["forces_targets"], 16)
    assert ccm.judge(rec, g) == (True, True)
    assert not ccs.judge(rec, g)[0]
    assert (rec["gravity"][0] > g["gravity_rel_rms_max"]
            > rec["mesh_gravity"]["rel_rms"])
    assert rec["gravity"][0] < g["forces_gravity_rel_rms_max"]
    long = rec["cooling"]["dt"]["long"]
    assert long["dt"] > rec["cooling"]["dt_cool"]["program"]
    assert long["fractions"] > g["cooling_fraction_abs_max"] \
        > rec["cooling"]["dt"]["step"]["fractions"]
    assert rec["dt"]["limiter"] == "cool"
    assert rec["alignment"]["aligned"] == 0.0
    assert rec["alignment"]["misaligned"] > 0.1
    assert rec["e_cool"]["iteration"] == 18
    assert rec["e_cool"]["rel"] < 1e-6 < ccm.E_COOL_REL_MAX
    assert all(m == 0 and n == 4189076 for m, n in rec["sort_migrants"])
