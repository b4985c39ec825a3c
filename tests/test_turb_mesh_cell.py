"""Subsonic turbulence on the mesh through the normal path: the tier-1 case of
the cell ``turb-ve-8m-x4.steady`` (BASELINE.json config 4 as it is written).

``Simulation(prop="turb-ve", num_devices=4)`` on a small ``turbulence`` box
for two 4-step check windows from the IC, as the cell drives it on the chip,
in a fresh process on a virtual CPU mesh (conftest.run_mesh_subprocess): ONE
jitted mesh step holding the global sort, the five streamed VE pair ops
under ``shard_map`` with their five serve rounds of a periodic halo, and the
OU stirring as plain XLA over the sharded rows with the turb state (phases,
PRNG key, mode tables) replicated. A file of its own, so that ``--dist
loadfile`` gives it a worker.

Held here:

- the trajectory and the Mach-RMS rows against the ONE-DEVICE Simulation
  after the same steps, the OU phases and the PRNG key EQUAL TO THE BIT
  (the state is replicated and every step's dt is the same float32 on both
  sides);
- what benchmarks/check_turb_mesh.py holds the chip run to, on this live
  mesh state with every particle a target: the step's own VE force stage
  against benchmarks/reference_sph_ve.py under ``forces_ve_rel_max``, the
  stirring against benchmarks/reference_stirring.py under the ``stirring_*``
  limits, both lower-precision controls refused, and the OU comparison's own
  control (a record one step behind must be refused);
- no ``retrace`` once the first step has compiled;
- the configuration: config 4's widths as ``turb-ve-8m.json`` states them;
- the chip's recorded check re-judged under the limits that stand.

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

CONFIG = os.path.join(BENCH, "configs", "turb-ve-8m-x4.json")
#: the chip's two runs of the check: call c45b (seed 4500000221, judged on
#: the chip under rho 5e-6) and c45e (seed 4500000222, the committed rule)
RECORDED = [os.path.join(BENCH, "tests", "fixtures", name) for name in (
    "turb_ve_8m_x4_steady.check.json", "turb_ve_8m_x4_steady.check2.json")]
CELL = "turb-ve-8m-x4.steady"
SIDE = 12  # 1,728 particles, 432 a slab: every slab's halo crosses a face
STEPS = 8
SEED = 4500000046

#: Mesh against one device after the same steps: the same pairs summed in
#: another order (slab-local groups, served halo rows), float32 rounding.
#: dt is the 1.1 x ramp from ``minDt`` on both sides (equal to the bit, and
#: the OU phases with it); etot is u0 = 1000 to its last bits; ecin and the
#: Mach RMS grow from rest under the jittered lattice's pressure noise and
#: the stirring, and read 2e-7 to 6e-7 apart
TRAJECTORY_RTOL = {"dt": 0.0, "etot": 1e-6, "ecin": 1e-5, "eint": 1e-6,
                   "extra": 1e-5}

RUNNER = """
    import json, sys
    sys.path[:0] = [{bench!r}, {tests!r}]
    import numpy as np

    import check_stirring
    import check_turb_mesh as ctm
    from test_turb_mesh_cell import simulate

    sim, const, sink = simulate(num_devices=4, backend="pallas")
    for _ in range({steps}):
        sim.step()
    sim.flush()
    rows = sim.drain_science()
    events = list(sink.events)
    stage = ctm.system_forces(sim)
    targets = np.arange(int(sim.state.n))
    out = dict(
        particles=int(sim.state.n), iteration=int(sim.iteration),
        rows=[{{k: r[k] for k in ("it", "dt", "etot", "ecin", "eint",
                                  "extra")}} for r in rows],
        engine=sim._engine_facts(), halo=sim._halo_info["mode"],
        devices=int(sim._mesh.size),
        kinds={{k: sum(1 for e in events if e["kind"] == k)
               for k in ("reconfigure", "rollback", "replay")}},
        retraces=[e["it"] for e in events if e["kind"] == "retrace"],
        stages=sorted({{e.get("stage") for e in events
                       if e["kind"] == "exchange"}}),
        turb_replicated=bool(all(
            a.sharding.is_fully_replicated
            for a in (sim.turb_state.phases, sim.turb_state.key,
                      sim.turb_state.modes, sim.turb_state.amplitudes))),
        x_sharding=str(sim.state.x.sharding.spec),
        halo_trips=int(sim.telemetry.counters.get("halo_trips", 0)),
        energy_drift=sim.energy_drift, correct=True,
        ou_state=ctm.ou_record(sim, events),
        forces=ctm.compare_forces(sim, const, targets, stage=stage),
        forces_bf16_control=ctm.compare_forces(
            sim, const, targets[::432], stage=stage,
            product_dtype="bfloat16"),
        stirring=check_stirring.compare(sim, {seed}, int(sim.state.n)))
    print("TURB-MESH-RESULT " + json.dumps(out))
"""


def simulate(**kw):
    """The cell's construction at ``SIDE`` (``run.build_simulation``'s
    keyword arguments); ``kw`` is the steering."""
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    state, box, const = make_initializer("turbulence")(SIDE)
    sink = MemorySink()
    sim = Simulation(state, box, const, prop="turb-ve", theta=0.5,
                     check_every=4,
                     obs_spec=make_observable_spec("turbulence"),
                     science_rows=True, telemetry=Telemetry(sinks=[sink]),
                     workload="turbulence", **kw)
    return sim, const, sink


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh_run():
    from conftest import run_mesh_subprocess

    out = run_mesh_subprocess(RUNNER.format(bench=BENCH, tests=TESTS,
                                            seed=SEED, steps=STEPS))
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("TURB-MESH-RESULT ")]
    assert lines, out.stderr[-3000:]
    return json.loads(lines[-1].split(" ", 1)[1])


@pytest.fixture(scope="module")
def one_device():
    """The same box on one device for the same steps (the portable XLA
    engine: the same sums as the interpreted Mosaic one in a fraction of its
    time on the CPU), recorded as the check's ``--one-chip`` form records
    the chip's."""
    import check_turb_mesh as ctm

    sim, _, sink = simulate(backend="xla")
    record = ctm.record_one_device(sim, sink, STEPS)
    return {"record": record, "rows": sim.drain_science()}


@pytest.fixture(scope="module")
def checked(mesh_run, one_device):
    """``check_turb_mesh.main``'s result for this run: the subprocess's
    comparisons with the OU state held against the one-device record."""
    import check_turb_mesh as ctm

    return dict(mesh_run, ou=ctm.against_one_chip(mesh_run["ou_state"],
                                                  one_device["record"]))


def test_cell_and_metrics_are_declared(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "turb-ve-8m-x4", "steady", 4)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"] == ["ranks"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    for word in ("turbulence_init.hpp", "hydro_turb", "turb_ve.hpp",
                 "run on 4 ranks"):
        assert word in entry["source"] and word in config["source"], word
    assert (config["init"], config["prop"], config["side"],
            config["devices"], config["particles"], config["theta"],
            config["ranks"]) == ("turbulence", "turb-ve", 200, 4, 8000000,
                                 0.5, 4)
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert {"stirring_ms_step", "halo_ms_step", "halo_rows_step",
            "halo_wire_ms_step", "halo_cover_ms_step", "halo_pack_ms_step",
            "halo_localize_ms_step", "halo_run_slots", "halo_run_fill",
            "cell_ranges_ms_step", "pairs_ms_step", "sort_nbr_ms_step",
            "steady_step_ms", "device_idle_share", "hbm_peak_gb"} <= listed
    # the turbulence state is a replicated table: no row gather carries it,
    # and there is neither gravity nor cooling nor a list in the step
    assert not {m for m in listed if m.startswith(
        ("grav", "cooling", "sort_aux", "sort_migrant", "list_", "dump"))}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    rates = next(m for m in bench["end_to_end"]
                 if m["name"] == "updates_per_s_chip")
    assert CELL in rates["workloads"]
    # at most half of a benchmark's cells may ask for four chips (four of
    # twelve with this one)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert (four, len(bench["workloads"])) == (4, 12)
    assert len(bench["configs"]) == 11


def test_configuration_states_config_4_as_the_one_chip_cell_does(config):
    """No width is cut: the stirring constants and the assumptions are
    ``turb-ve-8m.json``'s to the letter, the one cut is the rank count, and
    the mesh limits are ``sedov-std-8m-x4.json``'s."""
    with open(os.path.join(BENCH, "configs", "turb-ve-8m.json")) as f:
        one = json.load(f)
    with open(os.path.join(BENCH, "configs", "sedov-std-8m-x4.json")) as f:
        sedov = json.load(f)
    for block in ("stirring", "assumed"):
        assert config[block] == one[block], block
    g = config["guarantees"]
    for key in ("stirring_targets", "stirring_rel_rms_max",
                "stirring_rel_max", "energy_drift_max", "state_dtype",
                "nc_band", "theta"):
        assert g[key] == one["guarantees"][key], key
    for key in ("device_balance_max", "halo_trips_max"):
        assert g[key] == sedov["guarantees"][key], key
    assert (config["side"], config["particles"], config["init"],
            config["prop"]) == (one["side"], one["particles"], one["init"],
                                one["prop"])
    assert set(config["reduced_why"]) == {"ranks"}
    assert "time limit" in config["memory_why"]
    assert set(g["forces_ve_rel_max"]) == {"rho", "acc_rms", "acc_max", "du"}
    assert 16 <= g["forces_ve_targets"] <= 32
    for key in ("forces_ve_why", "stirring_why", "init_why"):
        assert len(g.get(key, config.get(key, ""))) > 200, key


def test_one_mesh_step_holds_the_stirring(mesh_run):
    r = mesh_run
    assert r["iteration"] == STEPS and r["particles"] == SIDE ** 3
    assert r["devices"] == 4 and r["x_sharding"] == "PartitionSpec('p',)"
    assert r["engine"]["backend"] == "pallas" and not r["engine"]["lists"]
    assert r["engine"]["gravity"] is None
    # the SPH halo is the sized sparse serve, and the only exchange
    assert r["halo"] == "sparse" and r["stages"] == ["sph"]
    assert r["turb_replicated"]
    # the construction's configure and no other: no cap was undersized
    assert r["kinds"] == {"reconfigure": 1, "rollback": 0, "replay": 0}
    assert r["halo_trips"] == 0 and abs(r["energy_drift"]) < 1e-3


def test_no_retrace_once_the_first_step_compiled(mesh_run):
    """The stepper commits the turb state replicated before the first call
    (parallel/mesh.py ``_place_aux_leaf``): an uncommitted aux would compile
    a second executable at step 2, which correct.py's ``retrace`` check
    refuses."""
    assert [it for it in mesh_run["retraces"] if it > 1] == []


@pytest.mark.parametrize("key", sorted(TRAJECTORY_RTOL))
def test_trajectory_matches_one_device(mesh_run, one_device, key):
    """``extra`` is the Mach-RMS row: a reduction over the slabs."""
    got = [row[key] for row in mesh_run["rows"]]
    want = [row[key] for row in one_device["rows"]]
    assert len(got) == len(want) == STEPS
    np.testing.assert_allclose(got, want, rtol=TRAJECTORY_RTOL[key])
    if key in ("ecin", "extra"):
        assert want[-1] > want[0] > 0.0  # the gas is moving


def test_ou_state_is_the_one_device_runs_to_the_bit(checked):
    ou = checked["ou"]
    assert ou["common"] and ou["iteration"] == STEPS
    assert ou["dt_compared"] == STEPS and ou["dt_first_differs"] is None
    assert ou["key_equal"] and ou["phases_equal"]
    assert ou["phase_rel_err"] == 0.0 and ou["rollbacks"] == 0


def test_live_mesh_state_passes_the_chip_check(checked, config):
    """benchmarks/check_turb_mesh.py's verdict, every particle a target."""
    import check_turb_mesh as ctm

    g = config["guarantees"]
    assert ctm.judge(checked, g) == (True, True), checked
    f = checked["forces"]
    assert f["targets"] == checked["particles"] and f["finite"]
    assert f["face_targets"] > 0.5 * f["targets"]
    assert ctm.forces_inside(f["errors"], g["forces_ve_rel_max"])
    low = checked["forces_bf16_control"]["errors"]
    for key, name in (("rho_rel_max", "rho"), ("acc_rel_rms", "acc_rms"),
                      ("du_rel_max", "du")):
        assert low[key] > 20 * g["forces_ve_rel_max"][name], (key, low)
    s = checked["stirring"]
    assert s["sound"][0] < g["stirring_rel_rms_max"] < s["bf16_control"][0]
    assert s["sound"][1] < g["stirring_rel_max"] < s["bf16_control"][1]


def test_ou_comparison_refuses_its_controls(mesh_run, one_device, checked):
    """A record one step behind (a skipped step, a key drawn once less) and
    a record that rolled back are both refused."""
    import check_turb_mesh as ctm

    assert ctm.ou_inside(checked["ou"])
    record = one_device["record"]
    behind = dict(record, by_iteration={
        str(int(k) + 1): v for k, v in record["by_iteration"].items()})
    ou = ctm.against_one_chip(mesh_run["ou_state"], behind)
    assert ou["common"] and not ou["key_equal"] and not ou["phases_equal"]
    assert ou["phase_rel_err"] > ctm.OU_REL_MAX and not ctm.ou_inside(ou)
    assert not ctm.ou_inside(dict(checked["ou"], rollbacks=1))
    short = dict(record, by_iteration={"1": record["by_iteration"]["1"]})
    assert not ctm.ou_inside(ctm.against_one_chip(mesh_run["ou_state"],
                                                  short))
    # no record handed in: nothing to hold, as on a run without the form
    assert ctm.ou_inside({"iteration": STEPS})


@pytest.mark.parametrize("recorded", RECORDED, ids=["c45b", "c45e"])
def test_recorded_chip_check_under_the_stated_limits(config, recorded):
    """This PR's chip runs of benchmarks/check_turb_mesh.py at the timed size
    (four v5e chips, 8,000,000 particles), each result as recorded, under
    the limits the configuration states: inside every one, every control
    refused, the OU state the one-chip run's."""
    import check_turb_mesh as ctm

    with open(recorded) as f:
        rec = json.load(f)
    g = config["guarantees"]
    assert rec["correct"] and rec["cell"] == CELL and rec["platform"] == "tpu"
    assert rec["particles"] == 8000000
    assert rec["forces"]["targets"] == g["forces_ve_targets"]
    assert ctm.judge(rec, g) == (True, True)
    # each limit lies between the sound reading and the control's
    f, low = rec["forces"]["errors"], rec["forces_bf16_control"]["errors"]
    for key, name in (("rho_rel_max", "rho"), ("acc_rel_rms", "acc_rms"),
                      ("acc_rel_max", "acc_max"), ("du_rel_max", "du")):
        limit = g["forces_ve_rel_max"][name]
        assert 2 * f[key] < limit < low[key] / 5, (key, f[key], low[key])
    assert rec["retrace"] == rec["rollback"] == rec["reconfigure"] == 0
    # what the chip taught the OU comparison: 22 steps of 8.0M particles
    # leave the ramp (iterations 16-17: the ``rho`` candidate, a pair sum
    # that the one chip's list walk and the mesh's streamed engine round
    # apart), so the phases are no longer the same bits, and are held to
    # ``OU_REL_MAX``; this tier's eight steps never leave the ramp
    ou = rec["ou"]
    assert ou["common"] and ou["key_equal"] and ou["iteration"] == 22
    assert ou["dt_first_differs"] == 16 and not ou["phases_equal"]
    assert ou["dt_rel_max"] < 1e-6
    assert 0.0 < ou["phase_rel_err"] < 1e-3 * ctm.OU_REL_MAX
