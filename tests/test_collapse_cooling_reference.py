"""The ``evrard-cooling`` deployment (std hydro + self-gravity + the cooling
network in one step) against its coupled plain reference, at CPU sizes.

``benchmarks/reference_collapse_step.py`` is the right-hand side of one
``std-cooling`` step under gravity, composed from the benchmark's three plain
references and importing nothing of the program; the comparison is
``benchmarks/check_collapse_step.py``'s ``system_step`` / ``compare`` /
``judge``, the one the 1.1M chip run is held to, under the limits of
``benchmarks/configs/evrard-cooling-1m.json`` (``guarantees``), here with
every particle a target, so the step's dt is the reference's own to the last
candidate. At ``-n 12`` (920 particles) the sound readings are: hydro ``rho``
4e-7, acceleration 4e-7 / 8e-7, ``du`` 7e-7; the tree's part 2e-6 / 1e-5;
``du`` with the source in it 1e-6 / 5e-6; fractions 7e-8. The controls read
1.05 / 5.5 (the source dropped from ``du``: at rest the source IS ``du``) and
1.5 / 2.9 (gravity dropped from the acceleration).

Also here: the chemistry stays row-aligned with the particles through twelve
per-step sorts under gravity with a forced gravity reconfigure and a forced
rollback with replay in between; ``etot`` less the radiated-energy counter
(schema v16) is conserved to the drift the same run shows with cooling off;
the cell's files are declared.
"""

import dataclasses
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import check_collapse_step as check  # noqa: E402
import check_cooling  # noqa: E402

from sphexa_tpu.init import make_initializer  # noqa: E402
from sphexa_tpu.observables import make_observable_spec  # noqa: E402
from sphexa_tpu.physics.cooling import (  # noqa: E402
    ChemistryData, CoolingConfig)
from sphexa_tpu.propagator import DT_LIMITERS  # noqa: E402
from sphexa_tpu.simulation import Simulation  # noqa: E402
from sphexa_tpu.telemetry import Telemetry  # noqa: E402
from sphexa_tpu.telemetry.registry import validate_event  # noqa: E402
from sphexa_tpu.telemetry.sinks import MemorySink  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "evrard-cooling-1m.json")
CELL = "evrard-cooling-1m.steady"
SIDE = 12


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def labelled(side=SIDE):
    """Evrard's IC with every particle its own mass (1 % spread): a label
    that rides every permutation of the state and that no step changes."""
    state, box, const = make_initializer("evrard-cooling")(side)
    label = np.arange(state.n) / state.n
    m0 = float(state.m[0])
    state = dataclasses.replace(
        state, m=jnp.asarray(m0 * (1.0 + 0.01 * label), jnp.float32))
    return state, box, const, m0


def seeded_chem(label):
    """A chemistry that is a function of the label: the metal fraction,
    which no mode evolves, and the H and He ionisation states."""
    x, y = 0.76, 1.0 - 0.76 - 0.0122
    he = np.stack([0.1 * label, 0.3 * (1 - label), 1 - 0.1 * label
                   - 0.3 * (1 - label)])
    chem = {"hi": x * 0.2 * label, "hii": x * (1 - 0.2 * label),
            "hei": y * he[0], "heii": y * he[1], "heiii": y * he[2],
            "metal": 0.005 + 0.01 * label}
    chem["e"] = chem["hii"] + chem["heii"] / 4.0 + chem["heiii"] / 2.0
    return chem


def label_of(sim, m0):
    return (np.asarray(sim.state.m, np.float64) / m0 - 1.0) / 0.01


class TestCellFiles:
    def test_cell_and_metrics_are_declared(self, config):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = next(w for w in bench["workloads"] if w["name"] == CELL)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            "evrard-cooling-1m", "steady", 1)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        assert entry["reduced"] == config["reduced"] == ["side", "ranks"]
        assert entry["source"] == config["source"]
        assert (config["init"], config["prop"], config["particles"]) == (
            "evrard-cooling", "std-cooling", 1098340)
        listed = [m["name"] for m in bench["per_layer"]
                  if "workloads" not in m or CELL in m["workloads"]]
        assert len(listed) == 41
        assert {"sort_aux_ms_step", "cooling_radiated_share",
                "gravity_ms_step", "cell_ranges_ms_step",
                "cooling_network_ms_step"} <= set(listed)
        for name in listed:
            assert os.path.exists(os.path.join(BENCH, "layers",
                                               name + ".py"))
        rates = next(m for m in bench["end_to_end"]
                     if m["name"] == "updates_per_s_chip")
        assert CELL in rates["workloads"]
        # at most half of a benchmark's cells may ask for four chips
        assert (2 * sum(w["chips"] == 4 for w in bench["workloads"])
                <= len(bench["workloads"]))

    def test_guarantees_state_every_limit_with_its_reason(self, config):
        g = config["guarantees"]
        for key in ("forces_rel_max", "gravity_rel_rms_max",
                    "gravity_rel_p99_max", "cooling_rel_rms_max",
                    "cooling_rel_max", "cooling_fraction_abs_max",
                    "cooling_dt_rel_max", "cooling_refuse_differenced",
                    "energy_drift_max"):
            assert key in g
        for why in ("forces_why", "gravity_why", "cooling_why",
                    "energy_drift_why"):
            assert len(g[why]) > 100
        assert set(g["forces_rel_max"]) == {"rho", "acc_rms", "acc_max", "du"}
        # the cooling block is the program's default model
        assert check_cooling.model_of(
            CoolingConfig(evolve_species=True)) == pytest.approx(
                config["cooling"])

    def test_reference_imports_nothing_of_the_program(self):
        with open(os.path.join(BENCH, "reference_collapse_step.py")) as f:
            code = f.read().split('"""', 2)[2]
        assert "sphexa_tpu" not in code


@pytest.fixture(scope="module", params=["xla", "pallas"])
def stepped(request):
    """Three steps from the labelled IC on one engine (the gas is moving, so
    the hydro ``du`` and the viscosity are not zero), then the calls the
    fourth step makes, and the comparison with every particle a target."""
    state, box, const, m0 = labelled()
    label = np.arange(state.n) / state.n
    chem = ChemistryData(**{k: jnp.asarray(v, jnp.float32)
                            for k, v in seeded_chem(label).items()})
    sim = Simulation(state, box, const, prop="std-cooling", theta=0.5,
                     backend=request.param, chem=chem,
                     obs_spec=make_observable_spec("evrard-cooling"))
    for _ in range(3):
        sim.step()
    step = check.system_step(sim, const)
    return sim, const, m0, step


class TestCoupledStep:
    def test_the_engine_under_gravity(self, stepped):
        """Since PR 44 the Mosaic engine walks its pair lists under
        self-gravity on one device; the XLA engine has none."""
        sim = stepped[0]
        on_lists = sim._cfg.backend == "pallas"
        assert sim.gravity_on and sim._use_lists is on_lists
        assert (sim.pair_lists is not None) is on_lists
        assert sim.cooling_cfg.evolve_species

    def test_step_is_the_references(self, stepped, config):
        _, const, _, step = stepped
        result = check.compare(step, const, config["cooling"], 0, None)
        within, refused = check.judge(result, config["guarantees"])
        assert result["finite"] and result["targets"] == result["particles"]
        assert within, result
        assert refused, result
        # both controls by a wide margin, not by the limit's last digit
        assert result["du_without_cooling"][1] > 0.1
        assert result["acceleration_without_gravity"][0] > 0.1
        assert result["dt"]["limiter"] == "growth"

    def test_misaligned_chem_is_refused(self, stepped, config):
        """The reference handed the chemistry in the order it was SEEDED in
        (what the program would hold had the sort left ``chem`` behind)."""
        sim, const, m0, step = stepped
        label = label_of(sim, m0)
        assert np.any(np.diff(label) < 0)
        seeded = seeded_chem(np.arange(label.size) / label.size)
        aligned = seeded_chem(label)
        np.testing.assert_allclose(np.asarray(step["chem"].metal),
                                   aligned["metal"], atol=2e-7)
        result = check.compare(step, const, config["cooling"], 0, None,
                               chem_for_reference=seeded)
        within, _ = check.judge(result, config["guarantees"])
        assert not within
        assert result["fractions"] > 1e-3
        assert result["du"][1] > 10 * (
            config["guarantees"]["forces_rel_max"]["du"]
            + config["guarantees"]["cooling_rel_max"])

    def test_the_step_takes_the_references_dt(self, stepped, config):
        """One real step from the compared state: its dt and the candidate
        that set it are the reference's."""
        sim, const, _, step = stepped
        result = check.compare(step, const, config["cooling"], 0, None)
        d = sim.step()
        assert float(d["dt"]) == pytest.approx(result["dt"]["reference"],
                                               rel=1e-6)
        assert (DT_LIMITERS[int(d["dt_limiter"])]
                == result["dt"]["reference_limiter"])
        for name, want in result["dt"]["reference_candidates"].items():
            assert result["dt"]["candidates"][name] == pytest.approx(
                want, rel=config["guarantees"]["cooling_dt_rel_max"]), name


class TestChemThroughSortsReconfigureAndRollback:
    """Twelve steps, a sort each, under gravity on the XLA engine: a
    forced gravity reconfigure at iteration 4 and, at iteration 8, a near
    field cap cut under the lists' need, so the next window's first step
    overflows and the driver rolls back, re-sizes and replays."""

    @pytest.fixture(scope="class", params=[False, True],
                    ids=["table", "network"])
    def driven(self, request):
        sink = MemorySink()
        state, box, const, m0 = labelled()
        seeded = seeded_chem(np.arange(state.n) / state.n)
        chem = ChemistryData(**{k: jnp.asarray(v, jnp.float32)
                                for k, v in seeded.items()})
        sim = Simulation(
            state, box, const, prop="std-cooling", theta=0.5, check_every=4,
            chem=chem, telemetry=Telemetry(sinks=[sink]),
            obs_spec=make_observable_spec("evrard-cooling"),
            cooling_cfg=CoolingConfig(gamma=const.gamma,
                                      evolve_species=request.param))
        for i in range(12):
            if i == 4:
                sim.flush()
                sim._configure(grav_margin=2.0, reason="overflow")
            if i == 8:
                sim.flush()
                sim._cfg = dataclasses.replace(
                    sim._cfg, gravity=dataclasses.replace(
                        sim._cfg.gravity, p2p_cap=4))
            sim.step()
        sim.flush()
        return sim, sink, m0, request.param

    def test_went_through_a_reconfigure_and_a_rollback(self, driven):
        sim, sink, _, _ = driven
        # (on the CPU ``auto`` is the XLA engine: sorted and streamed every
        # step; the same drive on lists is tests/test_gravity_lists.py's)
        assert sim.iteration == 12
        assert (sim.pair_lists is not None) is (sim._cfg.backend == "pallas")
        reasons = [e["reason"] for e in sink.of_kind("reconfigure")]
        assert reasons.count("overflow") >= 2
        rollbacks = sink.of_kind("rollback")
        assert [e["reason"] for e in rollbacks] == ["overflow"]
        assert len(sink.of_kind("replay")) == 1
        assert sim._cfg.gravity.p2p_cap > 4

    def test_chem_is_row_aligned(self, driven):
        sim, _, m0, evolve = driven
        label = label_of(sim, m0)
        # the rows are no longer in label order, and chem went with them
        assert np.any(np.diff(label) < 0)
        want = seeded_chem(label)
        got = {k: np.asarray(getattr(sim.chem, k), np.float64)
               for k in check_cooling.CHEM_FIELDS}
        fields = check_cooling.CHEM_FIELDS if not evolve else ("metal",)
        # the label comes back from a float32 mass to 6e-6: a fraction to
        # 5e-6 of its total at most; a row astray reads 1e-3 to 0.1
        for k in fields:
            np.testing.assert_allclose(got[k], want[k], atol=5e-6)
        np.testing.assert_allclose(got["hi"] + got["hii"], 0.76, rtol=1e-5)
        # (the network ionises the seeded neutral share at 2e6 K within a
        # step: its rows carry the label in ``metal`` and keep their
        # elements' totals)
        np.testing.assert_allclose(got["hei"] + got["heii"] + got["heiii"],
                                   1.0 - 0.76 - 0.0122, rtol=1e-5)

    def test_an_unpermuted_chem_would_not_be(self, driven):
        sim, _, m0, _ = driven
        n = sim.state.n
        stale = seeded_chem(np.arange(n) / n)["metal"]
        want = seeded_chem(label_of(sim, m0))["metal"]
        assert np.abs(stale - want).max() > 1e-3

    def test_the_rolled_back_window_radiated_nothing(self, driven):
        """The counter holds the verified steps' shares and no other: it is
        the sum of the ``numerics`` events' per-step lists, twelve entries,
        the rolled-back window's four launched steps not among them."""
        sim, sink, _, _ = driven
        events = sink.of_kind("numerics")
        assert all(validate_event(e) == [] for e in events)
        steps = [v for e in events for v in e["e_cool_step"]]
        # (a step's share can be positive: the lagged half of the
        # Adams-Bashforth weights, after a step whose rate was far larger)
        assert len(steps) == 12 and sum(steps) < 0.0
        assert sim.e_cool == pytest.approx(sum(steps), rel=1e-12)
        assert events[-1]["e_cool"] == pytest.approx(sim.e_cool, rel=1e-12)


class TestEnergyBalance:
    """Twenty steps from the IC with the code mass unit a tenth of
    upstream's (T 2e5 K, near the cooling peak: the sphere's cooling time
    0.45 against a free-fall time of 0.8) and the same steps under ``std``.
    ``etot`` alone moves by what cooling took, 1.3e-3 of |etot|;
    ``etot - e_cool`` moves as the run without cooling does, to 9e-7 (at 920
    particles both drift 2.3e-2: the softened potential follows h while h
    relaxes, the same in both runs). The plain product ``rate x dt`` in
    place of the integrator's Adams-Bashforth weights would leave half a
    step's cooling, 6.5e-5."""

    @staticmethod
    def run(prop, **kw):
        state, box, const = make_initializer("evrard-cooling")(SIDE)
        sim = Simulation(state, box, const, prop=prop, theta=0.5,
                         check_every=4, science_rows=True,
                         obs_spec=make_observable_spec("evrard-cooling"),
                         **kw)
        for _ in range(20):
            sim.step()
        sim.flush()
        return sim, sim.drain_science()

    def test_etot_less_the_counter_is_conserved(self):
        _, off = self.run("std")
        unit = CoolingConfig().m_code_g
        sim, on = self.run("std-cooling", cooling_cfg=CoolingConfig(
            gamma=5.0 / 3.0, evolve_species=True, m_code_g=0.1 * unit))
        assert all("e_cool" not in r for r in off)
        assert [r["it"] for r in on] == list(range(1, 21))
        e0 = abs(on[0]["etot"])
        change = lambda rows, f: (f(rows[-1]) - f(rows[0])) / e0
        drift_off = change(off, lambda r: r["etot"])
        drift_on = change(on, lambda r: r["etot"])
        balance = change(on, lambda r: r["etot"] - r["e_cool"])
        radiated = -change(on, lambda r: r["e_cool"])
        assert sim.e_cool == pytest.approx(on[-1]["e_cool"])
        assert 1e-3 < radiated < 2e-3
        assert abs(drift_on - drift_off) > 0.9 * radiated
        assert abs(balance - drift_off) < 5e-6
