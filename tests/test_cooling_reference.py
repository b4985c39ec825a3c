"""The std-cooling deployment against its plain reference, at CPU sizes.

``benchmarks/reference_cooling.py`` is the cooling source written plainly in
float64 from the published fits (six rates, ten channels, the equilibrium
balance, the metal residual over the configuration's table, the subcycled
backward-Euler update, the cooling time); ``sphexa_tpu/physics/cooling.py``
and ``physics/primordial.py`` are what the step runs. The limits are the
configuration's own (``benchmarks/configs/windshock-cooling-4m.json``,
``guarantees``), the ones ``benchmarks/check_cooling.py`` holds the 4.04M chip
run to, and the comparison is that script's ``compare`` / ``judge``:

- ``cooling_rel_rms_max`` / ``cooling_rel_max``: errors of the step-averaged
  ``du_cool`` over the reference's rms. An f32 evaluation reads 1e-6 to 4e-6 /
  4e-6 to 1e-5 here at every dt; the parent program's ``(u_final - u) / dt``
  reads 1.0 wherever dt is under 1e-5 of the cooling time and 0.06-0.2 at a
  Courant dt; the reference with T and the rates rounded to bf16 reads 2e-3 /
  6e-3 everywhere;
- ``cooling_fraction_abs_max`` and ``cooling_dt_rel_max``: the six fractions
  over their element's total, the cooling time and its limiter.

Also here: ``Simulation(prop="std-cooling")`` with no ``cooling_cfg`` evolves
the species; the run spec parses; the chemistry stays row-aligned with the
state through a list rebuild and a rolled-back window on the interpreted list
engine (on Noh's sphere: see the class); the ``numerics`` event carries the cooling extrema (schema v15).
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

import check_cooling  # noqa: E402
import reference_cooling as ref  # noqa: E402

from sphexa_tpu.init import (  # noqa: E402
    CAPABILITIES, make_initializer, split_case_spec)
from sphexa_tpu.observables import make_observable_spec  # noqa: E402
from sphexa_tpu.physics.cooling import (  # noqa: E402
    ChemistryData, CoolingConfig)
from sphexa_tpu.simulation import Simulation  # noqa: E402
from sphexa_tpu.telemetry import Telemetry  # noqa: E402
from sphexa_tpu.telemetry.registry import (  # noqa: E402
    SCHEMA_VERSION, validate_event)
from sphexa_tpu.telemetry.sinks import MemorySink  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "windshock-cooling-4m.json")
CELL = "windshock-cooling-4m.steady"
N = 2048
#: log10 T [K] of the four sample bands: below the H/He peak to bremsstrahlung
BANDS = ((4.0, 5.0), (5.0, 6.0), (6.0, 7.0), (7.0, 8.0))
#: dt over the sample's SHORTEST cooling time
DT_SHARES = (1e-12, 1e-8, 1e-4, 1e-2, 1.0)


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def limits(config):
    return config["guarantees"]


def program_cfg(evolve):
    return CoolingConfig(evolve_species=evolve)


def sample(band, model, seed=5):
    """Seeded (rho, u, ChemistryData): rho over a decade, T over ``band``,
    any ionisation state, metals to twice solar."""
    rng = np.random.default_rng(seed + int(10 * band[0]))
    x, z = model["hydrogen_fraction"], model["metallicity"]
    y = 1.0 - x - z
    a = rng.uniform(0.0, 1.0, N)
    he = rng.dirichlet((1.0, 1.0, 1.0), N)
    chem = {"hi": x * a, "hii": x * (1 - a), "hei": y * he[:, 0],
            "heii": y * he[:, 1], "heiii": y * he[:, 2],
            "metal": z * rng.uniform(0.0, 2.0, N)}
    chem["e"] = chem["hii"] + chem["heii"] / 4.0 + chem["heiii"] / 2.0
    inv_mu = (chem["hi"] + chem["hii"] + (chem["hei"] + chem["heii"]
              + chem["heiii"]) / 4.0 + chem["e"] + chem["metal"] / 2.0)
    T = 10.0 ** rng.uniform(*band, N)
    u = T * ref.KB * inv_mu / ((model["gamma"] - 1.0) * ref.MH
                               * ref.units(model)[2])
    rho = 10.0 ** rng.uniform(0.0, 1.0, N)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return f32(rho), f32(u), ChemistryData(**{k: f32(v)
                                              for k, v in chem.items()})


class TestCellFiles:
    def test_cell_and_metrics_are_declared(self, config):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = next(w for w in bench["workloads"] if w["name"] == CELL)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            "windshock-cooling-4m", "steady", 1)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        assert entry["reduced"] == config["reduced"] == ["ranks"]
        assert entry["source"] == config["source"]
        # the metrics this cell is the first to report (PR 38 appended the
        # Evrard cooling cell to their lists and brought the fourth)
        ours = {m["name"]: m for m in bench["per_layer"]
                if m.get("workloads", [None])[0] == CELL}
        assert sorted(ours) == ["cooling_dt_ratio", "cooling_ms_step",
                                "cooling_network_ms_step",
                                "cooling_radiated_share"]
        assert {m["layer"] for m in ours.values()} == {"cooling"}
        for name in ours:
            assert os.path.exists(os.path.join(BENCH, "layers",
                                               name + ".py"))
        listed = [m["name"] for g in ("end_to_end", "per_layer")
                  for m in bench[g] if CELL in m.get("workloads", ())]
        assert len(listed) == 13 + 4 and "updates_per_s_chip" in listed

    def test_configuration_states_the_programs_model(self, config):
        """The ``cooling`` block the reference is given IS what
        ``Simulation(prop="std-cooling")`` builds by default."""
        state, box, const = make_initializer(config["init"])(4)
        built = CoolingConfig(gamma=const.gamma, evolve_species=True)
        got = check_cooling.model_of(built)
        assert sorted(got) == sorted(config["cooling"])
        for key, want in config["cooling"].items():
            np.testing.assert_allclose(got[key], want, rtol=1e-12,
                                       err_msg=key)
        assert config["guarantees"]["state_dtype"] == str(state.x.dtype)

    def test_run_spec_parses_and_an_unknown_need_is_refused(self, config):
        assert "cooling-network" in CAPABILITIES
        assert split_case_spec(config["init"]) == ("wind-shock", None)
        assert make_observable_spec(config["init"]) is not None
        with pytest.raises(ValueError, match="needs"):
            split_case_spec("wind-shock+cooling-network+grackle")


@pytest.mark.parametrize("share", DT_SHARES)
@pytest.mark.parametrize("band", BANDS, ids=lambda b: f"T1e{b[0]:.0f}")
@pytest.mark.parametrize("evolve", (True, False),
                         ids=("network", "table"))
def test_source_inside_the_limits_and_controls_outside(config, limits,
                                                       evolve, band, share):
    """Both integrators against the reference, T 1e4-1e8 K, dt from 1e-12
    to 1 cooling time: the sound reading inside the configuration's limits,
    the bf16 control refused at every dt, the differenced control refused
    wherever a step's dt lies (1e-4 of the cooling time and under)."""
    model = config["cooling"]
    rho, u, chem = sample(band, model)
    host = {k: np.asarray(getattr(chem, k), np.float64)
            for k in check_cooling.CHEM_FIELDS}
    dt = share * ref.cooling_time(np.asarray(rho, np.float64),
                                  np.asarray(u, np.float64), host, model,
                                  evolve).min()
    result = check_cooling.compare(rho, u, chem, program_cfg(evolve), model,
                                   seed=11, count=N, dts={"step": dt})
    within, refused = check_cooling.judge(
        result, limits,
        refuse_differenced=("step",) if share <= 1e-4 else ())
    assert within, result
    assert refused, result


class TestControls:
    """What the limits are set to refuse, on the configuration's own two
    states (wind: rho 1, u 1.5; cloud: rho 10, u 0.15)."""

    @pytest.fixture(scope="class")
    def states(self):
        rng = np.random.default_rng(3)
        cloud = np.arange(N) >= N // 2
        jitter = lambda: 1.0 + 0.05 * rng.standard_normal(N)
        rho = np.where(cloud, 10.0, 1.0) * jitter()
        u = np.where(cloud, 0.15, 1.5) * jitter()
        chem = {k: np.asarray(v, np.float64) for k, v in dataclasses.asdict(
            ChemistryData.ionized(N)).items()}
        return rho, u, chem

    def inside(self, errs, limits):
        return (errs[0] < limits["cooling_rel_rms_max"]
                and errs[1] < limits["cooling_rel_max"])

    @pytest.mark.parametrize("channel", ("brem", "rec_hii", "rec_heiii"))
    def test_a_dropped_channel_is_refused(self, config, limits, states,
                                          channel):
        rho, u, chem = states
        model = config["cooling"]
        want, _, _ = ref.step(4e-4, rho, u, chem, model)
        got, _, _ = ref.step(4e-4, rho, u, chem, model, skip=(channel,))
        assert not self.inside(ref.rel_errors(got, want), limits)

    def test_a_dropped_subcycle_is_refused_where_subcycles_matter(
            self, config, limits, states):
        """Seven subcycles for eight differ by 1e-5 at 1e-2 time units (the
        scheme's own truncation there is 5e-5 against 4,096 subcycles) and by
        more than the limits from a tenth of the cooling time on."""
        rho, u, chem = states
        model = config["cooling"]
        dt = 0.3 * ref.cooling_time(rho, u, chem, model).min()
        want, _, _ = ref.step(dt, rho, u, chem, model)
        got, _, _ = ref.step(dt, rho, u, chem, model, substeps=7)
        assert not self.inside(ref.rel_errors(got, want), limits)

    def test_differenced_form_reads_noise_at_a_steps_dt(self, config,
                                                        limits, states):
        """The parent's form on the reference's own decrements: zero at
        1e-10 and 1e-6, 1.7 % rms off at the Courant dt (over wind and cloud
        together; the wind alone reads a fifth)."""
        rho, u, chem = states
        model = config["cooling"]
        for dt, floor in ((1e-10, 0.99), (1e-6, 0.99), (4e-4, 0.005)):
            want, _, dec = ref.step(dt, rho, u, chem, model)
            errs = ref.rel_errors(ref.differenced_f32(u, dec, dt), want)
            assert errs[0] > floor and not self.inside(errs, limits)


class TestSimulationDefault:
    @pytest.fixture(scope="class")
    def stepped(self):
        sink = MemorySink()
        state, box, const = make_initializer("wind-shock")(8)
        sim = Simulation(state, box, const, prop="std-cooling", block=256,
                         obs_spec=make_observable_spec("wind-shock"),
                         telemetry=Telemetry(sinks=[sink]),
                         science_rows=True)
        for _ in range(3):
            sim.step()
        sim.flush()
        return sim, sink

    def test_no_cooling_cfg_means_the_network(self, stepped):
        sim, _ = stepped
        assert sim.cooling_cfg.evolve_species
        hi = np.asarray(sim.chem.hi)
        # recombination out of the fully ionised IC moves HI off zero
        assert np.all(np.isfinite(hi)) and hi.max() > 0.0
        np.testing.assert_allclose(np.asarray(sim.chem.hi + sim.chem.hii),
                                   0.76, rtol=1e-5)

    def test_table_mode_stays_reachable(self):
        state, box, const = make_initializer("wind-shock")(8)
        sim = Simulation(
            state, box, const, prop="std-cooling", block=256,
            cooling_cfg=CoolingConfig(gamma=const.gamma,
                                      evolve_species=False))
        sim.step()
        assert float(np.asarray(sim.chem.hi).max()) == 0.0

    def test_numerics_event_carries_the_cooling_extrema(self, stepped):
        """Schema v15. At dt 1e-10 the source is the rate itself: the
        cloud's -0.055, not the -162.6 of one ulp of u over dt."""
        _, sink = stepped
        assert SCHEMA_VERSION >= 15
        events = sink.of_kind("numerics")
        assert events and all(validate_event(e) == [] for e in events)
        for e in events:
            assert 0.02 < e["dt_cool_min"] < 0.5
            assert -0.08 < e["du_cool_min"] < -0.04

    def test_other_propagators_carry_no_cooling_fields(self):
        sink = MemorySink()
        state, box, const = make_initializer("sedov")(8)
        sim = Simulation(state, box, const, prop="std", block=256,
                         obs_spec=make_observable_spec("sedov"),
                         telemetry=Telemetry(sinks=[sink]),
                         science_rows=True)
        sim.step()
        sim.flush()
        (e,) = sink.of_kind("numerics")
        assert "dt_cool_min" not in e and "du_cool_min" not in e


class TestChemRidesTheLists:
    """A forced list rebuild and a forced rollback on the interpreted list
    engine leave ``chem`` row-aligned with the state
    (tests/test_cooling.py::TestChemAlignment covers the sort alone). On
    Noh's sphere at -n 12 and not on wind-shock: its 4:1:1 periodic box takes
    its grid level from the short side, which puts the lists out of reach
    (fold mode) under -n 24 = 55,772 particles, 38 s a step interpreted."""

    @pytest.fixture(scope="class")
    def driven(self):
        sink = MemorySink()
        state, box, const = make_initializer("noh")(12)
        # every particle its own mass (1 % spread): a label that rides every
        # permutation of the state and that no step changes
        label = np.arange(state.n) / state.n
        m0 = float(state.m[0])
        state = dataclasses.replace(
            state, m=jnp.asarray(m0 * (1.0 + 0.01 * label), jnp.float32))
        chem = dataclasses.replace(
            ChemistryData.ionized(state.n),
            metal=jnp.asarray(0.005 + 0.01 * label, jnp.float32))
        sim = Simulation(state, box, const, prop="std-cooling",
                         backend="pallas", tuned={"cell_target": 16},
                         use_lists=True, check_every=4, list_skin_rel=0.1,
                         chem=chem, telemetry=Telemetry(sinks=[sink]))
        for i in range(12):
            if i == 8:
                # shift every particle by 0.75 of the live list's skin at a
                # verified boundary (the open box is translation
                # invariant): the window's first step finds slack -0.5
                sim.flush()
                shift = 0.75 * float(sim.pair_lists.skin)
                sim.state = dataclasses.replace(sim.state,
                                                x=sim.state.x + shift)
            sim.step()
        sim.flush()
        return sim, sink, m0

    def test_went_through_a_rebuild_and_a_rollback(self, driven):
        sim, sink, _ = driven
        assert sim._use_lists and sim.pair_lists is not None
        reasons = [e["reason"] for e in sink.of_kind("rebuild_lists")]
        assert reasons[0] == "first" and "rollback" in reasons
        rollbacks = [e["reason"] for e in sink.of_kind("rollback")]
        assert rollbacks and set(rollbacks) == {"list-expiry"}
        assert len(sink.of_kind("replay")) == len(rollbacks)
        assert sim.iteration == 12

    def test_chem_is_row_aligned(self, driven):
        sim, _, m0 = driven
        label = (np.asarray(sim.state.m, np.float64) / m0 - 1.0) / 0.01
        metal = np.asarray(sim.chem.metal, np.float64)
        # the rows are no longer in label order, and chem went with them
        assert np.any(np.diff(label) < 0)
        np.testing.assert_allclose(metal, 0.005 + 0.01 * label, atol=2e-7)
        np.testing.assert_allclose(np.asarray(sim.chem.hi + sim.chem.hii),
                                   0.76, rtol=1e-5)
