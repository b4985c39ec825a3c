"""The turb-ve deployment against its plain reference, at CPU sizes.

``benchmarks/reference_stirring.py`` is upstream's stirring written plainly in
float64 (the OU step, the Helmholtz projection, one particle's loop over the
modes); ``sphexa_tpu/sph/hydro_turb.py`` is what the step runs. The limits are
the configuration's own (``benchmarks/configs/turb-ve-8m.json``,
``guarantees``), the ones ``benchmarks/check_stirring.py`` holds the 8.0M
chip run to:

- ``stirring_rel_rms_max`` 3e-5 and ``stirring_rel_max`` 2e-4, errors of the
  acceleration vector over the rms magnitude of the reference. An f32
  evaluation reads 2e-7 / 1e-6 here (the rounding of k.x at |k.x| <= 16.3 and
  of 224 f32 terms); the same sum with cosines, sines and weights rounded to
  bf16, which is what an f32 matmul at a TPU's default precision computes,
  reads 2e-3 / 6e-3. The limits sit a factor of 70-150 from either side, so
  one precision down is refused by both and a sound evaluation has room.

Also here: no intermediate of N x M elements exists in the stirring at any N
(walked in the jaxpr), a rolled-back check window draws the same noise again
(bitwise), and the cell's files parse.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import reference_stirring as ref  # noqa: E402

from sphexa_tpu.init import init_turbulence, turbulence_constants  # noqa: E402
from sphexa_tpu.sph import hydro_turb  # noqa: E402
from sphexa_tpu.sph.hydro_turb import (  # noqa: E402
    compute_phases, create_stirring_modes, drive_turbulence, st_calc_accel,
    update_noise)

CONFIG = os.path.join(BENCH, "configs", "turb-ve-8m.json")
MODES = 112  # counted from create_stirring_modes' loop for stSpectForm 1


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def limits(config):
    g = config["guarantees"]
    return g["stirring_rel_rms_max"], g["stirring_rel_max"]


@pytest.fixture(scope="module")
def turb():
    s = turbulence_constants()
    cfg, state = create_stirring_modes(
        s["Lbox"], st_max_modes=int(s["stMaxModes"]),
        energy_prefac=s["stEnergyPrefac"], mach_velocity=s["stMachVelocity"],
        sol_weight=s["solWeight"], spect_form=int(s["stSpectForm"]),
        seed=int(s["rngSeed"]))
    # a few OU steps in, as a running case is
    for _ in range(3):
        state = update_noise(state, jnp.float32(2e-3), cfg)
    return cfg, state


def particles(n, seed=31):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.uniform(-0.5, 0.5, n), jnp.float32)
                 for _ in range(3))


def reference_accel(xyz, turb, operand_dtype=None):
    cfg, state = turb
    pr, pi = ref.compute_phases(state.modes, state.phases, cfg.sol_weight)
    return ref.stir_accel(*xyz, state.modes, state.amplitudes, pr, pi,
                          cfg.sol_weight_norm, operand_dtype=operand_dtype)


def test_the_case_has_112_modes(turb, config):
    cfg, state = turb
    assert cfg.num_modes == state.modes.shape[0] == MODES
    assert config["stirring"]["modes"] == MODES


@pytest.mark.parametrize("n", [4096, 3001], ids=["4096", "ragged-3001"])
def test_stirring_within_the_configurations_limits(turb, limits, n):
    cfg, state = turb
    xyz = particles(n)
    pr, pi = compute_phases(state, cfg)
    got = jax.jit(st_calc_accel, static_argnums=4)(*xyz, state, cfg, pr, pi)
    rms, worst = ref.rel_errors(got, reference_accel(xyz, turb))
    assert rms < limits[0] and worst < limits[1], (rms, worst)
    # and with the room the docstring claims: f32 reads 2e-7 / 1e-6 here
    assert rms < 1e-6 and worst < 5e-6, (rms, worst)


def test_bf16_rounded_reference_is_refused(turb, limits):
    """The control: one precision down must fail BOTH limits."""
    xyz = particles(4096)
    sound = reference_accel(xyz, turb)
    rounded = reference_accel(xyz, turb, operand_dtype=ml_dtypes.bfloat16)
    rms, worst = ref.rel_errors(rounded, sound)
    assert rms > limits[0] and worst > limits[1], (rms, worst)
    assert rms > 5e-4, rms  # 2e-3 as predicted from 8 bits of mantissa


def test_default_precision_tpu_matmul_is_refused(turb, limits):
    """What the matmul form this module had computes on a TPU at jax's
    default precision: operands rounded to bf16, products summed in f32."""
    cfg, state = turb
    x, y, z = particles(4096)
    pr, pi = compute_phases(state, cfg)
    kdotx = (x[:, None] * state.modes[None, :, 0]
             + y[:, None] * state.modes[None, :, 1]
             + z[:, None] * state.modes[None, :, 2])
    bf = lambda a: a.astype(jnp.bfloat16)
    dot = lambda a, b: jnp.matmul(bf(a), bf(b),
                                  preferred_element_type=jnp.float32)
    acc = cfg.sol_weight_norm * (
        dot(jnp.cos(kdotx), state.amplitudes[:, None] * pr)
        - dot(jnp.sin(kdotx), state.amplitudes[:, None] * pi))
    rms, worst = ref.rel_errors(
        [acc[:, 0], acc[:, 1], acc[:, 2]], reference_accel((x, y, z), turb))
    assert rms > limits[0] and worst > limits[1], (rms, worst)


@pytest.mark.parametrize("dt", [1.7e-3, 1e-5], ids=["dt1.7e-3", "dt1e-5"])
def test_update_noise_matches_the_reference_on_the_same_draws(turb, dt):
    cfg, state = turb
    dt = jnp.float32(dt)
    z, key = ref.system_draws(state.key, state.phases.shape,
                              state.phases.dtype)
    want = ref.update_noise(state.phases, z, dt, cfg.decay_time,
                            cfg.variance)
    got = update_noise(state, dt, cfg)
    np.testing.assert_array_equal(np.asarray(got.key), np.asarray(key))
    # f32 against f64: the rounding of the phases themselves (two ulp of
    # the largest). At dt / ts = 6e-6 the noise term is 1e-4 of that
    # scale, and sqrt(1 - f * f) in f32 would be 0.3 % off: 10 ulp
    scale = np.abs(np.asarray(state.phases)).max()
    assert np.abs(np.asarray(got.phases) - want).max() < 2.5e-7 * scale
    # the step is a real one: damping and noise both moved the phases
    step = np.abs(want - np.asarray(state.phases)).max()
    assert step > 2e-3 * float(np.sqrt(dt)) * cfg.variance


@pytest.mark.parametrize("sol_weight", [0.5, 1.0, 0.0])
def test_compute_phases_matches_the_reference(turb, sol_weight):
    cfg, state = turb
    cfg = dataclasses.replace(cfg, sol_weight=sol_weight)
    got = compute_phases(state, cfg)
    want = ref.compute_phases(state.modes, state.phases, sol_weight)
    scale = np.abs(np.asarray(state.phases)).max()
    for g, w in zip(got, want):
        assert np.abs(np.asarray(g) - w).max() < 1e-6 * scale


@pytest.mark.parametrize("span", [17.0, 1e2, 1e4])
def test_sincos_is_f32_exact_over_the_stirrings_arguments(span):
    """The module's own sine / cosine pair (one reduction, polynomials:
    cheap ops XLA fuses into the sum) against float64: 1e-7, the size of
    f32's own rounding of a value near 1, far past |k.x| <= 16.3."""
    a = np.random.default_rng(5).uniform(-span, span, 200_000).astype(
        np.float32)
    s, c = jax.jit(hydro_turb._sincos)(jnp.asarray(a))
    assert s.dtype == c.dtype == jnp.float32
    a64 = a.astype(np.float64)
    assert np.abs(np.asarray(s) - np.sin(a64)).max() < 2e-7
    assert np.abs(np.asarray(c) - np.cos(a64)).max() < 2e-7


def _sizes(jaxpr):
    """Element counts of every value a jaxpr computes, sub-jaxprs (loop
    bodies, nested calls) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _sizes(sub)


@pytest.mark.parametrize("n", [65536, 1000])
def test_no_n_by_m_intermediate_in_the_stirring(turb, n):
    cfg, state = turb
    v = jax.ShapeDtypeStruct((n,), jnp.float32)
    closed = jax.make_jaxpr(lambda *a: drive_turbulence(*a, cfg))(
        v, v, v, v, v, v, jax.ShapeDtypeStruct((), jnp.float32), state)
    sizes = list(_sizes(closed.jaxpr))
    assert len(sizes) > 100  # the walk did reach the loop's body
    # nothing of N x M, nor of N x (modes per turn): O(N) whatever M is
    assert max(sizes) < n * MODES
    assert max(sizes) <= max(n, 9 * MODES), max(sizes)  # the (M, 9) table


def test_a_mode_table_that_is_no_multiple_of_the_turn(turb):
    """The padded tail of the loop adds nothing: 112 modes cut to 109."""
    cfg, state = turb
    cut = MODES - 3
    assert cut % hydro_turb.MODES_PER_TURN
    short = dataclasses.replace(
        state, modes=state.modes[:cut], amplitudes=state.amplitudes[:cut],
        phases=state.phases[:cut])
    xyz = particles(512)
    pr, pi = compute_phases(short, cfg)
    got = st_calc_accel(*xyz, short, cfg, pr, pi)
    want = reference_accel(xyz, (cfg, short))
    rms, worst = ref.rel_errors(got, want)
    assert rms < 1e-6 and worst < 5e-6, (rms, worst)


# -- the driven step family: a rolled-back window draws the same noise ------
# (ONE driven run of 24,389 particles, interpret mode: 90-130 s, the file's
# wall. Here, in a file of 21 tests, and not in a file of its own: xdist's
# ``--dist loadfile`` starts the files with the most tests first, and a file
# of one test starts last: it was the run's tail, 90 s of 663.)

SIDE = 29  # the smallest periodic box whose grid takes persistent lists


def _turb_sim(sink):
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry

    state, box, const = init_turbulence(SIDE)
    return Simulation(state, box, const, prop="turb-ve", backend="pallas",
                      use_lists=True, check_every=4, science_rows=True,
                      obs_spec=make_observable_spec("turbulence"),
                      telemetry=Telemetry(sinks=[sink]))


def _bits(tree):
    return [np.asarray(a) for a in jax.tree.leaves(tree)]


def test_a_rolled_back_window_replays_the_same_noise_bitwise():
    """The OU phases and the PRNG key advance inside the jitted step; the
    window's pin carries the turb slot, so a rollback restores both and the
    replay must draw the noise the window drew. One window of four steps
    from the IC whose fetched diagnostics are doctored to read ``list
    expired``: what the four launched steps left (read as the rollback
    starts, before it restores the pin) against what the four replayed
    steps leave: same bits, particles and turb. (ONE driven Simulation:
    an undisturbed twin would run the window's own program on the
    window's own inputs, which the first pass already is.)"""
    from sphexa_tpu.telemetry.sinks import MemorySink

    sink = MemorySink()
    sim = _turb_sim(sink)
    assert sim._use_lists and sim._aux_slot == "turb"
    key0 = np.asarray(sim.turb_state.key)

    # flush() asks once (the first bad step) and _rollback() once more
    fresh, left = sim._lists_fresh, [2]

    def expired_twice(diagnostics):
        if left[0]:
            left[0] -= 1
            return False
        return fresh(diagnostics)

    rollback, drew = sim._rollback, []

    def rollback_after_reading(*args):
        assert sim.iteration == 4
        drew.append((_bits(sim.turb_state), _bits(sim.state)))
        return rollback(*args)

    sim._lists_fresh = expired_twice
    sim._rollback = rollback_after_reading
    for _ in range(4):
        sim.step()
    sim.flush()
    (rb,) = sink.of_kind("rollback")
    assert rb["reason"] == "list-expiry" and rb["steps"] == 4
    assert rb["to_it"] == 0
    (rp,) = sink.of_kind("replay")
    assert rp["steps"] == 4 and sim.iteration == 4

    ((turb_drawn, state_drawn),) = drew
    for a, b in zip(turb_drawn, _bits(sim.turb_state)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(state_drawn, _bits(sim.state)):
        np.testing.assert_array_equal(a, b)
    # four steps advanced the stream, and the stirring moved the gas
    assert not np.array_equal(np.asarray(sim.turb_state.key), key0)
    rows = sim.drain_science()
    assert [r["it"] for r in rows] == [1, 2, 3, 4]
    assert rows[-1]["ecin"] > rows[0]["ecin"] > 0.0


# -- the cell's files ---------------------------------------------------------

def test_configuration_parses_and_counts(config):
    assert config["particles"] == config["side"] ** 3 == 8_000_000
    assert (config["init"], config["prop"]) == ("turbulence", "turb-ve")
    assert config["devices"] == config["ranks"] == 1
    assert config["reduced"] == ["ranks"]
    assert len(config["source"]) <= 200
    g = config["guarantees"]
    assert 0 < g["stirring_rel_rms_max"] < g["stirring_rel_max"] < 1e-3
    # every published constant the file states is the initialiser's own
    s = turbulence_constants()
    for k, v in config["stirring"].items():
        if k != "modes":
            assert s[k] == v, k
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "turb-ve-8m.steady"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "turb-ve-8m", "steady", 1)
    (metric,) = [m for m in bench["per_layer"]
                 if m["name"] == "stirring_ms_step"]
    # (the mesh cell of the same box reads it too since PR 45)
    assert metric["workloads"] == ["turb-ve-8m.steady",
                                   "turb-ve-8m-x4.steady"]


def _reader():
    import run

    return run.load_reader("layers", "stirring_ms_step")


@pytest.mark.parametrize("trace", [
    None,
    {"steps": 4, "phase_s_max": {"momentum-energy": 2.7, "integrate": 0.02}},
], ids=["no-trace", "no-turbulence-phase"])
def test_reader_is_silent_without_the_phase(trace):
    assert _reader()({"trace": trace}) is None


def test_reader_reads_the_phase_per_traced_step():
    trace = {"steps": 4, "phase_s_max": {"turbulence": 0.2, "iad": 1.0}}
    assert _reader()({"trace": trace}) == pytest.approx(50.0)
