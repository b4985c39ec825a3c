"""The cooling source the step computes against the plain reference, on the
live state after a cell's traffic, with the two controls its limits must
refuse.

    python3 benchmarks/check_cooling.py --workload windshock-cooling-4m.steady --seed <n> [--seconds 30] [--side 12]

A builder's script, not a metric: it runs the cell exactly as run.py does
(the same ``run_cell``: initialiser, ``Simulation`` as ``main()`` builds it,
warm-up, the traffic's check windows for ``--seconds``, ``correct``) and
then, outside any clock, makes on the LIVE particle state and chemistry the
calls the step makes (``propagator.std_forces`` for ``rho``, then
``cooling.cool_timestep`` and ``cooling.cool_step``, jitted, over all the
particles) and compares them with ``reference_cooling.py`` (float64, from the
published fits) at seeded targets of the wind and as many of the cloud
(``rho`` > 3), at three dt: the last verified step's own, the state's
Courant dt, and 1e-2.

Exit 0 only if the run is ``correct``, the sound reading is inside the
configuration's ``cooling_*`` limits at every dt AND both controls are
refused where they must be: the parent program's differenced form
``(u_final - u) / dt`` (the program's own subcycles chained through ``u`` in
float32) at the step's dt and the Courant dt, and the reference with T and
every rate rounded to bf16 at all three. It fails without a TPU, like
run.py; ``--side <n>`` instead rehearses it on the CPU at a tiny size with
the list engine interpreted, and then prints no device number.

``compare`` and ``judge`` are what the tier-1 tests' comparison is made of
too (tests/test_cooling_reference.py: the same reference, limits and error
functions), so the chip and the CPU tier make the same comparison.
"""

import argparse
import dataclasses
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

#: the third dt of the comparison: 1e-2 time units, 0.4 % of the cloud's
#: cooling time, where the differenced form begins to resolve the source
DT_LONG = 1e-2
#: a target is of the cloud above this density (wind 1, cloud 10)
RHO_CLOUD = 3.0
CHEM_FIELDS = ("hi", "hii", "hei", "heii", "heiii", "e", "metal")


def model_of(cooling_cfg):
    """The reference's ``model`` arguments as the PROGRAM's configuration
    holds them: what a test sets beside the configuration file's ``cooling``
    block, and what ``compare`` hands the reference when the program runs a
    configuration of its own (a test's heating, another table)."""
    import reference_cooling
    from sphexa_tpu.physics import cooling

    c = cooling_cfg
    return {
        "m_code_in_ms": c.m_code_g / cooling.MSUN,
        "l_code_in_kpc": c.l_code_cm / cooling.KPC,
        "gamma": c.gamma, "hydrogen_fraction": c.hydrogen_fraction,
        "metallicity": reference_cooling.Z_SUN,
        "heating_rate": c.heating_rate,
        "ct_crit": c.ct_crit, "substeps": c.substeps,
        "logT_table": list(c.logT_table), "logL_table": list(c.logL_table),
    }


@functools.lru_cache(maxsize=None)
def _programs():
    """The jitted calls, built once per process (the program is imported
    late: this module loads before the platform is chosen)."""
    import jax

    from sphexa_tpu.physics.cooling import cool_step, cool_timestep
    from sphexa_tpu.propagator import std_forces

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def forces(state, box, cfg, chem, lists):
        (state, _, _, _, _, _, dt_courant, _, _, _, rho, _, _,
         chem) = std_forces(state, box, cfg, None, aux=chem, lists=lists)
        return state, chem, rho, dt_courant

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def limiter(rho, u, chem, cfg):
        return cool_timestep(rho, u, chem, cfg)

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def source(dt, rho, u, chem, cfg):
        return cool_step(dt, rho, u, chem, cfg)

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def differenced(dt, rho, u, chem, cfg):
        """The parent's form: the program's own subcycles, one
        ``cool_step`` of one subcycle each, chained through ``u`` in the
        state's dtype, then ``(u_final - u) / dt``."""
        one = dataclasses.replace(cfg, substeps=1)
        dt_sub = dt / cfg.substeps
        carried = u
        for _ in range(cfg.substeps):
            du, chem = cool_step(dt_sub, rho, carried, chem, one)
            carried = carried + dt_sub * du
        return (carried - u) / dt

    return forces, limiter, source, differenced


def system_inputs(sim, const):
    """What the cooling calls of the next step would be given: ``rho`` from
    the program's std force stage on ``sim``'s live state under its live
    configuration and pair lists, ``u`` = cv T, the chemistry row-aligned
    with both (the stage's own order), and the stage's Courant dt."""
    forces = _programs()[0]
    state, chem, rho, dt_courant = forces(
        sim.state, sim.box, sim.active_cfg, sim.chem, sim.pair_lists)
    return rho, const.cv * state.temp, chem, float(dt_courant)


def draw_targets(rho, seed, count):
    """``count`` seeded rows of the wind and ``count`` of the cloud (fewer
    where a group is smaller), as two index arrays."""
    import numpy as np

    rng = np.random.default_rng(seed)
    groups = []
    for rows in (np.flatnonzero(rho <= RHO_CLOUD),
                 np.flatnonzero(rho > RHO_CLOUD)):
        take = min(count, rows.size)
        groups.append(np.sort(rng.choice(rows, take, replace=False)))
    return groups


def _worst(pairs):
    """Element-wise max over (rel_rms, rel_max) pairs."""
    return [max(p[0] for p in pairs), max(p[1] for p in pairs)]


def compare(rho, u, chem, cooling_cfg, model, seed, count, dts,
            converged_substeps=0):
    """The program's cooling calls on (rho, u, chem), all rows, against the
    reference at seeded targets, at every dt of ``dts`` ({name: dt}).

    Per dt: ``sound``, ``differenced_control`` and ``bf16_control`` as
    (rel_rms, rel_max) of ``du_cool`` over the reference's rms, the worse of
    the wind's and the cloud's; ``fractions`` / ``bf16_fractions`` the
    largest absolute error of a fraction over its element's total; with
    ``converged_substeps``, ``scheme`` = the reference's own subcycle count
    against that many (the scheme's truncation error, unjudged). Once:
    ``cooling_time`` the per-target cooling time's largest relative error,
    ``dt_cool`` the global limiter against the reference's min over ALL
    rows."""
    import ml_dtypes
    import numpy as np

    import reference_cooling as ref

    _, limiter, source, differenced = _programs()
    evolve = cooling_cfg.evolve_species
    host = lambda a: np.asarray(a, np.float64)
    rho_h, u_h = host(rho), host(u)
    chem_h = {k: host(getattr(chem, k)) for k in CHEM_FIELDS}
    groups = draw_targets(rho_h, seed, count)
    at = lambda rows: (rho_h[rows], u_h[rows],
                       {k: v[rows] for k, v in chem_h.items()})

    out = {"targets": [int(g.size) for g in groups], "dt": {}}
    want_tc = ref.cooling_time(rho_h, u_h, chem_h, model, evolve)
    got_dt = float(limiter(rho, u, chem, cooling_cfg))
    out["dt_cool"] = {
        "program": got_dt, "reference": float(model["ct_crit"] * want_tc.min()),
    }
    out["dt_cool"]["rel_err"] = abs(
        got_dt / out["dt_cool"]["reference"] - 1.0)
    for name, dt in dts.items():
        du, new = source(np.float32(dt), rho, u, chem, cooling_cfg)
        du_h = host(du)
        new_h = {k: host(getattr(new, k)) for k in CHEM_FIELDS}
        diff_h = host(differenced(np.float32(dt), rho, u, chem, cooling_cfg))
        sound, control, bf16, scheme, frac, frac16 = [], [], [], [], [], []
        for rows in groups:
            if not rows.size:
                continue
            r, e, c = at(rows)
            want, wfrac, _ = ref.step(dt, r, e, c, model,
                                      evolve_species=evolve)
            low, lfrac, _ = ref.step(dt, r, e, c, model,
                                     round_to=ml_dtypes.bfloat16,
                                     evolve_species=evolve)
            sound.append(ref.rel_errors(du_h[rows], want))
            control.append(ref.rel_errors(diff_h[rows], want))
            bf16.append(ref.rel_errors(low, want))
            frac.append(ref.fraction_errors(
                {k: v[rows] for k, v in new_h.items()}, wfrac, model))
            frac16.append(ref.fraction_errors(lfrac, wfrac, model))
            if converged_substeps:
                fine, _, _ = ref.step(dt, r, e, c, model,
                                      substeps=converged_substeps,
                                      evolve_species=evolve)
                scheme.append(ref.rel_errors(want, fine))
        out["dt"][name] = {
            "dt": float(dt), "sound": _worst(sound),
            "differenced_control": _worst(control),
            "bf16_control": _worst(bf16), "fractions": max(frac),
            "bf16_fractions": max(frac16),
            "du_cool_rms": [float(np.sqrt(np.mean(np.square(du_h[g]))))
                            for g in groups if g.size],
            "finite": bool(np.all(np.isfinite(du_h))),
        }
        if scheme:
            out["dt"][name]["scheme"] = _worst(scheme)
    # the program has no per-particle cooling time of its own (its limiter
    # hands back the min): its source at dt 1e-12 is the rate as it stands
    rows = np.concatenate(groups)
    got_tc = np.abs(u_h[rows] / np.minimum(
        host(source(np.float32(1e-12), rho, u, chem, cooling_cfg)[0])[rows],
        -1e-300))
    out["cooling_time"] = float(np.max(np.abs(got_tc / want_tc[rows] - 1.0)))
    return out


def judge(result, g, refuse_differenced=("step", "courant"),
          refuse_bf16=("step", "courant", "long")):
    """(within_bounds, controls_refused) of ``compare``'s result under the
    guarantees ``g``. A source is inside when both its errors are under
    ``cooling_rel_rms_max`` / ``cooling_rel_max``; the sound reading must
    also keep the fractions, the cooling time and the limiter inside
    theirs. A control is refused when ONE limit refuses it."""
    inside = lambda r: (r[0] < g["cooling_rel_rms_max"]
                        and r[1] < g["cooling_rel_max"])
    dts = result["dt"]
    within = (all(inside(d["sound"]) and d["finite"]
                  and d["fractions"] < g["cooling_fraction_abs_max"]
                  for d in dts.values())
              and result["cooling_time"] < g["cooling_dt_rel_max"]
              and result["dt_cool"]["rel_err"] < g["cooling_dt_rel_max"])
    refused = (all(not inside(dts[k]["differenced_control"])
                   for k in refuse_differenced if k in dts)
               and all(not inside(dts[k]["bf16_control"])
                       or dts[k]["bf16_fractions"]
                       >= g["cooling_fraction_abs_max"]
                       for k in refuse_bf16 if k in dts))
    return within, refused


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--side", type=int, default=None,
                    help="CPU rehearsal at this tiny side (no device number)")
    args = ap.parse_args(argv)

    if args.side:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run  # sibling

    bench, cell, config, traffic = run.load_cell(args.workload)
    g = config["guarantees"]
    if "cooling_rel_rms_max" not in g:
        raise SystemExit(f"{config['init']} states no cooling guarantee")
    if args.side:
        import rehearse_lists_cpu

        rehearse_lists_cpu.steer_auto_to_pallas()
        config = {**config, "side": args.side}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        dev = require_tpu("benchmarks/check_cooling.py")
        platform = dev.platform
        enable_compile_cache()

    # run_cell keeps its Simulation to itself: take it as it is built
    built = []
    build = run.build_simulation

    def build_and_keep(*a, **kw):
        built.append(build(*a, **kw))
        if args.side:  # the rehearsal's particle count is the side's own
            config["particles"] = int(built[-1][0].state.n)
        return built[-1]

    run.build_simulation = build_and_keep
    out_dir = os.path.join(HERE, "out", "cooling-" + cell["name"])
    rec = run.run_cell(cell, config, traffic, args.seed, args.seconds,
                       False, out_dir, run.Spans())
    sim, const = built[-1]
    w = rec["window"]
    for ok, what in rec["checks"]:
        print(f"# [{'PASS' if ok else 'FAIL'}] {what}")
    print(f"# {cell['name']}: platform={platform} particles="
          f"{rec['particles']} cycles={w['cycles']} steps="
          f"{w['steps_completed']} attempted={w['attempted']} engine="
          f"{json.dumps(rec['engine'])}")
    # the same run as run.py's, so its end-to-end numbers count as a seed's
    rates = run.read_metrics(
        run.metrics_of(bench, "end_to_end", cell["name"]), "end_to_end", rec)
    print(f"# end to end ({platform}): " + json.dumps(
        {k: v["value"] for k, v in rates.items()} if not args.side
        else sorted(rates)))

    rho, u, chem, dt_courant = system_inputs(sim, const)
    dts = {"step": float(sim.state.min_dt), "courant": dt_courant,
           "long": DT_LONG}
    result = compare(rho, u, chem, sim.cooling_cfg, config["cooling"],
                     args.seed, g["cooling_targets"], dts,
                     converged_substeps=4096)
    within, refused = judge(result, g)
    lists = sim.pair_lists
    result.update(
        cell=cell["name"], platform=platform, seed=args.seed,
        particles=int(sim.state.n), iteration=sim.iteration,
        correct=all(c for c, _ in rec["checks"]),
        evolve_species=sim.cooling_cfg.evolve_species,
        limits={k: g[k] for k in g if k.startswith("cooling_")
                and k != "cooling_why"},
        resident={
            "lane_table": 0 if lists is None else int(lists.gidx.nbytes),
            "slot_cap": 0 if lists is None else int(lists.slot_cap),
            "slots_cap": 0 if lists is None else int(lists.slots_cap),
            "slots_live": 0 if lists is None else int(lists.slots_live),
            "memory_peak_bytes": rec["memory_peak_bytes"]},
        within_bounds=within, controls_refused=refused)
    print(json.dumps(result))
    return 0 if (result["correct"] and within and refused) else 1


if __name__ == "__main__":
    sys.exit(main())
