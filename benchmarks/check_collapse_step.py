"""The coupled step ``std-cooling`` computes under self-gravity against the
plain reference, on the live state after a cell's traffic, with the controls
its limits must refuse.

    python3 benchmarks/check_collapse_step.py --workload evrard-cooling-1m.steady --seed <n> [--seconds 30] [--side 12]

A builder's script, not a metric: it runs the cell exactly as run.py does
(the same ``run_cell``: initialiser, ``Simulation`` as ``main()`` builds it,
warm-up, the traffic's check windows for ``--seconds``, ``correct``) and
then, outside any clock, makes on the LIVE particle state, chemistry and
configuration the calls the next step makes: ``propagator.std_forces`` WITH
the live gravity tree and ``aux=chem`` (sort, cell ranges, the three std
pair ops, the tree solve), once more without gravity (so the tree solve's
part is the difference), ``cooling.cool_timestep``, ``compute_timestep`` and
``cooling.cool_step``. It compares them with reference_collapse_step.py at
seeded targets of the core (``rho`` > 3) and as many of the envelope, and
the cooling calls alone with reference_cooling.py through
``check_cooling.compare`` (4,096 targets a group, at the case's ``minDt``
where the window starts, the step's own dt and 1e-2).

Exit 0 only if the run is ``correct``, every sound reading is inside the
limits the configuration states (``forces_rel_max``, ``gravity_rel_*``,
``cooling_*``) AND every control is refused where the configuration says it
must be: the cooling source dropped from ``du``, gravity dropped from the
acceleration, the reference's kernel values and its cooling rounded to bf16
(every dt), the differenced form ``(u_final - u) / dt`` (the dt named in
``cooling_refuse_differenced``). It fails without a TPU, like run.py;
``--side <n>`` instead rehearses it on the CPU at a tiny size with the
streamed engine interpreted, and then prints no device number.

``system_step``, ``compare`` and ``judge`` are what the tier-1 tests call
too (tests/test_collapse_cooling_reference.py), so the chip and the CPU tier
make the same comparison.
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


@functools.lru_cache(maxsize=None)
def _programs():
    """The jitted calls, built once per process (the program is imported
    late: this module loads before the platform is chosen)."""
    import jax

    from sphexa_tpu.physics.cooling import cool_step, cool_timestep
    from sphexa_tpu.propagator import std_forces
    from sphexa_tpu.sph.timestep import compute_timestep

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def forces(state, box, cfg, gtree, chem):
        (state, _, ax, ay, az, du, dt_courant, extra_dts, _, _, rho, _, _,
         chem) = std_forces(state, box, cfg, gtree, aux=chem)
        return state, chem, {"rho": rho, "ax": ax, "ay": ay, "az": az,
                             "du": du}, dt_courant, extra_dts

    @functools.partial(jax.jit, static_argnames=("cfg", "const"))
    def cooling(state, rho, chem, dt_courant, extra_dts, cfg, const):
        u = const.cv * state.temp
        dt_cool = cool_timestep(rho, u, chem, cfg)
        dt = compute_timestep(state.min_dt, dt_courant, dt_cool, *extra_dts,
                              const=const)
        du_cool, new = cool_step(dt, rho, u, chem, cfg)
        return u, dt_cool, dt, du_cool, new

    return forces, cooling


def system_step(sim, const):
    """What the next step would compute, on ``sim``'s live state under its
    live configuration and gravity tree: the sorted state and chemistry,
    ``total`` = {rho, ax, ay, az, du} with gravity in the acceleration,
    ``hydro`` the same stage without gravity, and the step's candidates,
    dt, cooling source and advanced chemistry."""
    forces, cooling = _programs()
    cfg = sim.active_cfg
    state, chem, total, dt_courant, extra_dts = forces(
        sim.state, sim.box, cfg, sim._gtree, sim.chem)
    bare = dataclasses.replace(cfg, gravity=None, grav_meta=None)
    _, _, hydro, _, _ = forces(sim.state, sim.box, bare, None, sim.chem)
    u, dt_cool, dt, du_cool, new = cooling(
        state, total["rho"], chem, dt_courant, extra_dts, sim.cooling_cfg,
        const)
    cands = {"growth": float(const.max_dt_increase * state.min_dt),
             "courant": float(dt_courant), "cool": float(dt_cool)}
    if extra_dts:
        cands["accel"] = float(extra_dts[0])
    return {"state": state, "chem": chem, "new_chem": new, "total": total,
            "hydro": hydro, "u": u, "du_cool": du_cool, "dt": float(dt),
            "candidates": cands}


def reference_constants(const):
    return {"gamma": const.gamma, "cv": const.cv,
            "sinc_index": const.sinc_index, "g": const.g,
            "k_cour": const.k_cour, "eta_acc": const.eta_acc,
            "eps": const.eps, "max_dt_increase": const.max_dt_increase}


def _over_rms(got, want):
    """(rms, p99) of the error of 3-vectors over the sample's rms |want|: a
    target where pressure balances gravity has a small |a| of its own, and
    an error that is not small beside it."""
    import numpy as np

    err = np.sqrt(sum((g - w) ** 2 for g, w in zip(got, want)))
    scale = np.sqrt(np.mean(sum(w ** 2 for w in want)))
    return [float(np.sqrt(np.mean(err ** 2)) / scale),
            float(np.percentile(err, 99) / scale)]


def compare(step, const, model, seed, count, evolve_species=True,
            chem_for_reference=None, block=64, product_dtype=None):
    """The system's step (``system_step``'s result) against
    reference_collapse_step at ``count`` seeded targets of each group (every
    particle where ``count`` is None: the reference then takes its own dt;
    of a sample its minima bound the step's from above, so it is handed the
    step's dt). ``chem_for_reference`` replaces the chemistry the reference
    is handed (the mis-alignment control), ``product_dtype`` rounds its
    kernel values (the lower-precision control of the hydro part). The
    reference takes the particles from the host and computes on jax's
    default device: the caller's to choose (``main``'s control).

    ``hydro``: reference_sph_std.errors of the gravity-free stage;
    ``gravity``: (rel rms, rel p99) per target of the tree's part against the
    direct sum; ``acceleration``: (rms, p99) of the total's error over the
    sample's rms |a|; ``du``: (rel rms, rel max) of ``du + du_cool`` over the
    reference's rms; ``fractions``; ``dt``; and the two controls:
    ``du_without_cooling`` and ``acceleration_without_gravity``, the same
    errors with the part left out of what the system is read as."""
    import numpy as np

    import check_cooling
    import reference
    import reference_collapse_step as ref
    import reference_cooling
    import reference_sph_std

    host = lambda a: np.asarray(a, np.float64)
    s = step["state"]
    rho = host(step["total"]["rho"])
    if count is None:
        rows = np.arange(rho.size)
    else:
        # check_cooling's two groups fit: its split, rho 3, is r < 0.053 of
        # Evrard's rho = 1 / (2 pi r): the core, and as many of the envelope
        rows = np.sort(np.concatenate(
            check_cooling.draw_targets(rho, seed, count)))
    core = rho[rows] > check_cooling.RHO_CLOUD
    chem = chem_for_reference or {k: getattr(step["chem"], k)
                                  for k in check_cooling.CHEM_FIELDS}
    want = ref.collapse_step(
        rows, *(np.asarray(a) for a in (s.x, s.y, s.z, s.vx, s.vy, s.vz, s.h,
                                        s.m, s.temp)), chem,
        const=reference_constants(const), model=model,
        dt_last=float(s.min_dt), dt=None if count is None else step["dt"],
        block=block, evolve_species=evolve_species,
        product_dtype=product_dtype)

    at = lambda a: host(a)[rows]
    hydro = {k: at(v) for k, v in step["hydro"].items()}
    total = {k: at(v) for k, v in step["total"].items()}
    want_hydro = {"rho": want["rho"], "ax": want["ax_hydro"],
                  "ay": want["ay_hydro"], "az": want["az_hydro"],
                  "du": want["du_hydro"]}
    axes = ("ax", "ay", "az")
    tree = [total[k] - hydro[k] for k in axes]
    want_g = [want["g" + k] for k in "xyz"]
    want_a = [want[k] for k in axes]
    du = total["du"] + at(step["du_cool"])
    new = {k: at(getattr(step["new_chem"], k))
           for k in check_cooling.CHEM_FIELDS}

    cands = step["candidates"]
    limiter = min((k for k in ref.CANDIDATES if k in cands),
                  key=lambda k: cands[k])
    out = {
        "targets": int(rows.size), "core_targets": int(core.sum()),
        "particles": int(rho.size), "ring_a": want["ring_a"],
        "ring_b": want["ring_b"],
        "finite": all(bool(np.all(np.isfinite(want[k])))
                      for k in ("rho", "ax", "ay", "az", "du", "du_cool")),
        "hydro": reference_sph_std.errors(hydro, want_hydro),
        "gravity": list(reference.vector_rel_error(tree, want_g)),
        "acceleration": _over_rms([total[k] for k in axes], want_a),
        "du": list(reference_cooling.rel_errors(du, want["du"])),
        "fractions": reference_cooling.fraction_errors(
            new, want["fractions"], model),
        "du_without_cooling": list(reference_cooling.rel_errors(
            total["du"], want["du"])),
        "acceleration_without_gravity": _over_rms(
            [hydro[k] for k in axes], want_a),
        "du_cool_share": float(np.sqrt(np.mean(want["du_cool"] ** 2)
                                       / np.mean(want["du"] ** 2))),
        "dt": {"program": step["dt"],
               "reference": want["candidates"][want["limiter"]],
               "limiter": limiter, "reference_limiter": want["limiter"],
               "candidates": cands,
               "reference_candidates": want["candidates"],
               # a sample's minima are upper bounds of the step's
               "sampled": count is not None},
    }
    return out


def judge(result, g):
    """(within_bounds, controls_refused) of ``compare``'s result under the
    guarantees ``g``: the gravity-free stage inside ``forces_rel_max``, the
    tree's part and the total acceleration inside ``gravity_rel_*``, ``du``
    with the source in it inside the sum of the two ``du`` limits, the
    fractions inside theirs, the step's dt the reference's and set by the
    same candidate (of a sample the reference's ``courant`` / ``cool`` /
    ``accel`` are upper bounds: there ``growth`` must agree, the others may
    only be smaller, and the step's dt is the smallest of its own). A
    control is refused when the limit of the part it drops refuses it."""
    f = g["forces_rel_max"]
    h = result["hydro"]
    grav = lambda r: (r[0] < g["gravity_rel_rms_max"]
                      and r[1] < g["gravity_rel_p99_max"])
    du_max = f["du"] + g["cooling_rel_max"]
    dt = result["dt"]
    tol = g["cooling_dt_rel_max"]
    near = lambda a, b: abs(a / b - 1.0) < tol
    both = [(dt["candidates"][k], v)
            for k, v in dt["reference_candidates"].items()
            if k in dt["candidates"]]
    if dt["sampled"]:
        dt_ok = (near(dt["candidates"]["growth"],
                      dt["reference_candidates"]["growth"])
                 and all(a <= b * (1.0 + tol) for a, b in both)
                 and dt["program"] == min(dt["candidates"].values()))
    else:
        dt_ok = (dt["limiter"] == dt["reference_limiter"]
                 and near(dt["program"], dt["reference"])
                 and all(near(a, b) for a, b in both))
    within = (result["finite"] and h["rho_rel_max"] < f["rho"]
              and h["acc_rel_rms"] < f["acc_rms"]
              and h["acc_rel_max"] < f["acc_max"]
              and h["du_rel_max"] < f["du"]
              and grav(result["gravity"]) and grav(result["acceleration"])
              and result["du"][1] < du_max
              and result["fractions"] < g["cooling_fraction_abs_max"]
              and dt_ok)
    refused = (result["du_without_cooling"][1] >= du_max
               and not grav(result["acceleration_without_gravity"]))
    return bool(within), bool(refused)


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
    import check_cooling
    import run  # sibling

    bench, cell, config, traffic = run.load_cell(args.workload)
    g = config["guarantees"]
    if "forces_rel_max" not in g or "cooling_rel_rms_max" not in g:
        raise SystemExit(f"{config['init']} states no coupled-step guarantee")
    if args.side:
        import rehearse_lists_cpu

        rehearse_lists_cpu.steer_auto_to_pallas()
        config = {**config, "side": args.side}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        platform = require_tpu("benchmarks/check_collapse_step.py").platform
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
    out_dir = os.path.join(HERE, "out", "collapse-" + cell["name"])
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

    step = system_step(sim, const)
    evolve = sim.cooling_cfg.evolve_species
    import jax
    import ml_dtypes

    half = g["forces_targets"] // 2
    result = compare(step, const, config["cooling"], args.seed, half,
                     evolve_species=evolve)
    within, refused = judge(result, g)
    # one precision down in the hydro part: the reference's kernel values
    # rounded to bf16 must read outside ``forces_rel_max``. On the host's
    # CPU backend: the chip's compiler takes the f32 -> bf16 -> f32 round
    # trip out as excess precision, and the control then reads the sound
    # reference's numbers to the digit (PR 38's first two chip runs). At an
    # eighth of the targets: all pairs of their rings with 1.1M rows take
    # the host minutes, and a refusal by a factor of a hundred needs no more
    with jax.default_device(jax.devices("cpu")[0]):
        low = compare(step, const, config["cooling"], args.seed,
                      max(half // 8, 1), evolve_species=evolve,
                      product_dtype=ml_dtypes.bfloat16)
    result["hydro_bf16_control"] = low["hydro"]
    refused = refused and not judge(dict(result, hydro=low["hydro"]), g)[0]
    # the window's dt: from the case's minDt (its first step) to the step's
    # own; and 1e-2, where the differenced form resolves the source
    dts = {"ramp": float(config["evrard"]["minDt"]), "step": step["dt"],
           "long": check_cooling.DT_LONG}
    cool = check_cooling.compare(
        step["total"]["rho"], step["u"], step["chem"], sim.cooling_cfg,
        config["cooling"], args.seed, g["cooling_targets"], dts,
        converged_substeps=4096)
    cool_within, cool_refused = check_cooling.judge(
        cool, g, refuse_differenced=tuple(g["cooling_refuse_differenced"]),
        refuse_bf16=tuple(dts))
    result.update(
        cell=cell["name"], platform=platform, seed=args.seed,
        iteration=sim.iteration, evolve_species=evolve,
        correct=all(c for c, _ in rec["checks"]),
        e_cool=sim.e_cool, energy_drift=sim.energy_drift,
        memory_peak_bytes=rec["memory_peak_bytes"], cooling=cool,
        within_bounds=within and cool_within,
        controls_refused=refused and cool_refused)
    print(json.dumps(result))
    return 0 if (result["correct"] and result["within_bounds"]
                 and result["controls_refused"]) else 1


if __name__ == "__main__":
    sys.exit(main())
