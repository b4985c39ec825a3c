"""The stirring the step computes against the plain reference, on the live
state after a cell's traffic, with the control its limits must refuse.

    python3 benchmarks/check_stirring.py --workload turb-ve-8m.steady --seed <n> [--seconds 30] [--side 29]

A builder's script, not a metric: it runs the cell exactly as run.py does
(the same ``run_cell``: initialiser, ``Simulation`` as ``main()`` builds it,
warm-up, the traffic's check windows for ``--seconds``, ``correct``) and
then, outside any clock, makes on the LIVE particle state and turb state the
call the step makes (``hydro_turb.compute_phases`` and
``hydro_turb.st_calc_accel``, jitted, over all the particles) and compares
it at seeded targets with ``reference_stirring.py``: float64, one particle's
loop over the modes. It also takes one more OU step of the live turb state
both ways on the same draws.

Exit 0 only if the run is ``correct``, the sound reading is inside the
configuration's ``stirring_rel_rms_max`` / ``stirring_rel_max`` AND the
control, the reference with every product's operands rounded to bf16 (what
an f32 matmul at a TPU's default precision computes), is refused by them.
It fails without a TPU, like run.py; ``--side <n>`` instead rehearses it on
the CPU at a tiny size with the list engine interpreted, and then prints no
device number.

``compare`` is what the tier-1 tests' comparison is made of too
(tests/test_turbulence_reference.py uses the same reference, limits and
``rel_errors``), so the chip and the CPU tier make the same comparison.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]


def compare(sim, seed, count):
    """The program's stirring on ``sim``'s live state against the reference
    at ``count`` seeded targets: ``sound`` and ``bf16_control`` as (rel_rms,
    rel_max) of the acceleration vector over the reference's rms magnitude,
    and the next OU step of the live phases against the reference on the
    same draws."""
    import jax
    import ml_dtypes
    import numpy as np

    import reference
    import reference_stirring as ref
    from sphexa_tpu.sph import hydro_turb

    cfg, turb, s = sim.turb_cfg, sim.turb_state, sim.state

    @jax.jit
    def program(x, y, z, turb):
        pr, pi = hydro_turb.compute_phases(turb, cfg)
        return hydro_turb.st_calc_accel(x, y, z, turb, cfg, pr, pi)

    targets = reference.seeded_targets(seed, int(s.n), count)
    got = [np.asarray(a)[targets] for a in program(s.x, s.y, s.z, turb)]
    xyz = [np.asarray(a)[targets] for a in (s.x, s.y, s.z)]
    pr, pi = ref.compute_phases(turb.modes, turb.phases, cfg.sol_weight)
    accel = lambda dtype=None: ref.stir_accel(
        *xyz, turb.modes, turb.amplitudes, pr, pi, cfg.sol_weight_norm,
        operand_dtype=dtype)
    sound = accel()

    dt = s.min_dt
    z, key = ref.system_draws(turb.key, turb.phases.shape, turb.phases.dtype)
    stepped = hydro_turb.update_noise(turb, dt, cfg)
    want = ref.update_noise(turb.phases, z, float(dt), cfg.decay_time,
                            cfg.variance)
    return {
        "sound": list(ref.rel_errors(got, sound)),
        "bf16_control": list(ref.rel_errors(accel(ml_dtypes.bfloat16),
                                            sound)),
        "targets": len(targets), "modes": int(turb.modes.shape[0]),
        "particles": int(s.n),
        "accel_rms": float(np.sqrt(np.mean(sum(a * a for a in sound)))),
        "kdotx_max": float(np.max(np.abs(
            np.asarray(turb.modes, np.float64) @ np.stack(xyz)))),
        "finite": bool(np.all(np.isfinite(np.stack(got)))),
        "ou_dt": float(dt),
        "ou_key_equal": bool(np.array_equal(np.asarray(stepped.key),
                                            np.asarray(key))),
        "ou_phase_err": float(np.abs(np.asarray(stepped.phases) - want).max()
                              / np.abs(want).max()),
    }


def resident(sim, rec):
    """What holds the device's memory after the traffic, in bytes by owner
    (array sizes; a count of the program's own arrays, not the allocator's
    view, which is ``memory_peak_bytes`` and the last ``memory`` event)."""
    import jax

    nbytes = lambda tree: int(sum(
        a.nbytes for a in jax.tree.leaves(tree) if hasattr(a, "nbytes")))
    lists = sim._lists
    memory = [e for e in rec["events"] if e["kind"] == "memory"]
    return {
        "state": nbytes(sim.state), "turb_state": nbytes(sim.turb_state),
        "lists": nbytes(lists),
        "lane_table": 0 if lists is None else int(lists.gidx.nbytes),
        "slot_cap": 0 if lists is None else int(lists.slot_cap),
        "slots_cap": 0 if lists is None else int(lists.slots_cap),
        "slots_live": 0 if lists is None else int(lists.slots_live),
        "bytes_in_use": memory[-1]["bytes_in_use"] if memory else None,
        "memory_peak_bytes": rec["memory_peak_bytes"],
    }


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
    if "stirring_rel_rms_max" not in g:
        raise SystemExit(f"{config['init']} states no stirring guarantee")
    if args.side:
        import rehearse_lists_cpu

        rehearse_lists_cpu.steer_auto_to_pallas()
        config = {**config, "side": args.side, "particles": args.side ** 3}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        dev = require_tpu("benchmarks/check_stirring.py")
        platform = dev.platform
        enable_compile_cache()

    # run_cell keeps its Simulation to itself: take it as it is built
    built = []
    build = run.build_simulation

    def build_and_keep(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]

    run.build_simulation = build_and_keep
    out_dir = os.path.join(HERE, "out", "stirring-" + cell["name"])
    rec = run.run_cell(cell, config, traffic, args.seed, args.seconds,
                       False, out_dir, run.Spans())
    sim, _ = built[-1]
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

    result = compare(sim, args.seed, g["stirring_targets"])
    result["resident"] = resident(sim, rec)
    rms_max, rel_max = g["stirring_rel_rms_max"], g["stirring_rel_max"]
    inside = lambda r: r[0] < rms_max and r[1] < rel_max
    # f32 against f64 on the same draws: a few ulp of the largest phase
    ou_ok = result["ou_key_equal"] and result["ou_phase_err"] < 1e-6
    result.update(
        cell=cell["name"], platform=platform, seed=args.seed,
        correct=all(c for c, _ in rec["checks"]), iteration=sim.iteration,
        rms_max=rms_max, rel_max=rel_max,
        within_bounds=inside(result["sound"]) and result["finite"] and ou_ok,
        control_refused=not inside(result["bf16_control"]))
    print(json.dumps(result))
    return 0 if (result["correct"] and result["within_bounds"]
                 and result["control_refused"]) else 1


if __name__ == "__main__":
    sys.exit(main())
