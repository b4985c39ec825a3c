"""The stirred VE step ON A MESH against the plain references, on the live
mesh state after a cell's traffic, with the controls its limits must refuse.

    python3 benchmarks/check_turb_mesh.py --workload turb-ve-8m-x4.steady --seed <n> [--seconds 30] [--one-chip-record <file>] [--side 12]
    python3 benchmarks/check_turb_mesh.py --workload turb-ve-8m-x4.steady --one-chip <steps> --out <file> [--side 12]

A builder's script, not a metric. The first form runs the cell exactly as
run.py does (the same ``run_cell``: initialiser, ``Simulation`` as ``main()``
builds it on the cell's chips, warm-up, the traffic's check windows for
``--seconds``, ``correct``) and then, outside any clock, on the LIVE mesh
state, turb state and configuration (``Simulation.active_cfg``: the sharded
stepper's, with its mesh and halo caps):

- ``compare_forces``: the step's own VE force stage (``propagator._ve_forces``
  as ``_step_turb_ve`` calls it: the global sort, then ``_ve_forces_sharded``,
  the five streamed pair ops under ``shard_map`` with their five serve rounds
  of a periodic halo) against ``reference_sph_ve.py``'s all-pairs rings at
  ``forces_ve_targets`` seeded targets, errors as ``reference_sph_std.errors``
  defines them, under ``forces_ve_rel_max``; the control, the reference with
  every kernel value rounded to bf16 on its bits, must be refused;
- check_stirring.py's ``compare``, unedited: ``hydro_turb``'s stirring on the
  sharded rows against ``reference_stirring.py`` (float64) under
  ``stirring_rel_rms_max`` / ``stirring_rel_max``, its bf16 control refused,
  the next OU step on the same draws;
- the OU phases after the window against a one-chip run of the same steps
  from the same ``rngSeed`` (``--one-chip-record``: what the second form
  wrote). The turb state is replicated and the step's dt is a global
  minimum, so where both runs took the same dt at every iteration the
  phases have to be EQUAL TO THE BIT; where a dt differs (the two runs' pair
  sums run in another order, so a Courant-limited dt can differ in its last
  bits) the phases are held to ``OU_REL_MAX`` and the first such iteration
  is named.

Exit 0 only if the run is ``correct``, every sound reading is inside its
limit and every control is refused. It fails without a TPU or with fewer
chips than the cell asks for, like run.py; ``--side <n>`` instead rehearses
it on virtual CPU devices at a tiny size with the engine interpreted, and
then prints no device number.

The second form runs the SAME configuration on one device for ``<steps>``
steps and writes the OU phases, the PRNG key and dt by iteration to
``--out``.

``system_forces``, ``compare_forces``, ``record_one_device``,
``against_one_chip`` and ``judge`` are what the tier-1 tests call too (tests/test_turb_mesh_cell.py,
tests/test_ve_reference.py), so the chip and the CPU tier make the same
comparison.
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

#: mesh against one chip where a dt differed: largest |phase difference| over
#: the largest |phase|. A dt off by an ulp moves a phase by ~1e-7 of its
#: noise term a step; a draw from another key, a skipped or a doubled step
#: moves it by the OU variance itself (order 1)
OU_REL_MAX = 1e-4
#: rows of an all-pairs block at the timed size: 8.0M sources a row
CHIP_BLOCK_ROWS = 8


@functools.lru_cache(maxsize=None)
def _force_program():
    """The jitted call, built once per process (the program is imported
    late: this module loads before the platform is chosen)."""
    import jax

    from sphexa_tpu.propagator import _ve_forces

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def forces(state, box, cfg, lists):
        (state, _, ax, ay, az, du, _, alpha, nc, _, rho, _, _) = _ve_forces(
            state, box, cfg, None, lists=lists)
        return state, {"rho": rho, "alpha": alpha, "ax": ax, "ay": ay,
                       "az": az, "du": du}, nc

    return forces


def system_forces(sim):
    """What the next step's force stage would compute, on ``sim``'s live
    state under its live configuration (and its live pair lists, where a
    one-device run walks them): the state in the stage's order (host
    arrays), the stage's ``{rho, alpha, ax, ay, az, du}`` and the neighbour
    counts."""
    import numpy as np

    state, got, nc = _force_program()(sim.state, sim.box, sim.active_cfg,
                                      sim.pair_lists)
    fields = ("x", "y", "z", "vx", "vy", "vz", "h", "m", "temp", "alpha")
    host = {k: np.asarray(getattr(state, k)) for k in fields}
    host["dt"] = float(state.min_dt)
    return host, {k: np.asarray(v) for k, v in got.items()}, np.asarray(nc)


def compare_forces(sim, const, targets, *, block=64, product_dtype=None,
                   stage=None):
    """The force stage of ``sim`` against ``reference_sph_ve.ve_forces`` at
    the particle indices ``targets`` (of the SORTED state): ``errors`` as
    ``reference_sph_std.errors`` defines them plus the switches' largest
    absolute error, the rings' sizes and the samples' scales. ``stage``: a
    ``system_forces`` result to reuse."""
    import numpy as np

    import reference_sph_std
    import reference_sph_ve
    from sphexa_tpu.sfc.box import BoundaryType

    host, got, nc = stage or system_forces(sim)
    targets = np.asarray(targets)
    box = sim.box
    ref = reference_sph_ve.ve_forces(
        targets, *(host[k] for k in ("x", "y", "z", "vx", "vy", "vz", "h",
                                     "m", "temp", "alpha")), host["dt"],
        lengths=np.asarray(box.lengths),
        periodic=tuple(b == BoundaryType.periodic for b in box.boundaries),
        gamma=const.gamma, cv=const.cv, sinc_index=const.sinc_index,
        block=block, product_dtype=product_dtype, at_min=const.at_min,
        at_max=const.at_max, alphamin=const.alphamin,
        alphamax=const.alphamax, decay_constant=const.decay_constant)
    at = {k: v[targets] for k, v in got.items()}
    err = reference_sph_std.errors(at, ref)
    err["alpha_abs_max"] = float(np.max(np.abs(
        at["alpha"].astype(np.float64) - ref["alpha"])))
    # targets whose support crosses a periodic face: they see images
    lo, span = np.asarray(box.lo), np.asarray(box.lengths)
    xyz = np.stack([host[k][targets] for k in ("x", "y", "z")], axis=1)
    reach = 2.0 * host["h"][targets][:, None]
    crossing = np.any((xyz - lo < reach) | (lo + span - xyz < reach), axis=1)
    return {
        "errors": err, "targets": int(len(targets)),
        "face_targets": int(np.sum(crossing)),
        "finite": bool(all(np.all(np.isfinite(ref[k])) and
                           np.all(np.isfinite(at[k])) for k in at)),
        "rings": [int(ref[k]) for k in ("ring_a", "ring_b", "ring_c",
                                        "ring_d")],
        "nc_mean_targets": float(np.mean(nc[targets])),
        "acc_rms": float(np.sqrt(np.mean(sum(
            ref[k].astype(np.float64) ** 2 for k in ("ax", "ay", "az"))))),
        "du_rms": float(np.sqrt(np.mean(ref["du"].astype(np.float64) ** 2))),
        "alpha_range": [float(ref["alpha"].min()), float(ref["alpha"].max())],
        "dt": host["dt"],
    }


def forces_inside(errors, limits):
    """Whether ``compare_forces``'s errors lie inside ``forces_ve_rel_max``."""
    return bool(errors["rho_rel_max"] < limits["rho"]
                and errors["acc_rel_rms"] < limits["acc_rms"]
                and errors["acc_rel_max"] < limits["acc_max"]
                and errors["du_rel_max"] < limits["du"])


def _bits(a):
    """A float32 / uint32 array as a list of ints: exact in JSON."""
    import numpy as np

    a = np.ascontiguousarray(np.asarray(a))
    return a.view(np.uint32).reshape(-1).tolist()


def dt_by_iteration(events):
    """{iteration: dt} of every verified step, from ``physics`` events."""
    return {int(it): float(dt) for e in events if e["kind"] == "physics"
            for it, dt in zip(e["its"], e["dt"])}


def ou_record(sim, events):
    """The live OU state of ``sim`` with the run's dt by iteration."""
    turb = sim.turb_state
    return {"iteration": int(sim.iteration), "phases": _bits(turb.phases),
            "key": _bits(turb.key),
            "dt": {str(k): v for k, v in dt_by_iteration(events).items()}}


def record_one_device(sim, sink, steps):
    """``sim`` (one device, ``sink`` its telemetry's MemorySink) driven for
    ``steps`` steps in its check windows: the OU state after every step and
    the verified dt by iteration. A rollback would replay steps whose state
    was already recorded: the record counts them, and is then no record."""
    by_it = {}
    while sim.iteration < steps:
        sim.step()
        by_it[str(sim.iteration)] = {"phases": _bits(sim.turb_state.phases),
                                     "key": _bits(sim.turb_state.key)}
    sim.flush()
    return {"particles": int(sim.state.n), "iteration": sim.iteration,
            "rollbacks": sum(e["kind"] == "rollback" for e in sink.events),
            "by_iteration": by_it,
            "dt": {str(k): v for k, v in
                   dt_by_iteration(sink.events).items()},
            "energy_drift": sim.energy_drift}


def one_chip(config, traffic, steps, out):
    """The configuration on ONE device for ``steps`` steps in the traffic's
    check windows; ``record_one_device``'s record to ``out``."""
    import run
    from sphexa_tpu.telemetry.sinks import MemorySink

    sink = MemorySink()
    sim, _ = run.build_simulation({**config, "devices": 1}, traffic, sink)
    record = record_one_device(sim, sink, steps)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f)
    print(json.dumps({k: record[k] for k in
                      ("particles", "iteration", "rollbacks",
                       "energy_drift")}))
    return 0 if not record["rollbacks"] else 1


def against_one_chip(mesh, record):
    """The mesh run's OU state (``ou_record``) against the one-chip
    record's at the mesh's iteration: bit equality of phases and key, the
    largest phase difference over the largest phase, and up to which
    iteration the two runs took the same dt to the bit."""
    import numpy as np

    it = str(mesh["iteration"])
    if it not in record["by_iteration"]:
        return {"common": False, "iteration": mesh["iteration"],
                "one_chip_iterations": len(record["by_iteration"])}
    one = record["by_iteration"][it]
    f32 = lambda bits: np.asarray(bits, np.uint32).view(np.float32)
    a, b = f32(mesh["phases"]), f32(one["phases"])
    common = sorted(set(mesh["dt"]) & set(record["dt"]), key=int)
    differ = [int(k) for k in common
              if np.float32(mesh["dt"][k]) != np.float32(record["dt"][k])]
    return {
        "common": True, "iteration": mesh["iteration"],
        "rollbacks": record.get("rollbacks", 0),
        "phases_equal": bool(mesh["phases"] == one["phases"]),
        "key_equal": bool(mesh["key"] == one["key"]),
        "phase_rel_err": float(np.abs(a.astype(np.float64) - b).max()
                               / np.abs(b).max()),
        "dt_compared": len(common),
        "dt_first_differs": differ[0] if differ else None,
        "dt_rel_max": max((abs(mesh["dt"][k] / record["dt"][k] - 1.0)
                           for k in common), default=None),
    }


def ou_inside(ou):
    """The OU comparison's verdict (module docstring)."""
    if "common" not in ou:  # no one-chip record was handed in
        return True
    if not ou["common"] or not ou["key_equal"] or ou.get("rollbacks"):
        return False
    if ou["dt_first_differs"] is None:
        return ou["phases_equal"]
    return ou["phase_rel_err"] < OU_REL_MAX


def judge(result, g):
    """(within_bounds, controls_refused) of ``main``'s result (or of a
    recorded one) under the guarantees ``g``."""
    limits = g["forces_ve_rel_max"]
    rms_max, rel_max = g["stirring_rel_rms_max"], g["stirring_rel_max"]
    stir = result["stirring"]
    stir_in = lambda r: r[0] < rms_max and r[1] < rel_max
    forces = result["forces"]
    within = (forces["finite"] and forces_inside(forces["errors"], limits)
              and stir_in(stir["sound"]) and stir["finite"]
              and stir["ou_key_equal"] and stir["ou_phase_err"] < 1e-6
              and ou_inside(result["ou"]))
    refused = (not forces_inside(result["forces_bf16_control"]["errors"],
                                 limits)
               and not stir_in(stir["bf16_control"]))
    return bool(within), bool(refused)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--one-chip-record", default=None,
                    help="the file a --one-chip run wrote: compare the OU "
                         "state")
    ap.add_argument("--one-chip", type=int, default=0, metavar="STEPS",
                    help="run the configuration on one device instead")
    ap.add_argument("--out", default=None)
    ap.add_argument("--side", type=int, default=None,
                    help="CPU rehearsal at this tiny side (no device number)")
    args = ap.parse_args(argv)

    if args.side:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run  # sibling

    bench, cell, config, traffic = run.load_cell(args.workload)
    g = config["guarantees"]
    chips = 1 if args.one_chip else config["devices"]
    if config["devices"] < 2 or "forces_ve_rel_max" not in g:
        raise SystemExit(f"{cell['name']} is no mesh cell with a VE force "
                         "guarantee")
    if args.side:
        flag = f"--xla_force_host_platform_device_count={chips}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + flag).strip()
        import rehearse_lists_cpu

        rehearse_lists_cpu.steer_auto_to_pallas()
        config = {**config, "side": args.side, "particles": args.side ** 3}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        dev = require_tpu("benchmarks/check_turb_mesh.py")
        if dev.count < chips:
            raise SystemExit(f"{cell['name']} needs {chips} chips; jax "
                             f"found {dev.count}")
        platform = dev.platform
        enable_compile_cache()
    if args.one_chip:
        return one_chip(config, traffic, args.one_chip,
                        args.out or os.path.join(HERE, "out", "one_chip.json"))

    import check_stirring
    import reference

    # run_cell keeps its Simulation to itself: take it as it is built
    built = []
    build = run.build_simulation

    def build_and_keep(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]

    run.build_simulation = build_and_keep
    out_dir = os.path.join(HERE, "out", "turb-mesh-" + cell["name"])
    rec = run.run_cell(cell, config, traffic, args.seed, args.seconds,
                       False, out_dir, run.Spans())
    sim, const = built[-1]
    w = rec["window"]
    for ok, what in rec["checks"]:
        print(f"# [{'PASS' if ok else 'FAIL'}] {what}")
    counts = {k: sum(1 for e in rec["events"] if e["kind"] == k)
              for k in ("reconfigure", "rollback", "retrace")}
    print(f"# {cell['name']}: platform={platform} particles="
          f"{rec['particles']} cycles={w['cycles']} steps="
          f"{w['steps_completed']} attempted={w['attempted']} {counts} "
          f"engine={json.dumps(rec['engine'])}")
    # the same run as run.py's, so its end-to-end numbers count as a seed's
    rates = run.read_metrics(
        run.metrics_of(bench, "end_to_end", cell["name"]), "end_to_end", rec)
    print(f"# end to end ({platform}): " + json.dumps(
        {k: v["value"] for k, v in rates.items()} if not args.side
        else sorted(rates)), flush=True)

    block = 64 if args.side else CHIP_BLOCK_ROWS
    count = g["forces_ve_targets"]
    targets = reference.seeded_targets(args.seed, int(sim.state.n), count)
    stage = system_forces(sim)
    forces = compare_forces(sim, const, targets, block=block, stage=stage)
    print("# VE forces: " + json.dumps(forces), flush=True)
    # one precision down, at a quarter of the targets: a refusal by a factor
    # of a hundred needs no more, and a ring row costs 8.0M pairs
    low = compare_forces(sim, const, targets[:max(count // 4, 1)],
                         block=block, stage=stage, product_dtype="bfloat16")
    print("# VE forces, bf16 kernel values: " + json.dumps(low["errors"]),
          flush=True)

    stirring = check_stirring.compare(sim, args.seed, g["stirring_targets"])
    print("# stirring: " + json.dumps(stirring), flush=True)

    # (run_cell's record holds the window's events; the sink the warm-up's
    # too)
    mesh_ou = ou_record(sim, sim.telemetry.sinks[0].events)
    ou = {"iteration": mesh_ou["iteration"]}
    if args.one_chip_record:
        with open(args.one_chip_record) as f:
            ou = against_one_chip(mesh_ou, json.load(f))
    print("# OU state against one chip: " + json.dumps(ou), flush=True)

    memory = [e for e in rec["events"] if e["kind"] == "memory"]
    result = dict(
        cell=cell["name"], platform=platform, seed=args.seed,
        iteration=sim.iteration, particles=rec["particles"],
        correct=all(c for c, _ in rec["checks"]),
        energy_drift=sim.energy_drift,
        memory_peak_bytes=rec["memory_peak_bytes"],
        bytes_in_use=memory[-1].get("bytes_in_use") if memory else None,
        forces=forces, forces_bf16_control=low, stirring=stirring, ou=ou,
        ou_state=mesh_ou, **counts)
    result["within_bounds"], result["controls_refused"] = judge(result, g)
    print(json.dumps(result))
    return 0 if (result["correct"] and result["within_bounds"]
                 and result["controls_refused"]) else 1


if __name__ == "__main__":
    sys.exit(main())
