"""The coupled ``std-cooling`` step under self-gravity ON A MESH against the
plain references, on the live mesh state after a cell's traffic, with the
controls its limits must refuse.

    python3 benchmarks/check_collapse_mesh.py --workload evrard-cooling-4m-x4.steady --seed <n> [--seconds 30] [--one-chip-record <file>] [--side 16]
    python3 benchmarks/check_collapse_mesh.py --workload evrard-cooling-4m-x4.steady --one-chip <steps> --out <file>

A builder's script, not a metric. The first form runs the cell exactly as
run.py does (the same ``run_cell``: initialiser, ``Simulation`` as ``main()``
builds it on the cell's chips, warm-up, the traffic's check windows for
``--seconds``, ``correct``) and then, outside any clock, on the LIVE mesh
state, chemistry and configuration (``Simulation.active_cfg``: the sharded
stepper's, with its mesh, halo caps and near-field caps):

- check_collapse_step.py's ``system_step`` / ``compare`` / ``judge``: the
  calls the next step makes (``propagator.std_forces`` with the live gravity
  tree and ``aux=chem``: the global sort with the chemistry riding it, the
  std pair ops under ``shard_map`` with their halo, the sharded tree solve;
  once more without gravity; ``cool_timestep``, ``compute_timestep``,
  ``cool_step``) against reference_collapse_step.py at ``forces_targets``
  seeded targets, half of the core, under ``forces_rel_max`` and the
  configuration's mesh gravity limits, with that script's controls (the
  source dropped from ``du``, gravity dropped from the acceleration, the
  reference's kernel values rounded to bf16 on the host's CPU backend);
- check_gravity_mesh.py's ``compare``: the mesh's own tree solve against the
  direct sum at ``gravity_direct_targets`` uniform targets, with the
  bf16-rounded direct sum it must refuse;
- check_cooling.py's ``compare`` / ``judge``: ``cool_timestep`` and
  ``cool_step`` over ALL rows against reference_cooling.py (float64) at
  ``cooling_targets`` targets a group, at the case's ``minDt``, the step's
  own dt and 1e-2, with the differenced form and the bf16 reference refused
  where the configuration says;
- ``alignment_probe``: the chemistry's ROW ALIGNMENT through the mesh's sort.
  The cell's own chemistry starts uniform, so a row astray would not show
  in it: the probe shuffles the live state with a seeded permutation, gives
  every row a chemistry that is a function of its own position, sends both
  through the step's sort on the mesh (every row changes place, three in
  four change slab) and reads each sorted row's chemistry against its
  position's. ``cooling_fraction_abs_max`` must hold; the control, the
  chemistry permuted by ANOTHER order, must be refused by it;
- the radiated-energy counter ``e_cool`` by iteration against a one-chip
  run of the same particles (``--one-chip-record``: what the second form
  wrote), reported with the relative difference at the last common
  iteration. Two f32 trajectories whose sums run in another order: no limit
  is stated for it beyond ``E_COOL_REL_MAX``, which a lost slab (a quarter
  of the sum) or a doubled one would pass a thousand times over.

Exit 0 only if the run is ``correct``, every sound reading is inside its
limit and every control is refused. It fails without a TPU or with fewer
chips than the cell asks for, like run.py; ``--side <n>`` instead rehearses
it on virtual CPU devices at a tiny size with the engine interpreted, and
then prints no device number.

The second form runs the SAME configuration on one device for ``<steps>``
verified steps in the traffic's check windows and writes ``e_cool``,
``etot`` and ``dt`` by iteration to ``--out``.

``alignment_probe`` and ``e_cool_by_iteration`` are what the tier-1 test
calls too (tests/test_mesh_cooling_cell.py).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

#: mesh against one chip, |e_cool_mesh / e_cool_one - 1| at equal iteration.
#: The counter is a sum of f32 sums of m du_cool over all rows, per slab and
#: then across slabs on the mesh, and the two trajectories differ where a
#: MAC-marginal node flips (tests/mesh_gravity_case.py reads 1e-6 in the
#: energies over eight steps): 1e-3 is far over that and far under a slab
#: lost from the sum (0.25) or a step counted twice (1 / steps)
E_COOL_REL_MAX = 1e-3
#: rows of an all-pairs block at the timed size: 4.19M sources a row (the
#: one-chip check's block of 64 would hold 1 GB a temporary there)
CHIP_BLOCK_ROWS = 16


def probe_chem(x):
    """A chemistry that is a function of a row's own x (float32, computed
    on the host): a label in [0, 1) from the low bits of x, then
    tests/test_collapse_cooling_reference.py's fractions of it. No step
    changes x between the probe's making and its reading, and a gather
    moves values to the bit."""
    import numpy as np

    x = np.asarray(x, np.float32)
    label = np.mod(np.abs(x) * np.float32(8191.0), np.float32(1.0))
    hx, hy = np.float32(0.76), np.float32(1.0 - 0.76 - 0.0122)
    he0 = np.float32(0.1) * label
    he1 = np.float32(0.3) * (1 - label)
    chem = {"hi": hx * np.float32(0.2) * label,
            "hii": hx * (1 - np.float32(0.2) * label),
            "hei": hy * he0, "heii": hy * he1, "heiii": hy * (1 - he0 - he1),
            "metal": np.float32(0.005) + np.float32(0.01) * label}
    chem["e"] = chem["hii"] + chem["heii"] / 4 + chem["heiii"] / 2
    return {k: v.astype(np.float32) for k, v in chem.items()}


def alignment_probe(sim, seed):
    """The live state shuffled by a seeded permutation, with ``probe_chem``
    of its rows as aux, through the step's own sort on ``sim``'s mesh
    (``propagator.std_forces`` under ``sim.active_cfg``, as the step calls
    it). Returns the largest absolute difference of a sorted row's
    chemistry from its own position's (``aligned``: 0 when every row
    arrived with its particle), the same against the chemistry in the order
    it went in (``misaligned``: what a sort that left the aux behind would
    give), the share of rows that changed slab, and whether the sorted
    state is the live particles again."""
    import jax
    import numpy as np

    import check_collapse_step
    from sphexa_tpu.physics.cooling import ChemistryData

    forces = check_collapse_step._programs()[0]
    n = int(sim.state.n)
    perm = np.random.default_rng(seed).permutation(n)

    def shuffled(leaf):
        if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != n:
            return leaf
        return jax.device_put(np.asarray(leaf)[perm], leaf.sharding)

    state = jax.tree.map(shuffled, sim.state)
    went_in = probe_chem(state.x)
    chem = ChemistryData(**{
        k: jax.device_put(v, sim.state.x.sharding)
        for k, v in went_in.items()})
    out_state, out_chem, _, _, _ = forces(state, sim.box, sim.active_cfg,
                                          sim._gtree, chem)
    want = probe_chem(out_state.x)
    got = {k: np.asarray(getattr(out_chem, k)) for k in want}
    worst = lambda a, b: float(max(np.abs(a[k] - b[k]).max() for k in a))
    # where each row went: the i-th smallest x of what went in is the i-th
    # smallest of what came out (a jittered lattice's x are distinct)
    x_in, x_out = np.asarray(state.x), np.asarray(out_state.x)
    by_in = np.argsort(x_in, kind="stable")
    by_out = np.argsort(x_out, kind="stable")
    dest = np.empty(n, np.int64)
    dest[by_in] = by_out
    slab = n // (sim._mesh.size if sim._mesh is not None else 1)
    return {
        "rows": n, "aligned": worst(got, want),
        "misaligned": worst(went_in, want),
        "same_particles": bool(np.array_equal(x_in[by_in], x_out[by_out])),
        "moved_slab_share": float(np.mean(dest // slab
                                          != np.arange(n) // slab)),
    }


def e_cool_by_iteration(events):
    """{iteration: (e_cool so far, etot, dt)} from a run's ``numerics`` and
    ``physics`` events (schema v16: ``e_cool`` is the counter after the
    window, ``e_cool_step`` its verified steps' shares, parallel to
    ``physics.its``)."""
    out = {}
    phys = [e for e in events if e["kind"] == "physics"]
    nums = [e for e in events if e["kind"] == "numerics"
            and "e_cool_step" in e]
    for p, q in zip(phys, nums):
        after = q["e_cool"]
        for k in range(len(p["its"]) - 1, -1, -1):
            out[int(p["its"][k])] = (after, p["etot"][k], p["dt"][k])
            after -= q["e_cool_step"][k]
    return out


def one_chip(config, traffic, steps, out):
    """The configuration's particles (trimmed to the mesh's count as
    ``run.build_simulation`` trims them) on ONE device for ``steps``
    verified steps in the traffic's check windows; the counter by iteration
    to ``out``."""
    import jax

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.sinks import MemorySink

    state, box, const = make_initializer(config["init"])(config["side"])
    n_full = state.n
    keep = (n_full // config["devices"]) * config["devices"]
    state = jax.tree.map(
        lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
        and a.shape[0] == n_full else a, state)
    sink = MemorySink()
    sim = Simulation(
        state, box, const, prop=config["prop"], theta=config["theta"],
        check_every=traffic["check_every"],
        obs_spec=make_observable_spec(config["init"]), science_rows=True,
        telemetry=Telemetry(sinks=[sink]), workload=config["init"])
    while sim.iteration < steps:
        sim.step()
    sim.flush()
    record = {"particles": int(sim.state.n), "iteration": sim.iteration,
              "e_cool": sim.e_cool, "energy_drift": sim.energy_drift,
              "by_iteration": {str(k): v for k, v in
                               e_cool_by_iteration(sink.events).items()}}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f)
    print(json.dumps({k: record[k] for k in
                      ("particles", "iteration", "e_cool", "energy_drift")}))
    return 0


def against_one_chip(mesh_by_it, record):
    """The mesh run's counter against the one-chip record's at every common
    iteration: the last one's pair and the largest relative difference."""
    one = {int(k): v for k, v in record["by_iteration"].items()}
    common = sorted(set(one) & set(mesh_by_it))
    if not common:
        return {"common_iterations": 0}
    rel = {it: abs(mesh_by_it[it][0] / one[it][0] - 1.0) for it in common
           if one[it][0] != 0.0}
    last = common[-1]
    return {"common_iterations": len(common), "iteration": last,
            "mesh": mesh_by_it[last][0], "one_chip": one[last][0],
            "rel": rel.get(last), "rel_max": max(rel.values(), default=None),
            "etot_rel": abs(mesh_by_it[last][1] / one[last][1] - 1.0),
            "dt_rel": abs(mesh_by_it[last][2] / one[last][2] - 1.0)}


def judge(result, g):
    """(within_bounds, controls_refused) of ``main``'s result (or of a
    recorded one) under the guarantees ``g``.

    The coupled step (check_collapse_step.judge) with the tree's part and
    the total acceleration under ``forces_gravity_rel_*``: its targets are
    half of the core, where the solve's error is several times the uniform
    sample's that ``gravity_rel_*`` are set for (the one-chip cell judges
    the same sample under the same numbers); the mesh's own solve at
    uniform targets under ``gravity_rel_*``. The cooling calls at the dt
    the live state's own limiter allows (dt <= ``dt_cool``): a longer one
    is reported and not judged, the step can never take it (at 4.19M the
    core's cooling time is under ``check_cooling.DT_LONG`` by iteration
    26). The probe under ``cooling_fraction_abs_max``; ``e_cool`` under
    ``E_COOL_REL_MAX`` where a one-chip record was handed in."""
    import check_collapse_step
    import check_cooling

    sample = {**g, "gravity_rel_rms_max": g["forces_gravity_rel_rms_max"],
              "gravity_rel_p99_max": g["forces_gravity_rel_p99_max"]}
    within, refused = check_collapse_step.judge(result, sample)
    refused = refused and not check_collapse_step.judge(
        dict(result, hydro=result["hydro_bf16_control"]), sample)[0]

    grav = result["mesh_gravity"]
    rms_max, p99_max = g["gravity_rel_rms_max"], g["gravity_rel_p99_max"]
    grav_ok = (grav["rel_rms"] < rms_max and grav["rel_p99"] < p99_max
               and grav["within_caps"] and not grav["window_blown"]
               and grav["finite"])
    grav_refused = (grav["bf16_ref"][0] >= rms_max
                    or grav["bf16_ref"][1] >= p99_max)

    cool = result["cooling"]
    allowed = {k: d for k, d in cool["dt"].items()
               if d["dt"] <= cool["dt_cool"]["program"]}
    cool_within, cool_refused = check_cooling.judge(
        dict(cool, dt=allowed), g,
        refuse_differenced=tuple(g["cooling_refuse_differenced"]),
        refuse_bf16=tuple(allowed))

    probe = result["alignment"]
    frac_max = g["cooling_fraction_abs_max"]
    aligned = probe["aligned"] < frac_max and probe["same_particles"]
    e_cool = result["e_cool"]
    e_cool_ok = ("one_chip" not in e_cool
                 or (e_cool.get("rel") is not None
                     and e_cool["rel"] < E_COOL_REL_MAX))
    return (bool(within and grav_ok and cool_within and allowed
                 and aligned and e_cool_ok),
            bool(refused and grav_refused and cool_refused
                 and probe["misaligned"] >= frac_max))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--one-chip-record", default=None,
                    help="the file a --one-chip run wrote: compare e_cool")
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
    if config["devices"] < 2 or "forces_rel_max" not in g:
        raise SystemExit(f"{cell['name']} is no mesh cell with a coupled-"
                         "step guarantee: check_collapse_step.py covers it")
    if args.side:
        flag = f"--xla_force_host_platform_device_count={chips}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + flag).strip()
        import rehearse_lists_cpu
        from sphexa_tpu.init import make_initializer

        rehearse_lists_cpu.steer_auto_to_pallas()
        n = make_initializer(config["init"])(args.side)[0].n
        config = {**config, "side": args.side,
                  "particles": n - n % config["devices"]}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        dev = require_tpu("benchmarks/check_collapse_mesh.py")
        if dev.count < chips:
            raise SystemExit(f"{cell['name']} needs {chips} chips; jax "
                             f"found {dev.count}")
        platform = dev.platform
        enable_compile_cache()
    if args.one_chip:
        return one_chip(config, traffic, args.one_chip,
                        args.out or os.path.join(HERE, "out", "one_chip.json"))

    import check_collapse_step
    import check_cooling
    import check_gravity_mesh

    # run_cell keeps its Simulation to itself: take it as it is built
    built = []
    build = run.build_simulation

    def build_and_keep(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]

    run.build_simulation = build_and_keep
    out_dir = os.path.join(HERE, "out", "collapse-mesh-" + cell["name"])
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

    import jax
    import ml_dtypes

    evolve = sim.cooling_cfg.evolve_species
    block = 64 if args.side else CHIP_BLOCK_ROWS
    half = g["forces_targets"] // 2
    step = check_collapse_step.system_step(sim, const)
    result = check_collapse_step.compare(
        step, const, config["cooling"], args.seed, half,
        evolve_species=evolve, block=block)
    print("# coupled step: " + json.dumps(
        {k: result[k] for k in ("hydro", "gravity", "acceleration", "du",
                                "fractions", "targets", "core_targets")}),
          flush=True)
    # one precision down in the hydro part, on the host's CPU backend (the
    # chip's compiler removes the f32 -> bf16 -> f32 round trip), at an
    # eighth of the targets: a refusal by a factor of a hundred needs no more
    with jax.default_device(jax.devices("cpu")[0]):
        low = check_collapse_step.compare(
            step, const, config["cooling"], args.seed, max(half // 8, 1),
            evolve_species=evolve, block=block,
            product_dtype=ml_dtypes.bfloat16)
    result["hydro_bf16_control"] = low["hydro"]

    grav = check_gravity_mesh.compare(sim, const, args.seed,
                                      g["gravity_direct_targets"])
    for key in ("target_pos", "target_acc"):
        del grav[key]
    print("# mesh gravity: " + json.dumps(
        {k: grav[k] for k in ("rel_rms", "rel_p99", "bf16_ref")}), flush=True)

    dts = {"ramp": float(config["evrard"]["minDt"]), "step": step["dt"],
           "long": check_cooling.DT_LONG}
    cool = check_cooling.compare(
        step["total"]["rho"], step["u"], step["chem"], sim.cooling_cfg,
        config["cooling"], args.seed, g["cooling_targets"], dts)
    print("# cooling: " + json.dumps(
        {k: {r: v[r] for r in ("sound", "differenced_control",
                               "bf16_control", "fractions")}
         for k, v in cool["dt"].items()}), flush=True)

    probe = alignment_probe(sim, args.seed)
    print("# alignment: " + json.dumps(probe), flush=True)

    # (run_cell's record holds the window's events; the sink the warm-up's
    # too)
    mesh_by_it = e_cool_by_iteration(sim.telemetry.sinks[0].events)
    e_cool = {"mesh_final": sim.e_cool}
    if args.one_chip_record:
        with open(args.one_chip_record) as f:
            e_cool.update(against_one_chip(mesh_by_it, json.load(f)))
    sort_events = [e for e in rec["events"] if e["kind"] == "exchange"
                   and e.get("stage") == "sort"]
    result.update(
        cell=cell["name"], platform=platform, seed=args.seed,
        iteration=sim.iteration, evolve_species=evolve,
        correct=all(c for c, _ in rec["checks"]),
        energy_drift=sim.energy_drift,
        memory_peak_bytes=rec["memory_peak_bytes"], mesh_gravity=grav,
        cooling=cool, alignment=probe, e_cool=e_cool,
        sort_migrants=[[e["migrant_rows"], e["rows"]] for e in sort_events],
        **counts)
    result["within_bounds"], result["controls_refused"] = judge(result, g)
    print(json.dumps(result))
    return 0 if (result["correct"] and result["within_bounds"]
                 and result["controls_refused"]) else 1


if __name__ == "__main__":
    sys.exit(main())
