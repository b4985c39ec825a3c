"""Chip smoke: the main path, end to end, on the TPU, at a real size.

Drives ``sphexa_tpu.app.main.main(argv)`` — the function behind the
``sphexa-tpu`` command — through two legs in ONE process, with deferred
check windows, construction-time sizing, list builds, reconfigures, the
trailing flush, constants.txt rows and one restartable HDF5 dump inside
each run:

- Leg A, headline path at one chip's share of the 64M/16-chip campaign:
  ``--init sedov -n 160 --prop std -s 12 --check-every 4 -w 12``
  (4,096,000 particles; persistent pair lists, donated steps);
- Leg B, flagship coupled path over the gravity solver's 500k switch:
  ``--init evrard -n 128 --prop ve -s 6 --check-every 3 -w 6``
  (~1.10M particles, self-gravity, bitmask MAC compaction).

Each leg's answers are checked outside any timed span, from what the run
itself wrote (telemetry events, constants.txt, the dump): finite
diagnostics, neighbor counts in the case's band, energy drift < 1e-3. Leg B
also re-solves gravity on its dumped final state: against the direct sum
on a seeded sample of 256 targets over all sources (the theta = 0.5 bound
of tests/test_gravity.py), and against the same solve with the flat sort
compaction, whose accelerations must be identical.

``--devices N`` (N > 1, builder-run on a multi-chip host) runs both legs
sharded N ways and additionally checks the dump's N equal part files,
balanced per-device memory, and the conserved totals against a one-chip
run (``--compare summary.json``, else one is made in-process first).

Fails (non-zero exit, no result line) when jax finds no TPU, when any leg
or check fails, and on any exception. Every timing printed is a fact about
one run, not a metric. The last stdout line of a passing run is
``{"ok": true, "device": {...}}``.
"""

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

LEG_A = ("A", "sedov", 12,
         ["--init", "sedov", "-n", "160", "--prop", "std", "-s", "12",
          "--check-every", "4", "-w", "12"])
LEG_B = ("B", "evrard", 6,
         ["--init", "evrard", "-n", "128", "--prop", "ve", "-s", "6",
          "--check-every", "3", "-w", "6"])

#: the repo's north-star conservation bound (ROADMAP aim 1)
DRIFT_BOUND = 1e-3
#: tests/test_gravity.py::TestTreeVsDirect bounds at theta = 0.5
DIRECT_RMS_BOUND, DIRECT_P99_BOUND = 0.01, 0.05
DIRECT_TARGETS = 256
#: N-chip vs one-chip conserved energies after the same number of steps:
#: |a - b| <= TOTALS_RTOL * |b| + TOTALS_ATOL * (energy scale). Not
#: bitwise, for two reasons: the f32 reductions re-associate (per-shard
#: partials + psum, vs one device's tree), and the mesh path streams
#: candidates per step where one chip walks persistent lists, so pair sums
#: accumulate in another order. Both perturb at the f32 rounding level per
#: step; 1e-4 leaves two decades over what 12 steps accumulate and is two
#: decades under any physical difference (a lost halo row, a dropped
#: interaction list entry) at these sizes.
TOTALS_RTOL, TOTALS_ATOL = 1e-4, 1e-6
#: per-device bytes_in_use spread (max / min) a balanced slab run may
#: show; the whole problem left on one device would read >= 2
BALANCE_BOUND = 1.5


class Checks:
    """PASS/FAIL ledger: every check of a run prints and is counted, so
    one chip run reports all that is wrong, and any failure fails it."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what: str) -> None:
        print(f"  [{'PASS' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)


def leg_facts(tel_dir, wall_s):
    """What the run resolved and did, read from its own telemetry."""
    from sphexa_tpu.telemetry import read_manifest
    from sphexa_tpu.telemetry.cli import load_events

    events, schema_problems = load_events(tel_dir)
    manifest = read_manifest(tel_dir)
    of = lambda kind: [e for e in events if e["kind"] == kind]

    # steady step time: windows / checked steps whose launches compiled
    # nothing and followed no recovery since the previous fetch boundary
    steady, steady_wall, dirty = [], 0.0, False
    for e in events:
        if e["kind"] in ("retrace", "reconfigure", "rollback"):
            dirty = True
        elif e["kind"] in ("window", "step"):
            if not dirty:
                steady.append(e["per_step_s"] if e["kind"] == "window"
                              else e["wall_s"])
                steady_wall += e["wall_s"]
            dirty = False

    mem = [e for e in of("memory") if e.get("peak_bytes_in_use")]
    ndev = len(mem[0]["devices"]) if mem else 0
    series = {k: [v for e in of("physics") for v in e[k]]
              for k in ("its", "dt", "etot", "ecin", "eint", "egrav",
                        "linmom", "angmom")}
    num = of("numerics")
    return {
        "schema_problems": schema_problems,
        "particles": manifest["particles"],
        "manifest_backend": manifest["backend"],
        "mesh_shape": manifest["mesh_shape"],
        "engine": of("reconfigure")[-1]["engine"],
        "reconfigures": len([e for e in of("reconfigure")
                             if e["reason"] != "initial"]),
        "rollbacks": len(of("rollback")),
        "retraces": len(of("retrace")),
        "rebuild_lists": len(of("rebuild_lists")),
        # the allocator's peak is the PROCESS's (earlier legs included);
        # the largest bytes_in_use at this leg's snapshot points is its own
        "peak_hbm_bytes_process": (
            [max(e["peak_bytes_in_use"][d] for e in mem)
             for d in range(ndev)] if mem else "not reported"),
        "hbm_in_use_max_bytes": (
            [max(e["bytes_in_use"][d] for e in mem) for d in range(ndev)]
            if mem else "not reported"),
        "bytes_in_use_last": mem[-1]["bytes_in_use"] if mem else None,
        "wall_s": wall_s,
        # everything in the leg that is not a steady window: init, sizing,
        # compiles, replays, the dump
        "setup_s": wall_s - steady_wall,
        "steady_step_s": steady,
        "steady_step_s_median": float(np.median(steady)) if steady else None,
        "series": series,
        "nonfinite": sum(sum(e["nonfinite"].values()) for e in num),
        "nc_mean_min": min(e["nc_mean_min"] for e in num),
        "nc_mean_max": max(e["nc_mean_max"] for e in num),
    }


def run_leg(leg, out_dir, devices, check):
    """One leg through main(argv); returns its facts + dump path."""
    from sphexa_tpu.app.main import main as app_main

    name, case, steps, argv = leg
    tag = f"leg{name}_{devices}dev"
    leg_dir = os.path.join(out_dir, tag)
    shutil.rmtree(leg_dir, ignore_errors=True)
    tel_dir = os.path.join(leg_dir, "telemetry")
    argv = argv + ["-o", leg_dir, "--telemetry-dir", tel_dir]
    if devices > 1:
        argv += ["--devices", str(devices)]
    print(f"== {tag}: sphexa-tpu {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    rc = app_main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"{tag}: main(argv) returned {rc}")
    facts = leg_facts(tel_dir, wall)
    facts["dump"] = os.path.join(leg_dir, f"dump_{case}.h5")
    eng = facts["engine"]
    print(f"  resolved: backend={eng['backend']} interpret={eng['interpret']} "
          f"donate={eng['donate']} lists={eng['lists']} "
          f"rebuild_lists={facts['rebuild_lists']} gravity={eng['gravity']}")
    print(f"  counters: reconfigures={facts['reconfigures']} "
          f"rollbacks={facts['rollbacks']} retraces={facts['retraces']}")
    print(f"  N={facts['particles']} mesh={facts['mesh_shape']} "
          f"hbm_in_use_max_bytes={facts['hbm_in_use_max_bytes']} "
          f"peak_hbm_bytes_process={facts['peak_hbm_bytes_process']}")
    print(f"  wall_s={wall:.1f} setup_s={facts['setup_s']:.1f} "
          f"steady_step_s median={facts['steady_step_s_median']} "
          f"samples={facts['steady_step_s']}", flush=True)

    # -- the leg's own answers, outside any timed span ---------------------
    from sphexa_tpu.io.snapshot import _find_parts, read_snapshot_full

    s = facts["series"]
    check(not facts["schema_problems"],
          f"{tag}: event stream schema-clean {facts['schema_problems'][:3]}")
    check(s["its"] == list(range(1, steps + 1)),
          f"{tag}: one verified ledger row per step ({len(s['its'])}/{steps})")
    vals = [v for k in s if k != "its" for v in s[k]]
    check(bool(np.all(np.isfinite(vals))) and facts["nonfinite"] == 0,
          f"{tag}: every fetched diagnostic finite, no nonfinite rho/h/du")
    rows = np.loadtxt(os.path.join(leg_dir, "constants.txt"), ndmin=2)
    check(rows.shape[0] == steps and bool(np.all(np.isfinite(rows))),
          f"{tag}: constants.txt holds {steps} finite rows")
    state, _box, const, extra, attrs = read_snapshot_full(facts["dump"])
    check(state.n == facts["particles"]
          and int(attrs["iteration"]) == steps
          and all(bool(np.all(np.isfinite(np.asarray(v))))
                  for v in extra.values()),
          f"{tag}: dump restartable (n={state.n}, iteration "
          f"{int(attrs['iteration'])}, derived fields finite)")
    check(0.5 * const.ng0 <= facts["nc_mean_min"]
          and facts["nc_mean_max"] <= const.ngmax,
          f"{tag}: nc_mean in [{facts['nc_mean_min']:.1f}, "
          f"{facts['nc_mean_max']:.1f}] within the case band "
          f"[{0.5 * const.ng0:.0f}, {const.ngmax}]")
    drift = max(abs(e - s["etot"][0]) for e in s["etot"]) / abs(s["etot"][0])
    facts["energy_drift"] = drift
    check(drift < DRIFT_BOUND,
          f"{tag}: energy drift over the leg {drift:.3e} < {DRIFT_BOUND}")
    check(eng["backend"] == "pallas" and eng["interpret"] is False
          and facts["manifest_backend"] == "tpu",
          f"{tag}: Mosaic kernels compiled (backend pallas, interpret False)")
    if devices == 1:
        check(eng["donate"] is True, f"{tag}: donation active")
    else:
        parts = _find_parts(facts["dump"])
        import h5py

        part_rows = []
        for p in parts:
            with h5py.File(p, "r") as h5:
                part_rows.append(h5[sorted(h5.keys())[-1]]["x"].shape[0])
        check(len(parts) == devices
              and part_rows == [facts["particles"] // devices] * devices,
              f"{tag}: outputs sharded {devices} ways (dump part rows "
              f"{part_rows})")
        use = facts["bytes_in_use_last"]
        check(use is not None and max(use) <= BALANCE_BOUND * min(use),
              f"{tag}: per-device bytes_in_use balanced {use}")
    return facts


def gravity_checks(facts, seed, check):
    """Leg B's two solver checks, on the final state it dumped."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from sphexa_tpu.gravity.direct import direct_gravity_at
    from sphexa_tpu.gravity.traversal import compute_gravity
    from sphexa_tpu.io.snapshot import read_snapshot_full
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    from sphexa_tpu.simulation import Simulation

    state, box, const, _extra, _attrs = read_snapshot_full(facts["dump"])
    # the production sizing: the same constructor the run went through
    # resolves the solver shape, builds the tree and sizes the caps
    sim = Simulation(state, box, const, prop="ve", theta=0.5)
    s = sim.state
    gbox = make_global_box(s.x, s.y, s.z, sim.box)
    keys = compute_sfc_keys(s.x, s.y, s.z, gbox, curve=sim.curve)
    order = jnp.argsort(keys)
    xs, ys, zs, ms, hs = (a[order] for a in (s.x, s.y, s.z, s.m, s.h))

    def solve(gcfg):
        out = compute_gravity(xs, ys, zs, ms, hs, keys[order], gbox,
                              sim._gtree, sim._cfg.grav_meta, gcfg,
                              with_phi=True)
        return [np.asarray(a) for a in out[:4]], jax.device_get(out[4])

    margin = 1.5
    for _ in range(3):
        gcfg = dataclasses.replace(sim._cfg.gravity, G=const.g)
        acc, diag = solve(gcfg)
        if not sim._gravity_overflowed(diag):
            break
        # sampled caps too small for this state: regrow like the driver
        margin *= 1.5
        sim._configure(grav_margin=margin, reason="overflow")
    check(not sim._gravity_overflowed(diag),
          f"legB gravity: interaction lists within caps (m2p "
          f"{int(diag['m2p_max'])}/{gcfg.m2p_cap}, p2p "
          f"{int(diag['p2p_max'])}/{gcfg.p2p_cap}, super "
          f"{int(diag['c_max'])}/{gcfg.super_cap})")
    check(gcfg.compaction == "bitmask" and gcfg.target_block == 256
          and gcfg.super_factor == 8,
          f"legB gravity: >= 500k solver shape (compaction "
          f"{gcfg.compaction}, target_block {gcfg.target_block}, "
          f"super_factor {gcfg.super_factor})")

    # (i) tree vs direct sum on a seeded sample over all sources
    targets = np.sort(np.random.default_rng(seed).choice(
        state.n, DIRECT_TARGETS, replace=False))
    d = [np.asarray(a) for a in direct_gravity_at(
        jnp.asarray(targets, jnp.int32), xs, ys, zs, ms, hs, G=const.g)]
    ref = np.sqrt(sum(d[k] ** 2 for k in range(3)))
    def rel_error(a):
        err = np.sqrt(sum((a[k][targets] - d[k]) ** 2 for k in range(3)))
        rel = err / np.maximum(ref, 1e-6)
        return (float(np.sqrt(np.mean(rel ** 2))),
                float(np.percentile(rel, 99)))

    rms, p99 = rel_error(acc)
    facts["direct_rel_rms"], facts["direct_rel_p99"] = rms, p99
    check(rms < DIRECT_RMS_BOUND and p99 < DIRECT_P99_BOUND,
          f"legB gravity: tree vs direct sum on {DIRECT_TARGETS} targets x "
          f"{state.n} sources: rel rms {rms:.2e} < {DIRECT_RMS_BOUND}, "
          f"p99 {p99:.2e} < {DIRECT_P99_BOUND}")

    # (ii) bitmask lists vs the flat sort: exact-equivalent lists, so the
    # accelerations and potentials must be identical
    acc_s, diag_s = solve(dataclasses.replace(
        gcfg, compaction="sort", super_factor=0))
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(acc, acc_s))
    print("  sort-compaction solve vs direct sum: rel rms %.2e, p99 %.2e"
          % rel_error(acc_s))
    facts["sort_vs_bitmask_max_abs"] = worst
    check(all(np.array_equal(a, b) for a, b in zip(acc, acc_s))
          and all(int(diag[k]) == int(diag_s[k])
                  for k in ("m2p_max", "p2p_max")),
          f"legB gravity: bitmask == sort compaction, bitwise (max abs "
          f"difference {worst:.3e}; m2p_max {int(diag['m2p_max'])} vs "
          f"{int(diag_s['m2p_max'])}, p2p_max {int(diag['p2p_max'])} vs "
          f"{int(diag_s['p2p_max'])})")


def final_totals(facts):
    return {k: facts["series"][k][-1]
            for k in ("etot", "ecin", "eint", "egrav", "linmom", "angmom")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="shard both legs over N chips of this host [1]")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the direct-sum target sample [0]")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="output directory [<checkout>/chip_smoke_out]")
    ap.add_argument("--compare", default=None,
                    help="summary.json of a one-chip run whose conserved "
                         "totals the --devices N run must match (else the "
                         "one-chip legs run in-process first)")
    args = ap.parse_args(argv)

    # first thing, before any compile: is there a chip
    import jax
    import jaxlib
    from importlib import metadata

    from sphexa_tpu.util.device import (
        device_info, enable_compile_cache, require_tpu)

    dev = device_info()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"platform: {dev.platform}  device_kind: {dev.kind}  "
          f"count: {dev.count}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  libtpu {libtpu}", flush=True)
    require_tpu("chip_smoke.py")
    if args.devices > dev.count:
        raise RuntimeError(f"--devices {args.devices} > {dev.count} present")

    from sphexa_tpu import native

    cache_dir = enable_compile_cache()
    cache_count = lambda: (len(os.listdir(cache_dir))
                           if os.path.isdir(cache_dir) else 0)
    cache_before = cache_count()
    print(f"compile cache: {cache_dir} ({cache_before} entries before)")
    print(f"host runtime: {native.describe()}", flush=True)
    os.makedirs(args.out, exist_ok=True)

    check = Checks()
    summary = {"device": dev._asdict(), "devices_used": args.devices,
               "legs": {}}
    reference = None
    if args.devices > 1:
        if args.compare:
            with open(args.compare) as f:
                reference = json.load(f)["totals"]
        else:
            reference = {leg[0]: final_totals(run_leg(leg, args.out, 1, check))
                         for leg in (LEG_A, LEG_B)}
    totals = {}
    for leg in (LEG_A, LEG_B):
        facts = run_leg(leg, args.out, args.devices, check)
        name = leg[0]
        if name == "A" and args.devices == 1:
            check(facts["engine"]["lists"] is True
                  and facts["rebuild_lists"] >= 1,
                  "legA: persistent pair lists were the engine that ran")
        if name == "B":
            check(facts["particles"] >= 500_000
                  and facts["engine"]["gravity"]["compaction"] == "bitmask",
                  "legB: ran over the 500k switch with compaction bitmask")
            if args.devices == 1:
                gravity_checks(facts, args.seed, check)
        totals[name] = final_totals(facts)
        if reference is not None:
            ref = reference[name]
            scale = abs(ref["ecin"]) + abs(ref["eint"]) + abs(ref["egrav"])
            off = {k: abs(totals[name][k] - ref[k])
                   for k in ("etot", "ecin", "eint", "egrav")}
            check(all(off[k] <= TOTALS_RTOL * abs(ref[k])
                      + TOTALS_ATOL * scale for k in off),
                  f"leg{name}: {args.devices}-chip energies match one chip "
                  f"(|diff| {off}; rtol {TOTALS_RTOL}, atol "
                  f"{TOTALS_ATOL} x {scale:.3g})")
        summary["legs"][name] = {k: v for k, v in facts.items()
                                 if k != "series"}
    summary["totals"] = totals
    cache_after = cache_count()
    summary["compile_cache"] = {"dir": cache_dir, "before": cache_before,
                                "after": cache_after}
    print(f"compile cache: {cache_after} entries after "
          f"(+{cache_after - cache_before})")
    summary["failed"] = check.failed
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) FAILED:",
              *check.failed, sep="\n  ")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.kind, "count": dev.count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
