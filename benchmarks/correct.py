"""What decides ``correct``, per run, outside the clock.

``check_run`` returns a list of ``(ok, what)``; every check prints, and any
failure makes the run ``correct: false``. Which checks run is decided by what
the configuration's ``guarantees`` state (a gravity error bound brings the
direct-sum check, a dump density bound the read-back, a balance bound the
per-device memory check), never by the cell's name.
"""

import dataclasses
import math

import numpy as np

import reference
import windows

#: targets of the dump's density check
DENSITY_TARGETS = 1024


def steps_failed(run):
    """Steps lost for good: those of a cycle that raised, and every step
    whose fetched diagnostics were non-finite. A rollback that is replayed
    is a recovery (the layer metric ``recoveries``), not a failure."""
    w = run["window"]
    lost = 0
    if w["raised"]:
        lost += max(w["attempted"] - w["steps_completed"], 1)
    lost += sum(1 for e in run["events"] if e["kind"] == "field_health")
    return lost


def check_run(run, sim, const, config, rows, last_dump, seed):
    checks = []
    add = lambda ok, what: checks.append((bool(ok), what))
    g = config["guarantees"]
    events = run["events"]
    w = run["window"]

    add(w["raised"] is None, f"no step raised ({w['raised']})")
    add(run["particles"] == config["particles"],
        f"particle count {run['particles']} as configured "
        f"({config['particles']})")
    add(str(sim.state.x.dtype) == g["state_dtype"],
        f"state dtype {sim.state.x.dtype} is {g['state_dtype']}")

    num = [e for e in events if e["kind"] == "numerics"]
    nonfinite = sum(sum(e["nonfinite"].values()) for e in num)
    health = [e for e in events if e["kind"] == "field_health"]
    series = [r[k] for r in rows for k in ("dt", "etot", "ecin", "eint")]
    add(num and nonfinite == 0 and not health
        and all(math.isfinite(v) for v in series),
        f"every fetched diagnostic finite over {len(rows)} verified steps "
        f"(nonfinite {nonfinite}, field_health events {len(health)})")
    if num:
        lo = min(e["nc_mean_min"] for e in num)
        hi = max(e["nc_mean_max"] for e in num)
        add(0.5 * const.ng0 <= lo and hi <= const.ngmax,
            f"nc_mean in [{lo:.1f}, {hi:.1f}] within the case band "
            f"[{0.5 * const.ng0:.0f}, {const.ngmax}]")
    if rows:
        e0 = rows[0]["etot"]
        drift = max(abs(r["etot"] - e0) for r in rows) / abs(e0)
        run["energy_drift"] = drift
        add(drift < g["energy_drift_max"],
            f"energy drift over {len(rows)} verified steps {drift:.3e} < "
            f"{g['energy_drift_max']}")
    stray = windows.unexplained_retraces(events)
    add(not stray,
        f"no retrace in the window except after a reconfigure or rollback "
        f"({len(stray)} found: a shape the warm-up missed)")

    if "gravity_rel_rms_max" in g:
        _gravity_check(add, run, sim, const, g, seed)
    if "device_balance_max" in g:
        mem = [e for e in events if e["kind"] == "memory"
               and e.get("bytes_in_use")]
        use = mem[-1]["bytes_in_use"] if mem else []
        add(len(use) == run["chips"] and min(use) > 0
            and max(use) <= g["device_balance_max"] * min(use),
            f"per-device bytes_in_use balanced within "
            f"{g['device_balance_max']}: {use}")
    if "halo_trips_max" in g:
        trips = (sim.telemetry.counters.get("halo_trips", 0)
                 + sim.telemetry.counters.get("grav_halo_trips", 0))
        add(trips <= g["halo_trips_max"],
            f"halo sentinel trips {trips} <= {g['halo_trips_max']}")
    if last_dump is not None and "dump_rho_rel_max" in g:
        _dump_check(add, run, sim, last_dump, g, seed)
    return checks


def _gravity_check(add, run, sim, const, g, seed):
    """The final state's tree solve, under the configuration the run
    resolved, against the direct sum on seeded targets over all sources
    (chip_smoke.py:gravity_checks (i), on the live Simulation)."""
    import jax
    import jax.numpy as jnp

    from sphexa_tpu.gravity.traversal import compute_gravity
    from sphexa_tpu.sfc.box import make_global_box
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    s = sim.state
    gbox = make_global_box(s.x, s.y, s.z, sim.box)
    keys = compute_sfc_keys(s.x, s.y, s.z, gbox, curve=sim.curve)
    order = jnp.argsort(keys)
    xs, ys, zs, ms, hs = (a[order] for a in (s.x, s.y, s.z, s.m, s.h))
    margin = 1.5
    for _ in range(3):
        gcfg = dataclasses.replace(sim._cfg.gravity, G=const.g)
        out = compute_gravity(xs, ys, zs, ms, hs, keys[order], gbox,
                              sim._gtree, sim._cfg.grav_meta, gcfg)
        diag = jax.device_get(out[-1])
        if not sim._gravity_overflowed(diag):
            break
        # sampled caps too small for this state: regrow like the driver
        margin *= 1.5
        sim._configure(grav_margin=margin, reason="overflow")
    add(not sim._gravity_overflowed(diag),
        f"gravity: interaction lists within caps (m2p "
        f"{int(diag['m2p_max'])}/{gcfg.m2p_cap}, p2p "
        f"{int(diag['p2p_max'])}/{gcfg.p2p_cap})")
    targets = reference.seeded_targets(seed, run["particles"],
                                       g["gravity_direct_targets"])
    tj = jnp.asarray(targets, jnp.int32)
    ref = reference.direct_sum_gravity(tj, xs, ys, zs, ms, hs, const.g)
    got = [np.asarray(a)[targets] for a in out[:3]]
    rms, p99 = reference.vector_rel_error(got, ref)
    run["gravity_rel_rms"], run["gravity_rel_p99"] = rms, p99
    add(rms < g["gravity_rel_rms_max"] and p99 < g["gravity_rel_p99_max"],
        f"gravity: tree vs direct sum on {len(targets)} seeded targets x "
        f"{run['particles']} sources: rel rms {rms:.3e} < "
        f"{g['gravity_rel_rms_max']}, p99 {p99:.3e} < "
        f"{g['gravity_rel_p99_max']}")


def _dump_check(add, run, sim, path, g, seed):
    """The last dump reads back restartable, and its rho agrees with the
    brute-force kernel sum at seeded targets."""
    import jax.numpy as jnp

    from sphexa_tpu.io.snapshot import read_snapshot_full
    from sphexa_tpu.sfc.box import BoundaryType

    state, box, const, extra, attrs = read_snapshot_full(path)
    finite = all(bool(np.all(np.isfinite(np.asarray(v))))
                 for v in extra.values())
    add(state.n == run["particles"]
        and int(attrs["iteration"]) == sim.iteration and finite,
        f"dump restartable (n={state.n}, iteration "
        f"{int(attrs['iteration'])} of {sim.iteration}, derived fields "
        f"{sorted(extra)} finite: {finite})")
    targets = reference.seeded_targets(seed, state.n, DENSITY_TARGETS)
    ref = reference.brute_force_density(
        jnp.asarray(targets, jnp.int32), state.x, state.y, state.z, state.h,
        state.m, jnp.asarray(box.hi - box.lo, jnp.float32),
        sinc_index=float(const.sinc_index),
        periodic=tuple(b == BoundaryType.periodic for b in box.boundaries))
    worst, rms = reference.scalar_rel_error(
        np.asarray(extra["rho"])[targets], ref)
    run["dump_rho_rel_max"], run["dump_rho_rel_rms"] = worst, rms
    add(worst < g["dump_rho_rel_max"],
        f"dump rho vs brute-force kernel sum at {len(targets)} seeded "
        f"targets x {state.n} particles: max rel {worst:.3e} (rms "
        f"{rms:.3e}) < {g['dump_rho_rel_max']}")
