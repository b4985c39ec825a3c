"""Benchmark: particle-updates/sec/chip on the Sedov blast (driver contract).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device",
"extra"}. The headline metric is std SPH at Sedov BENCH_SIDE^3; "extra"
carries the flagship VE pipeline and VE+gravity (Evrard) throughputs, so
every pipeline the framework ships is pinned by the bench.

Chip-only: it refuses to start when jax finds no TPU (a CPU number under
these metric names would be a wrong record), stamps the device it ran on
into the JSON, and a line that raises fails the run.

Baseline: BASELINE.md's north star is Sedov 100^3 within 2x of sphexa-cuda
per-chip throughput (16xA100 vs v5e-16). The reference publishes no absolute
numbers (BASELINE.md), so the per-chip baseline constant below is the
working estimate of sphexa-cuda on one A100 for this problem size;
vs_baseline = value / BASELINE_UPDATES_PER_SEC.
"""

import json
import os
import sys
import time

# sphexa-cuda per-A100 working estimate for Sedov ~1e6 (no published number)
BASELINE_UPDATES_PER_SEC = 2.0e7

SIDE = int(os.environ.get("BENCH_SIDE", "100"))
WARMUP = 2
STEPS = int(os.environ.get("BENCH_STEPS", "10"))
# auxiliary pipelines are timed at a smaller N to bound bench wall-clock
# (VE ~2.5x the std step cost; gravity adds the tree solve)
AUX_SIDE = int(os.environ.get("BENCH_AUX_SIDE", str(min(SIDE, 80))))
AUX_STEPS = int(os.environ.get("BENCH_AUX_STEPS", "6"))


def _measure(sim, n, steps):
    """Clean reconfigure-free window throughput (updates/s); raises when
    three attempts find no such window."""
    import jax

    for _ in range(WARMUP):
        sim.step()
    d = sim.flush()
    jax.block_until_ready(sim.state.x)

    # A reconfigure swaps the static jit config: a mid-window one charges
    # a recompile to the clock directly, and one in the PREVIOUS flush
    # makes the next window's first step pay it — a window is clean only
    # when neither happened, else retry with the settled config.
    tainted = d["reconfigured"] > 0.0
    for _attempt in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            sim.step()
        d = sim.flush()
        jax.block_until_ready(sim.state.x)
        elapsed = time.perf_counter() - t0
        if d["reconfigured"] == 0.0 and not tainted:
            return n * steps / elapsed
        tainted = d["reconfigured"] > 0.0
    raise RuntimeError("bench: no reconfigure-free window in 3 attempts")


def _gravity_scale_line(n=1_000_000):
    """Gravity-only throughput at 1M (Plummer, theta=0.5, ~58k-node
    tree): the scale where the dense MAC classification cost matters.
    Standalone solve (no hydro) so the line isolates the tree walk the
    reference benches as its nbody path. The solver shape comes from
    gravity_tuning — the SAME choice Simulation makes — so on TPU this
    line exercises the hierarchical bitmask compaction; the "extra" block
    carries a phase breakdown (multipoles / solve, plus the sort-mode
    solve for comparison when the tuned mode differs) and the compaction
    complexity proxy (compact_width: candidate slots per block's list
    materialization — num_nodes for the flat sort, super_cap for the
    hierarchical kernel)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from sphexa_tpu.gravity.traversal import (
        GravityConfig, compute_gravity, compute_multipoles,
        estimate_gravity_caps, gravity_tuning)
    from sphexa_tpu.gravity.tree import build_gravity_tree
    from sphexa_tpu.init.plummer import sample_plummer
    from sphexa_tpu.sfc.box import BoundaryType, Box
    from sphexa_tpu.sfc.keys import compute_sfc_keys
    from sphexa_tpu.util.device import on_tpu

    x, y, z, m = sample_plummer(n)
    ext = float(np.max(np.abs(np.stack([x, y, z])))) * 1.001
    box = Box.create(-ext, ext, boundary=BoundaryType.open)
    keys = np.asarray(compute_sfc_keys(jnp.asarray(x), jnp.asarray(y),
                                       jnp.asarray(z), box))
    order = np.argsort(keys)
    xs, ys, zs, ms = (jnp.asarray(a[order]) for a in (x, y, z, m))
    skeys = jnp.asarray(keys[order])
    gtree, meta = build_gravity_tree(keys[order], bucket_size=64)
    cfg = estimate_gravity_caps(
        xs, ys, zs, ms, skeys, box, gtree, meta,
        GravityConfig(theta=0.5, bucket_size=64, G=1.0,
                      **gravity_tuning(n, on_tpu())),
        margin=1.6)
    hs = jnp.full_like(xs, 1e-3)
    args = (xs, ys, zs, ms, hs, skeys, box, gtree, meta)

    def timed_solve(c):
        out = compute_gravity(*args, c)
        jax.block_until_ready(out)
        out = compute_gravity(*args, c)  # discard post-compile outlier
        jax.block_until_ready(out)
        _ = float(out[3])
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(2):
                out = compute_gravity(*args, c)
            jax.block_until_ready(out)
            _ = float(out[3])
            best = min(best, (time.perf_counter() - t0) / 2)
        return best, out

    best, out = timed_solve(cfg)
    diag = out[4]

    # phase breakdown for the JSON extra block: the two headline terms
    # (shared multipole upsweep vs the classification+lists+eval solve),
    # and the flat-sort solve when the tuned compaction differs — the
    # direct before/after of the bitmask change on this hardware
    # discard the first standalone call: compute_multipoles has only run
    # INLINED inside compute_gravity's jit so far, and its top-level jit
    # compile would otherwise dominate the phase number
    mpc = compute_multipoles(xs, ys, zs, ms, skeys, gtree, meta)
    jax.block_until_ready(mpc)
    t0 = time.perf_counter()
    for _ in range(3):
        mpc = compute_multipoles(xs, ys, zs, ms, skeys, gtree, meta)
    jax.block_until_ready(mpc)
    t_mp = (time.perf_counter() - t0) / 3
    phases = {
        "multipoles_ms": round(t_mp * 1e3, 1),
        "solve_ms": round(best * 1e3, 1),
        "compaction": cfg.compaction,
        "super_factor": cfg.super_factor,
        "compact_width": int(diag["compact_width"]),
        "mac_work_ratio": round(float(diag["mac_work_ratio"]), 5),
    }
    if cfg.compaction != "sort":
        import dataclasses

        t_sort, _ = timed_solve(dataclasses.replace(
            cfg, compaction="sort", super_factor=0))
        phases["solve_sort_ms"] = round(t_sort * 1e3, 1)
    return {
        "gravity_1m_updates_per_sec": round(n / best, 1),
        "gravity_1m_nodes": int(meta.num_nodes),
        "gravity_1m_vs_baseline": round(
            n / best / BASELINE_UPDATES_PER_SEC, 4),
        "gravity_phases": phases,
    }


def main() -> int:
    from sphexa_tpu.util.device import enable_compile_cache, require_tpu

    dev = require_tpu("bench.py")
    enable_compile_cache()

    from sphexa_tpu.init import init_evrard, init_sedov
    from sphexa_tpu.observables import ObservableSpec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry
    from sphexa_tpu.telemetry.manifest import build_manifest

    # sink-less registry shared by every benched Simulation: counters
    # (retraces/rollbacks) ride into the JSON so a bench line carries its
    # own health record, not just a throughput number
    tel = Telemetry()

    n = SIDE**3
    state, box, const = init_sedov(SIDE)
    # deferred cap-checking: the happy path issues no device->host sync
    # per step (diagnostics checked in one batch at the window end).
    # BENCH_TUNED ("auto" or a table path) routes the non-explicit knobs
    # through the committed tuning table; either way the resolved
    # provenance is stamped into extra.tuning below, so history/diff can
    # attribute a throughput change to a knob change.
    tuned = os.environ.get("BENCH_TUNED") or None
    sim = Simulation(state, box, const, prop="std", block=8192,
                     check_every=STEPS, telemetry=tel,
                     obs_spec=ObservableSpec(),
                     tuned=tuned, workload="sedov")
    tuning_stamp = {k: v for k, v in sim.tuning_provenance.items()
                    if k in ("source", "key", "knobs", "explicit")
                    and v not in (None, [], {})}
    # BENCH_TRACE_DIR: capture a jax.profiler trace of the headline
    # window and stamp its per-phase attribution into the JSON — the
    # chip-harvest workflow (docs/NEXT.md round 8: every bench round
    # carries its phase table, `sphexa-telemetry trace` re-renders it)
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    phase_attr = None
    if trace_dir:
        import jax

        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
    std_ups = _measure(sim, n, STEPS)
    if trace_dir:
        jax.profiler.stop_trace()
        print(f"bench: profiler trace -> {trace_dir}", file=sys.stderr)
        from sphexa_tpu.telemetry.traceview import (
            phase_attr_digest,
            summarize_trace,
        )

        phase_attr = phase_attr_digest(summarize_trace(trace_dir))

    extra = {}
    # how the headline run's knobs were chosen (heuristic, or a table
    # entry's key) — existing keys stay byte-compatible, this only adds
    extra["tuning"] = tuning_stamp
    if phase_attr is not None:
        extra["phase_attr"] = phase_attr
    # conservation health of the benched run, free from the in-graph
    # ledger (|etot - etot0| / |etot0| at the last flush): a perf win
    # that leaks energy is not a win, so the bench line carries its own
    # physics evidence next to the throughput number
    if sim.energy_drift is not None:
        import math

        if math.isfinite(sim.energy_drift):
            extra["std_energy_drift"] = float(f"{sim.energy_drift:.3e}")
    n_aux = AUX_SIDE**3
    state, box, const = init_sedov(AUX_SIDE)
    sim = Simulation(state, box, const, prop="ve", block=8192,
                     check_every=AUX_STEPS, telemetry=tel,
                     obs_spec=ObservableSpec())
    ve_ups = _measure(sim, n_aux, AUX_STEPS)
    extra["ve_updates_per_sec"] = round(ve_ups, 1)
    extra["ve_side"] = AUX_SIDE
    extra["ve_vs_baseline"] = round(ve_ups / BASELINE_UPDATES_PER_SEC, 4)
    state, box, const = init_evrard(AUX_SIDE)
    sim = Simulation(state, box, const, prop="ve", block=8192,
                     check_every=AUX_STEPS, telemetry=tel,
                     obs_spec=ObservableSpec())
    nev = int(state.n)
    veg_ups = _measure(sim, nev, AUX_STEPS)
    extra["ve_gravity_updates_per_sec"] = round(veg_ups, 1)
    extra["ve_gravity_n"] = nev
    extra["ve_gravity_vs_baseline"] = round(
        veg_ups / BASELINE_UPDATES_PER_SEC, 4
    )
    # gravity at >=1e6 particles (VERDICT r3 #4): the Barnes-Hut solve
    # alone on a 1M Plummer sphere (the centrally-concentrated
    # distribution that stresses the MAC), dense classification at the
    # coarse target_block the Simulation picks at this N
    extra.update(_gravity_scale_line())

    # per-run health counters from the shared registry (a clean bench
    # window should show retraces only from first compiles; the
    # reconfigures counter excludes each Simulation's initial sizing)
    extra["telemetry"] = {
        "retraces": int(tel.counters.get("retraces", 0)),
        "rollbacks": int(tel.counters.get("rollbacks", 0)),
        "reconfigures": int(tel.counters.get("reconfigures", 0)),
        # distributed health (schema v2): zero on single-chip benches,
        # nonzero = the mesh run resized halos / tripped the watchdog
        "halo_trips": int(tel.counters.get("halo_trips", 0)),
        "imbalances": int(tel.counters.get("imbalances", 0)),
        # physics health (schema v3): nonzero = a benched sim produced
        # nonfinite rho/h/du (drift watchdog stays off here — benches
        # run without a budget; the drift itself is std_energy_drift)
        "field_health": int(tel.counters.get("field_health", 0)),
    }

    # measured breakdowns/commentary live in docs/NEXT.md, labeled with the
    # hardware + commit they were taken on — repeating them here would
    # assert stale numbers on every future run. The manifest stamp makes
    # bench rounds diffable (`sphexa-telemetry diff BENCH_rA.json
    # BENCH_rB.json`) — existing keys stay byte-compatible.
    print(
        json.dumps(
            {
                "metric": f"particle-updates/sec/chip (Sedov {SIDE}^3, std SPH)",
                "value": round(std_ups, 1),
                "unit": "particles/s",
                "vs_baseline": round(std_ups / BASELINE_UPDATES_PER_SEC, 4),
                "device": {"platform": dev.platform, "kind": dev.kind,
                           "count": dev.count},
                "extra": extra,
                "manifest": build_manifest(
                    config={"side": SIDE, "steps": STEPS,
                            "aux_side": AUX_SIDE, "aux_steps": AUX_STEPS,
                            "block": 8192, "prop": "std"},
                    particles=n,
                ),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
