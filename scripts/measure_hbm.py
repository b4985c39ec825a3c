"""Peak-HBM audit of the VE step at increasing single-chip N
(VERDICT r3 #8): measure device peak_bytes_in_use after a settled step,
derive bytes/particle, and extrapolate to the 400^3 / 16-chip target
(64M particles -> 4M/chip).

Built on the shared HBM accounting surface (telemetry/memory.py): the
same per-device ``memory_stats()`` snapshot the runtime ``memory``
events stamp at manifest/post-compile/flush, so this script's numbers
and a run's events.jsonl are the same quantity. ``--profile-dir`` also
dumps a ``jax.profiler`` device-memory profile (pprof) per size — the
allocation-site breakdown behind a surprising peak.

``--calibrate`` prints the static JXA202 liveness estimate (the same
model ``sphexa-audit preflight`` gates the campaign on, evaluated at the
measured N — no rescale) next to each measured peak and exits 1 when
they diverge by more than 20%: the check that keeps the preflight gate
honest against real allocator behavior. On backends without
``memory_stats()`` (CPU) it prints the estimate alone and exits 0.

Usage: [HBM_SIDES=100,126,159] python scripts/measure_hbm.py
       [--devices N] [--profile-dir DIR] [--calibrate]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from sphexa_tpu.init import init_sedov
from sphexa_tpu.simulation import Simulation
from sphexa_tpu.telemetry.memory import (
    device_memory_snapshot,
    save_memory_profile,
)

SIDES = [int(s) for s in os.environ.get("HBM_SIDES", "100,126,159,200").split(",")]


def _static_estimate(sim, n):
    """The JXA202 liveness model on the step this sim actually runs:
    per-device peak bytes at the measured N (ratio 0 = no campaign
    rescale), donation credited only when the sim donates."""
    import dataclasses

    from sphexa_tpu import propagator as prop
    from sphexa_tpu.devtools.audit.spmd import _peak_liveness

    cfg = sim._cfg
    P = 1
    if sim._mesh is not None:
        P = sim._mesh.size
        hi = sim._halo_info or {}
        cfg = dataclasses.replace(
            cfg, mesh=sim._mesh, shard_axis="p",
            halo_window=hi.get("wmax", 0),
            halo_cells=tuple(hi.get("caps", ())),
            halo_runs=hi.get("run_slots", 0),
        )
    closed = jax.make_jaxpr(
        lambda s, b: prop.step_hydro_ve(s, b, cfg, None)
    )(sim.state, sim.box)
    donated = set()
    if sim._donate_active:
        donated = set(range(len(jax.tree_util.tree_leaves(sim.state))))
    peak, _ = _peak_liveness(closed.jaxpr, P, n // P, 0.0, donated)
    return peak


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="shard over N devices (per-device peaks reported)")
    ap.add_argument("--profile-dir", default=None, dest="profile_dir",
                    help="write a device-memory profile (pprof) per size")
    ap.add_argument("--calibrate", action="store_true",
                    help="print the JXA202 static liveness estimate next "
                         "to each measured peak; exit 1 on >20% divergence")
    args = ap.parse_args(argv)
    if args.profile_dir:
        os.makedirs(args.profile_dir, exist_ok=True)
    worst_divergence = 0.0
    for side in SIDES:
        n = side ** 3
        try:
            state, box, const = init_sedov(side)
            if args.devices and n % args.devices:
                keep = (n // args.devices) * args.devices
                state = jax.tree.map(
                    lambda a: a[:keep] if getattr(a, "ndim", 0) == 1 else a,
                    state)
                n = keep
            sim = Simulation(state, box, const, prop="ve", block=8192,
                             check_every=5, num_devices=args.devices)
            for _ in range(5):
                sim.step()
            sim.flush()
            jax.block_until_ready(sim.state.x)
            snap = device_memory_snapshot()
            peaks = snap["peak_bytes_in_use"]
            lives = snap["bytes_in_use"]
            est = _static_estimate(sim, n) if args.calibrate else None
            if not peaks:
                suffix = ""
                if est is not None:
                    suffix = (f"  static estimate={est/2**30:.2f} GiB/dev "
                              f"(no measurement to calibrate against)")
                print(f"side={side} n={n} (backend reports no "
                      f"memory_stats — CPU?){suffix}", flush=True)
            else:
                peak, cur = max(peaks), max(lives)
                per_dev = "" if len(peaks) == 1 else (
                    "  per-dev peaks: "
                    + " ".join(f"{p/2**30:.2f}" for p in peaks))
                cal = ""
                if est is not None:
                    div = abs(est - peak) / peak
                    worst_divergence = max(worst_divergence, div)
                    cal = (f"  static={est/2**30:.2f} GiB "
                           f"(divergence {div:+.0%})")
                print(f"side={side} n={n} peak={peak/2**30:.2f} GiB "
                      f"({sum(peaks)/n:.0f} B/particle) "
                      f"live={cur/2**30:.2f} GiB{per_dev}{cal}", flush=True)
            if args.profile_dir:
                path = os.path.join(args.profile_dir, f"hbm_s{side}.pprof")
                if save_memory_profile(path):
                    print(f"  memory profile -> {path}", flush=True)
            del sim, state
        except Exception as e:
            print(f"side={side} n={n} FAILED: {type(e).__name__}: {e}"[:160],
                  flush=True)
            break
    # extrapolation guide printed for BASELINE.md
    print("target: 64M/16 chips = 4.0M particles/chip; v5e HBM = 16 GiB",
          flush=True)
    if args.calibrate and worst_divergence > 0.20:
        print(f"CALIBRATION FAILED: static estimate diverges "
              f"{worst_divergence:.0%} from measured peak (>20%) — "
              f"re-derive the JXA202 liveness model before trusting "
              f"preflight", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
