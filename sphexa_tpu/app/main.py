"""sphexa-tpu command-line front-end.

Counterpart of the reference's ``main/src/sphexa/sphexa.cpp`` CLI: the same
flag vocabulary (--init, -n, -s, -w, --prop, --quiet, ...), factory wiring
from case name to initializer, and the iteration loop with per-step console
reporting. Flags the TPU build does not support yet are accepted and
reported, not silently ignored.
"""

import argparse
import dataclasses as _dc
import os
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sphexa-tpu",
        description="TPU-native SPH simulation (Sedov, Noh, ... test cases)",
    )
    p.add_argument("--init", default="sedov",
                   help="test case name (sedov, ...), case:settings.json, "
                        "case+capability, or a snapshot file")
    p.add_argument("-n", type=int, default=50, dest="side",
                   help="particles per cube side (N = n^3)")
    p.add_argument("-s", type=float, default=10, dest="stop",
                   help="integer: number of iterations; float: simulated time")
    p.add_argument("-w", type=float, default=-1, dest="write_every",
                   help="integer: dump every N iterations; float: every t interval")
    p.add_argument("-f", default="", dest="out_fields", help="fields to dump")
    p.add_argument("-o", "--outDir", default=".", dest="out_dir")
    p.add_argument("--prop", default="std",
                   help="propagator: std | ve | turb-ve | std-cooling | nbody")
    p.add_argument("--quiet", action="store_true")
    p.add_argument("--avclean", action="store_true")
    p.add_argument("--theta", type=float, default=0.5,
                   help="gravity MAC accuracy parameter [0.5]")
    p.add_argument("--G", type=float, default=None, dest="grav_constant",
                   help="gravitational constant override (enables gravity)")
    p.add_argument("--m2p-cap-margin", type=float, default=None,
                   dest="m2p_cap_margin",
                   help="gravity M2P interaction-list cap margin [1.3]; "
                        "the M2P eval cost is linear in the cap, overflow "
                        "is diagnostic-guarded and auto-regrown; unset, "
                        "--tuned may resolve it from the tuning table")
    p.add_argument("--sym-pairs", default=None, choices=("on", "off"),
                   dest="sym_pairs",
                   help="momentum/energy pair-cutoff convention: on = min-h "
                        "symmetric (default), off = reference-parity "
                        "one-sided; overrides the snapshot's symPairs attr")
    p.add_argument("--glass", default=None,
                   help="glass template HDF5 file, tiled into every "
                        "lattice-based IC (init/utils.hpp glass blocks); "
                        "without it a procedural jittered lattice is used")
    p.add_argument("--wextra", default="",
                   help="comma-separated extra output triggers: integers = "
                        "iterations, floats = simulation times")
    p.add_argument("--ascii", action="store_true",
                   help="dump ASCII columns instead of HDF5 (not restartable)")
    p.add_argument("--duration", type=float, default=None,
                   help="maximum wall-clock run time in seconds; dumps a "
                        "final snapshot before exiting if -w is enabled")
    p.add_argument("--profile", action="store_true",
                   help="save a per-iteration timing series to profile.npz")
    p.add_argument("--telemetry-dir", default=None, dest="telemetry_dir",
                   help="write structured run telemetry (manifest.json + "
                        "events.jsonl) to this directory; summarize/diff "
                        "it with sphexa-telemetry (docs/OBSERVABILITY.md)")
    p.add_argument("--trace-dir", default=None, dest="trace_dir",
                   help="capture a jax.profiler trace of the run into "
                        "this directory (launch/flush/reconfigure scopes "
                        "are TraceAnnotation-named); view with "
                        "tensorboard/xprof")
    p.add_argument("--devices", type=int, default=None,
                   help="shard the run over N devices (SFC-slab domain "
                        "decomposition; default: single device)")
    p.add_argument("--cpu-mesh", action="store_true", dest="cpu_mesh",
                   help="force an N-virtual-device CPU mesh for --devices "
                        "runs on hosts with fewer real chips (validation "
                        "mode; same mechanism as the multi-chip dry run)")
    p.add_argument("--halo-mode", default="sparse",
                   choices=("sparse", "windowed"), dest="halo_mode",
                   help="multi-chip halo exchange: sparse cell-granular "
                        "per-distance buffers (default) or contiguous "
                        "per-peer windows")
    p.add_argument("--backend", default="auto",
                   choices=("auto", "pallas", "xla"),
                   help="force the engine backend (auto: pallas on TPU, "
                        "xla elsewhere); pallas off-TPU runs the Mosaic "
                        "kernels in interpret mode — the CPU-mesh "
                        "rehearsal path the multi-chip dry run uses")
    p.add_argument("--check-every", type=int, default=None,
                   dest="check_every",
                   help="deferred cap-checking window: launch up to N "
                        "steps (fewer where the pair list covers fewer) "
                        "with no device sync, fetch/verify diagnostics "
                        "in one batch at the window end (default 1 = "
                        "synchronous; unset, --tuned may resolve it "
                        "from the tuning table)")
    p.add_argument("--dt-bins", type=int, default=None, dest="dt_bins",
                   help="hierarchical block time steps: number of "
                        "power-of-two per-particle dt bins (std/ve "
                        "propagators; unset = the global-dt path, 1 = "
                        "bitwise-identical to it; docs/OBSERVABILITY.md "
                        "schema v6)")
    p.add_argument("--bin-sync-every", type=int, default=None,
                   dest="bin_sync_every",
                   help="cycles between bin reassignments at the sync "
                        "substep (block-dt mode; default 1)")
    p.add_argument("--bin-resort-drift", type=float, default=None,
                   dest="bin_resort_drift",
                   help="drift-aware resort threshold: keep the current "
                        "particle order while folded-key inversions stay "
                        "under this fraction of n (block-dt mode; "
                        "default 0 = resort on any inversion)")
    p.add_argument("--tuned", default=None,
                   help="resolve engine knobs through a committed tuning "
                        "table (docs/TUNING.md): 'auto' = the repo's "
                        "TUNING_TABLE.json, or a table path; explicit "
                        "flags always win over table entries")
    p.add_argument("--imbalance-ratio", type=float, default=1.5,
                   dest="imbalance_ratio",
                   help="imbalance-watchdog threshold on max/mean of the "
                        "per-shard load/comm metrics (telemetry "
                        "'imbalance' events) [1.5]")
    p.add_argument("--drift-budget", type=float, default=None,
                   dest="drift_budget",
                   help="conservation-drift watchdog: relative "
                        "total-energy budget |etot-etot0|/|etot0| per "
                        "check window (telemetry 'drift' events; "
                        "default: report-only, no watchdog)")
    p.add_argument("--memory-profile", default=None, dest="memory_profile",
                   help="write a jax.profiler device-memory profile "
                        "(pprof) to this path at the end of the run")
    p.add_argument("--insitu", default=None,
                   help="in-situ rendering: slice | projection (the "
                        "Ascent/Catalyst adaptor role, ascent_adaptor.h). "
                        "Frames render from the in-graph snapshot ring at "
                        "the check/flush boundary — zero added host syncs "
                        "(docs/OBSERVABILITY.md schema v8)")
    p.add_argument("--insitu-every", type=int, default=1, dest="insitu_every",
                   help="render every N iterations (default 1)")
    p.add_argument("--snap", default=None,
                   help="in-graph field snapshots riding the flush "
                        "boundary: comma-separated field list (e.g. "
                        "'rho' or 'rho,temp'; observables/snapshot.py). "
                        "Emits schema-v8 snapshot events + a snapshots/ "
                        ".npz ring next to events.jsonl (or --output)")
    p.add_argument("--snap-grid", type=int, default=16, dest="snap_grid",
                   help="snapshot grid side G (G x G projection) [16]")
    p.add_argument("--snap-every", type=int, default=None,
                   dest="snap_every",
                   help="emit a snapshot frame every N iterations "
                        "[--insitu-every when --insitu is on, else 1]")
    p.add_argument("--snap-keep", type=int, default=32, dest="snap_keep",
                   help="snapshot ring capacity in .npz frames (0 = "
                        "unbounded) [32]")
    p.add_argument("--kernel", default=None,
                   help="SPH kernel family: sinc | sinc-n1-n2 | wendland-c6 "
                        "(sph_kernel_tables.hpp SphKernelType)")
    p.add_argument("--debug-checks", action="store_true", dest="debug_checks",
                   help="run the step under the checkify sanitizer "
                        "(NaN/Inf + out-of-bounds-index checks); the "
                        "first failed check per step is reported per "
                        "iteration (slow; single-device)")
    p.add_argument("--sincIndex", type=float, default=None, dest="sinc_index",
                   help="sinc kernel exponent n (default: case setting)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.cpu_mesh:
        # explicit N-virtual-device CPU mesh (the mechanism the multi-chip
        # dry run and tests use) for driving --devices N on hosts with
        # fewer real chips; must run before jax's lazy backend init
        from sphexa_tpu.util.cpu_mesh import force_cpu_mesh

        try:
            force_cpu_mesh(args.devices or 8)
        except RuntimeError as e:
            print(f"--cpu-mesh: {e}", file=sys.stderr)
            return 2

    # persistent compile cache, placed before the first jit
    # (JAX_COMPILATION_CACHE_DIR, else on a TPU <checkout>/.jax_cache)
    from sphexa_tpu.util.device import device_info, enable_compile_cache

    enable_compile_cache()

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables import (
        ConstantsWriter,
        make_observable,
        make_observable_spec,
    )
    from sphexa_tpu.simulation import _PROPAGATORS, Simulation

    if args.prop not in _PROPAGATORS:
        print(f"unknown --prop {args.prop!r}; available: {sorted(_PROPAGATORS)}",
              file=sys.stderr)
        return 2
    if args.avclean and args.prop not in ("ve", "turb-ve"):
        print("--avclean only applies to --prop ve | turb-ve; ignoring",
              file=sys.stderr)

    # built-in case names take precedence over same-named files, exactly
    # like make_initializer; a restart reads the snapshot ONCE, recovering
    # state, metadata and any checkpointed turbulence stirring state
    from sphexa_tpu.init import CASES, split_case_spec
    from sphexa_tpu.init.file_init import looks_like_file, parse_file_spec

    log = (lambda *a, **k: None) if args.quiet else print
    # 'case:settings.json' selects the case with overrides; observables key
    # on the bare case name (with the overrides applied to their thresholds)
    try:
        case_name, settings_path = split_case_spec(args.init)
    except ValueError as e:  # a capability this program lacks
        print(e, file=sys.stderr)
        return 2
    case_overrides = None
    if settings_path is not None:
        import json

        try:
            with open(settings_path) as f:
                case_overrides = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read settings file {settings_path}: {e}",
                  file=sys.stderr)
            return 2
        if not isinstance(case_overrides, dict):
            print(f"{settings_path} must hold a JSON object", file=sys.stderr)
            return 2
    is_restart = args.init not in CASES and looks_like_file(args.init)
    turb_state, turb_cfg, restart_iteration = None, None, 0
    chem_restored = None
    if is_restart:
        from sphexa_tpu.io.snapshot import read_snapshot_full

        state, box, const, extra, attrs = read_snapshot_full(
            *parse_file_spec(args.init)
        )
        restart_iteration = int(attrs.get("iteration", 0))
        case_name = (
            np.asarray(attrs["initCase"]).item().decode()
            if "initCase" in attrs
            else ""
        )
        if case_overrides is None and "caseSettings" in attrs:
            # threshold-bearing observables (e.g. WindBubble) must see the
            # same overrides the original run used
            import json

            case_overrides = json.loads(
                np.asarray(attrs["caseSettings"]).item().decode()
            )
        if args.prop == "std-cooling" and "chem_hi" in extra:
            from sphexa_tpu.physics.cooling import chemistry_from_fields

            chem_restored = chemistry_from_fields(extra)
        if args.prop == "turb-ve" and "turb_phases" in extra:
            # resume the OU stirring state + config (the reference
            # checkpoints phases + RNG the same way, turb_ve.hpp:88-97)
            from sphexa_tpu.sph.hydro_turb import turbulence_state_from_fields

            turb_state, turb_cfg = turbulence_state_from_fields(extra)
    else:
        if args.glass:
            from sphexa_tpu.init.glass import set_glass_template

            try:
                set_glass_template(args.glass)
            except OSError as e:
                print(f"cannot read glass template {args.glass}: {e}",
                      file=sys.stderr)
                return 2
            log(f"# tiling glass template {args.glass}")
        try:
            initializer = make_initializer(args.init)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2
        try:
            state, box, const = initializer(args.side)
        finally:
            if args.glass:
                set_glass_template(None)

    if args.grav_constant is not None:
        # --G overrides the case's gravitational constant (sphexa.cpp --G)
        const = _dc.replace(const, g=args.grav_constant)
    if args.sym_pairs is not None:
        # explicit pair-cutoff convention override: reference-parity
        # comparisons and continuations of dumps that predate the
        # symPairs snapshot attribute need this (README round-4 notes)
        const = _dc.replace(const, sym_pairs=(args.sym_pairs == "on"))
    if args.kernel is not None or args.sinc_index is not None:
        from sphexa_tpu.sph.kernels import KERNEL_CHOICES, kernel_norm_3d

        kind = args.kernel or const.kernel_choice
        if kind not in KERNEL_CHOICES:
            print(f"unknown --kernel {kind!r}; choices: {KERNEL_CHOICES}",
                  file=sys.stderr)
            return 2
        n = args.sinc_index if args.sinc_index is not None else const.sinc_index
        const = _dc.replace(
            const, kernel_choice=kind, sinc_index=n,
            kernel_norm=kernel_norm_3d(n, kind),
        )

    # observable selected by the test case (observables/factory.hpp:46-70) —
    # on restart, by the case name the snapshot recorded. The observable
    # object only names the constants.txt columns now: the values are
    # computed IN-GRAPH by the step's science ledger (the matching
    # ObservableSpec below), so no second reduction program and no
    # per-step device sync remain — rows survive --check-every windows
    observable = make_observable(case_name, overrides=case_overrides)
    obs_spec = make_observable_spec(case_name, overrides=case_overrides)
    if args.devices and args.devices > 1 and state.n % args.devices:
        # slab sharding needs a mesh-divisible count; trim the trailing
        # SFC rows (cases with non-cubic counts, e.g. sphere cuts, already
        # truncate at an arbitrary boundary — this moves it by < P rows)
        import jax as _jax

        n_full = state.n
        keep = (n_full // args.devices) * args.devices
        print(f"# trimming {n_full - keep} trailing particles for an "
              f"even {args.devices}-way slab decomposition", file=sys.stderr)
        trim = lambda tree: _jax.tree.map(
            lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
            and a.shape[0] == n_full else a,
            tree,
        )
        state = trim(state)
        # per-particle aux state (std-cooling chemistry) must stay
        # row-aligned with the trimmed particle arrays
        if chem_restored is not None:
            chem_restored = trim(chem_restored)
    # telemetry registry shared by the driver, the loop and the dump's
    # spans; --telemetry-dir adds the persisted JSONL sink (the sink-less
    # registry costs counters only)
    from sphexa_tpu.telemetry import JsonlSink, Telemetry
    from sphexa_tpu.telemetry.registry import set_current

    sinks = []
    recorder = None
    if args.telemetry_dir:
        sinks.append(JsonlSink(os.path.join(args.telemetry_dir,
                                            "events.jsonl")))
    telemetry = Telemetry(sinks=sinks)
    set_current(telemetry)
    if args.telemetry_dir:
        # crash flight recorder: ring-buffer the event tail and dump
        # blackbox.json (+ a first-class ``crash`` event) on abnormal
        # exit, so a killed/OOM'd/aborted run EXPLAINS its truncated
        # events.jsonl (telemetry/flightrec.py; summary/science read it)
        from sphexa_tpu.telemetry import FlightRecorder

        recorder = FlightRecorder(args.telemetry_dir, telemetry=telemetry)
        telemetry.sinks.append(recorder.sink)
        recorder.install()

    # --snap: in-graph field snapshots riding the flush boundary
    # (observables/snapshot.py). --insitu without an explicit --snap
    # defaults to a density grid so the viz hook consumes the ring
    # instead of syncing full particle state every frame.
    snap_spec = None
    snap_every = None
    snap_dir = None
    snap_fields = None
    if args.snap:
        snap_fields = tuple(f.strip() for f in args.snap.split(",")
                            if f.strip())
    elif args.insitu:
        snap_fields = ("rho",)
    if snap_fields:
        from sphexa_tpu.observables.snapshot import SnapshotSpec

        try:
            snap_spec = SnapshotSpec(fields=snap_fields, grid=args.snap_grid)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            if recorder is not None:
                recorder.close()  # usage error, not a crash: no blackbox
            return 2
        snap_every = args.snap_every or (
            args.insitu_every if args.insitu else 1)
        if args.telemetry_dir:
            snap_dir = os.path.join(args.telemetry_dir, "snapshots")
        else:
            snap_dir = os.path.join(args.out_dir, "snapshots")
    try:
        sim = Simulation(state, box, const, prop=args.prop,
                         av_clean=args.avclean and args.prop in ("ve", "turb-ve"),
                         turb_state=turb_state, turb_cfg=turb_cfg,
                         chem=chem_restored,
                         theta=args.theta,
                         m2p_cap_margin=args.m2p_cap_margin,
                         num_devices=args.devices, halo_mode=args.halo_mode,
                         backend=args.backend,
                         check_every=args.check_every,
                         dt_bins=args.dt_bins,
                         bin_sync_every=args.bin_sync_every,
                         bin_resort_drift=args.bin_resort_drift,
                         imbalance_ratio=args.imbalance_ratio,
                         obs_spec=obs_spec, science_rows=True,
                         snap_spec=snap_spec, snap_every=snap_every,
                         snap_keep=args.snap_keep, snap_dir=snap_dir,
                         drift_budget=args.drift_budget,
                         debug_checks=args.debug_checks, telemetry=telemetry,
                         tuned=args.tuned,
                         workload=case_name or args.init)
    except (NotImplementedError, ValueError) as e:
        print(str(e), file=sys.stderr)
        if recorder is not None:
            # a run that cannot even construct is an abnormal end: leave
            # a blackbox naming the cause, then disarm cleanly
            recorder.dump(reason=f"simulation construction failed: {e}")
            recorder.close()
        return 2
    # the Simulation owns the (placed, possibly sharded) state from here on:
    # drop this frame's reference so the initializer's device-0 copy is
    # freed instead of riding along for the whole run
    n_particles, t_start = state.n, float(state.ttot)
    del state
    if args.telemetry_dir:
        from sphexa_tpu.telemetry import emit_memory_event, write_manifest

        mesh = getattr(sim, "_mesh", None)
        recorder.manifest = write_manifest(
            args.telemetry_dir,
            config={k: v for k, v in vars(args).items()
                    if isinstance(v, (str, int, float, bool, type(None)))},
            particles=n_particles,
            mesh_shape=tuple(mesh.devices.shape) if mesh is not None
            else None,
            extra={"case": case_name or args.init, "prop": args.prop,
                   # which knobs the run is actually using and why —
                   # the manifest-side half of the `tuning` event, so
                   # history/diff can attribute a perf change to a knob
                   # change (docs/TUNING.md)
                   "tuning": sim.tuning_provenance},
        )
        # manifest-point HBM snapshot: pre-compile residency (the state
        # arrays + constants), the baseline the post-compile and flush
        # snapshots are read against (docs/OBSERVABILITY.md)
        emit_memory_event(
            telemetry, "manifest",
            devices=list(mesh.devices.flat) if mesh is not None else None,
        )
        log(f"# telemetry -> {args.telemetry_dir}")
    # the resolved engine and the device it runs on, so no console log can
    # be read as a chip run when it was not
    dev = device_info()
    log(f"# sphexa-tpu --init {args.init} N={n_particles} prop={args.prop} "
        f"backend={sim._cfg.backend} platform={dev.platform} "
        f"device_kind={dev.kind!r} devices={dev.count}")

    # resuming from a snapshot continues the iteration numbering, and an
    # integer -s is the END iteration (sphexa.cpp main-loop semantics)
    if is_restart:
        sim.iteration = restart_iteration
        log(f"# restart from iteration {sim.iteration}, t={t_start:.6g}"
            + (f" (case {case_name})" if case_name else ""))

    num_steps = int(args.stop) if float(args.stop).is_integer() else None
    target_time = None if num_steps is not None else float(args.stop)

    os.makedirs(args.out_dir, exist_ok=True)

    # -w: integer = dump every N iterations, float = every t interval
    # (arg_parser.hpp:99-118 int-vs-float dispatch, same as -s)
    dump_path = None
    w = args.write_every
    w_steps = int(w) if w > 0 and float(w).is_integer() else None
    w_time = w if w > 0 and w_steps is None else None
    next_dump_time = [t_start + w_time] if w_time else None
    if w > 0 or args.wextra:
        # on restart, keep dumping under the ORIGINAL case's name (the
        # reference appends Step#n to the restarted file) instead of a
        # mangled snapshot-path tag that grows on every restart
        tag_src = case_name if (is_restart and case_name) else args.init
        case_tag = "".join(c if c.isalnum() else "_" for c in tag_src)
        ext = "txt" if args.ascii else "h5"
        dump_path = f"{args.out_dir}/dump_{case_tag}.{ext}"
        # drop leftovers of a previous run (would interleave old steps);
        # a restart instead APPENDS new Step#n groups to the existing dump
        import glob as _glob

        if args.ascii:
            stale = _glob.glob(f"{args.out_dir}/dump_{case_tag}_it*.txt")
        elif not is_restart:
            # base file AND any sharded part files (a leftover part set
            # from a previous run — possibly with a DIFFERENT device
            # count — would be appended to / concatenated with new parts)
            from sphexa_tpu.io.snapshot import _find_parts

            stale = ([dump_path] if os.path.exists(dump_path) else [])
            stale += _find_parts(dump_path)
        else:
            stale = []
        for f in stale:
            print(f"# removing stale {f}", file=sys.stderr)
            os.remove(f)

    want_fields = [f for f in args.out_fields.split(",") if f]

    # --wextra: one-shot triggers, integers = iterations, floats = sim
    # times (arg_parser.hpp isExtraOutputStep)
    wextra_steps, wextra_times = set(), []
    for tok in (t for t in args.wextra.split(",") if t):
        try:
            val = float(tok)
        except ValueError:
            print(f"--wextra: cannot parse {tok!r} (expected comma-separated "
                  "integers or floats)", file=sys.stderr)
            if recorder is not None:
                recorder.close()  # usage error, not a crash: no blackbox
            return 2
        if val.is_integer() and "." not in tok:
            wextra_steps.add(int(val))
        else:
            wextra_times.append(val)
    wextra_times.sort()

    constants_path = f"{args.out_dir}/constants.txt"
    if not is_restart and os.path.exists(constants_path):
        print(f"# truncating stale {constants_path}", file=sys.stderr)
        os.remove(constants_path)
    constants = ConstantsWriter(
        constants_path, observable,
        restart_iteration=restart_iteration if is_restart else None,
    )

    def write_science_rows():
        """Drain the verified in-graph ledger rows into constants.txt —
        one row per step (deferred windows land whole at their flush
        boundary, so --check-every N loses no science). The scalars were
        fetched at the Simulation's existing check boundary: writing
        them is pure host I/O, no device sync."""
        rows = sim.drain_science()
        for r in rows:
            vals = [r["it"], r["t"], r["dt"], r["etot"], r["ecin"],
                    r["eint"], r["egrav"]]
            if "extra" in r:
                vals.append(r["extra"])
            constants.write_row(vals)
        return rows

    def output_fields():
        from sphexa_tpu.analysis import compute_output_fields

        pipeline = "ve" if args.prop in ("ve", "turb-ve") else "std"
        return compute_output_fields(sim.state, sim.box, sim.active_cfg,
                                     pipeline=pipeline)

    last_dump_iteration = [None]

    def dump_now(it):
        """Write one output (restartable HDF5 snapshot, or ASCII columns
        with --ascii); derived fields are recomputed like the reference's
        saveFields pass, consistently with the active propagator."""
        with telemetry.span("sphexa:dump"):
            last_dump_iteration[0] = it
            extra = output_fields()
            if want_fields:
                unknown = [f for f in want_fields if f not in extra]
                if unknown:
                    print(f"# -f fields not available, skipped: {unknown}",
                          file=sys.stderr)
                extra = {k: v for k, v in extra.items() if k in want_fields}

            if args.ascii:
                from sphexa_tpu.io import write_ascii
                from sphexa_tpu.io.snapshot import CONSERVED_FIELDS

                cols = {f: np.asarray(getattr(sim.state, f)) for f in CONSERVED_FIELDS}
                cols.update(extra)
                path = dump_path.replace(".txt", f"_it{it}.txt")
                write_ascii(path, cols)
                log(f"# wrote ASCII dump -> {path} (not restartable)")
                return

            from sphexa_tpu.io import write_snapshot
            from sphexa_tpu.io.snapshot import write_snapshot_sharded

            if sim.turb_state is not None:
                from sphexa_tpu.sph.hydro_turb import turbulence_state_to_fields

                extra = {
                    **extra,
                    **turbulence_state_to_fields(sim.turb_state, sim.turb_cfg),
                }
            if sim.chem is not None:
                from sphexa_tpu.physics.cooling import chemistry_to_fields

                extra = {**extra, **chemistry_to_fields(sim.chem)}
            # on a mesh, dump file-per-shard (no global gather — the
            # reference's parallel MPI-IO role); restart reads the base path
            writer = (write_snapshot_sharded
                      if getattr(sim, "_mesh", None) is not None
                      else write_snapshot)
            step = writer(
                dump_path, sim.state, sim.box, const, iteration=it,
                extra_fields=extra, case=case_name,
                case_settings=case_overrides,
            )
            log(f"# wrote Step#{step} -> {dump_path}")

    def maybe_dump(it):
        """-w schedule + --wextra one-shot triggers."""
        if dump_path is None:
            return
        t_now = float(sim.state.ttot)
        due = (w_steps is not None and it % w_steps == 0) or (
            next_dump_time is not None and t_now >= next_dump_time[0]
        )
        if it in wextra_steps:
            due = True
        while wextra_times and t_now >= wextra_times[0]:
            wextra_times.pop(0)
            due = True
        if not due:
            return
        if next_dump_time is not None:
            # catch up across multi-interval steps: one dump, schedule
            # advanced past t_now (not one redundant dump per interval)
            while t_now >= next_dump_time[0]:
                next_dump_time[0] += w_time
        dump_now(it)

    from sphexa_tpu.util.timer import ProfileRecorder, Timer

    timer = Timer()
    # in-situ viz adaptor: init before the loop, execute per iteration,
    # finalize after (sphexa.cpp:141-142,172,179 hook points)
    insitu = None
    if args.insitu:
        from sphexa_tpu.viz import InsituViz

        try:
            insitu = InsituViz(args.out_dir, mode=args.insitu,
                               every=args.insitu_every)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            if recorder is not None:
                recorder.close()  # usage error, not a crash: no blackbox
            return 2
        insitu.init()

    def consume_snapshots():
        """Feed the in-graph snapshot ring into the viz hook. The frames
        were deposited inside the step and landed at the existing check/
        flush boundary (sim._emit_snapshot), so rendering here is pure
        host pixel work — no device sync, no full-state fetch (the old
        insitu.execute path pulled every particle array per frame)."""
        for fit, fpath in sim.drain_snapshots():
            if insitu is None:
                continue
            try:
                with np.load(fpath, allow_pickle=False) as z:
                    grid = np.asarray(z["grid"])
            except (OSError, ValueError, KeyError):
                continue  # frame pruned from the ring / partial write
            insitu.execute_grid(grid, fit)

    profile = ProfileRecorder()
    t0 = time.time()
    it0 = sim.iteration
    nan = float("nan")
    if args.trace_dir:
        # whole-run profiler capture: the program's host spans
        # (Telemetry.span: sphexa:launch/flush/fetch/dump-*...) are in
        # this trace under the names their ``span`` events carry
        import jax as _jax

        os.makedirs(args.trace_dir, exist_ok=True)
        _jax.profiler.start_trace(args.trace_dir)
        telemetry.event("trace", dir=args.trace_dir)
    try:
        while True:
            timer.start()
            d = sim.step()
            timer.step("step")
            it = sim.iteration
            if args.debug_checks and d.get("check_error"):
                print(f"# debug-checks it {it}: {d['check_error']}",
                      file=sys.stderr)
            if d.get("deferred"):
                # mid-window step (--check-every > 1): NO device->host
                # sync may happen here — observables/constants would
                # fetch state scalars and defeat the deferred window, so
                # they run at check boundaries only (the flush emits the
                # window's telemetry). -s (iterations) and --duration
                # are pure host arithmetic and still apply; a -s TIME
                # target needs state.ttot and so only fires at check
                # boundaries
                timer.pop()
                log(f"it {it:5d}  (deferred check)")
                if num_steps is not None and it >= num_steps:
                    break
                if args.duration is not None \
                        and time.time() - t0 >= args.duration:
                    log(f"# wall-clock limit {args.duration}s reached "
                        f"at iteration {it}")
                    sim.flush()  # verify + land the window's rows
                    write_science_rows()
                    if dump_path is not None \
                            and last_dump_iteration[0] != it:
                        dump_now(it)
                    break
                continue
            rows = write_science_rows()
            timer.step("observables")
            maybe_dump(it)  # dumps recompute the full derived set (r, p, u, ...)
            consume_snapshots()  # ring frames -> PNG (when --insitu)
            timer.step("output")
            laps = timer.pop()
            telemetry.event(
                "phases", it=it, **{k: round(v, 6) for k, v in laps.items()}
            )
            if args.profile:
                profile.record(it, laps, dt=float(d.get("dt", nan)),
                               nc_mean=float(d.get("nc_mean", nan)))
            r = rows[-1] if rows else {}
            extra_cols = " ".join(
                f"{n}={v:.4g}" for n, v in zip(
                    observable.extra_columns,
                    [r["extra"]] if "extra" in r else [])
            )
            log(
                f"it {it:5d}  t={r.get('t', nan):.6g} "
                f"dt={float(d.get('dt', nan)):.4g} "
                f"etot={r.get('etot', nan):.6f} "
                f"ecin={r.get('ecin', nan):.4g} "
                f"eint={r.get('eint', nan):.4g} "
                f"nc~{float(d.get('nc_mean', nan)):.0f}"
                + (f" {extra_cols}" if extra_cols else "")
            )
            if num_steps is not None and it >= num_steps:
                break
            if target_time is not None and float(sim.state.ttot) >= target_time:
                break
            if args.duration is not None and time.time() - t0 >= args.duration:
                # graceful wall-clock cutoff with a final restartable dump
                # (sphexa.cpp:153-173 --duration semantics)
                log(f"# wall-clock limit {args.duration}s reached at iteration {it}")
                if dump_path is not None and last_dump_iteration[0] != it:
                    dump_now(it)
                break
    finally:
        if args.trace_dir:
            _jax.profiler.stop_trace()
            log(f"# profiler trace -> {args.trace_dir}")
            # in-run phase attribution (schema v4): aggregate the capture
            # by sphexa/<phase> scope right here so the run record itself
            # carries the per-phase device-time table (`sphexa-telemetry
            # trace <dir>` re-renders it offline); a failed parse must
            # never take the run down with it
            try:
                from sphexa_tpu.telemetry.traceview import (
                    phase_attr_digest,
                    summarize_trace,
                )

                s = summarize_trace(args.trace_dir, top=3)
                telemetry.event("phase_attr", dir=args.trace_dir,
                                **phase_attr_digest(s))
                log("# phase attribution: "
                    + " ".join(f"{p['phase']}={p['share']:.0%}"
                               for p in s["phases"][:5])
                    + f" (coverage {s['coverage']:.0%})")
            except Exception as e:
                print(f"# trace attribution failed: {e}", file=sys.stderr)
    # drain any open deferred window (--check-every > 1, -s not a
    # multiple): the state must be verified before the final report, the
    # telemetry window/flush events must land (Simulation.run's trailing
    # flush, mirrored) and the window's constants.txt rows with them
    sim.flush()
    write_science_rows()
    consume_snapshots()  # frames landed by the trailing flush
    dt_wall = time.time() - t0
    n_done = sim.iteration - it0
    if args.profile:
        profile_path = f"{args.out_dir}/profile.npz"
        if profile.save(profile_path):
            means = profile.summary()
            log("# profile (mean s/iter): "
                + " ".join(f"{k}={v:.4f}" for k, v in means.items()
                           if k in ("step", "observables", "output")))
            log(f"# timing series -> {profile_path}")
        else:
            print("# --profile: no iterations recorded, profile.npz not "
                  "written", file=sys.stderr)
    if insitu is not None:
        log(f"# insitu: {insitu.finalize()} frames -> {args.out_dir}")
    if args.memory_profile:
        from sphexa_tpu.telemetry import save_memory_profile

        if save_memory_profile(args.memory_profile):
            log(f"# device-memory profile -> {args.memory_profile}")
        else:
            print("# --memory-profile: profiler unavailable, no dump "
                  "written", file=sys.stderr)
    telemetry.event("run_end", iterations=n_done, wall_s=round(dt_wall, 3))
    telemetry.close()
    if recorder is not None:
        recorder.close()  # clean exit: disarm the crash hooks, no blackbox
    log(f"# {n_done} iterations in {dt_wall:.2f}s "
        f"({n_particles * n_done / dt_wall / 1e6:.3f}M particle-updates/s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
