"""``sphexa-tune``: the sweep driver CLI.

Replays a workload (named init case, or reconstructed from a telemetry
run's manifest), sweeps a knob subset under a candidate budget, and
leaves the same artifacts a production run does: the sweep dir is a
telemetry run dir (manifest.json + events.jsonl with one schema-v5
``sweep`` event per candidate, flight-recorder armed so a hard death
leaves blackbox.json), and ``--write-table`` commits the winner into a
TUNING_TABLE.json entry with provenance. Exit codes follow the other
CLIs: 0 = sweep completed with a usable measurement, 1 = no candidate
measured ok (the gate failure), 2 = unusable input.
"""

import argparse
import json
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sphexa-tune",
        description="workload-replay autotuner scored by telemetry "
                    "(docs/TUNING.md)",
    )
    src = p.add_mutually_exclusive_group()
    src.add_argument("--case", default=None,
                     help="named init case to replay (sedov, evrard, ...)")
    src.add_argument("--from-run", default=None, dest="from_run",
                     help="telemetry run dir: replay the workload its "
                          "manifest describes")
    p.add_argument("--side", type=int, default=20,
                   help="particles per cube side with --case (N = side^3)")
    p.add_argument("--prop", default="std", help="propagator with --case")
    p.add_argument("--backend", default="auto",
                   choices=("auto", "pallas", "xla"))
    p.add_argument("--theta", type=float, default=0.5)
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--knobs", default="target_block,blocks_per_chunk,"
                                      "cell_target,gap",
                   help="comma-separated knob subset to sweep "
                        "(registry names, sphexa_tpu/tuning/knobs.py)")
    p.add_argument("--budget", type=int, default=16,
                   help="max measured candidates, baseline included")
    p.add_argument("--steps", type=int, default=6,
                   help="measured steps per candidate (one deferred "
                        "window unless check_every is being swept)")
    p.add_argument("--warmup", type=int, default=1,
                   help="unmeasured warmup windows per candidate")
    p.add_argument("--objective", default="per_step_s",
                   help="per_step_s; phase:<name> to score one phase of "
                        "the device-time table (runs under a trace); or "
                        "static-cost:<name> to score the phase's static "
                        "roofline prediction CHIP-FREE (jaxcost; see "
                        "docs/STATIC_ANALYSIS.md for the calibration "
                        "caveat)")
    p.add_argument("--cost-device", default="v5e", dest="cost_device",
                   help="device model a static-cost objective predicts "
                        "against (devtools/audit/devices.py) [v5e]")
    p.add_argument("--out", default="tune-out",
                   help="sweep run dir (events.jsonl / manifest / "
                        "blackbox land here)")
    p.add_argument("--write-table", default=None, dest="write_table",
                   help="TUNING_TABLE.json to upsert the result into")
    p.add_argument("--commit", default="improved",
                   choices=("improved", "best", "none"),
                   help="what --write-table commits: 'improved' only a "
                        "knob set that beat the baseline; 'best' the "
                        "best ok candidate even at zero/negative win "
                        "(pin a measured config; CI smoke); 'none' dry "
                        "run")
    p.add_argument("--workload", default=None,
                   help="table workload class (default: the case name)")
    p.add_argument("--quiet", action="store_true")
    p.add_argument("--format", default="text", choices=("text", "json"))
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    # resolving the spec before touching jax keeps bad input cheap
    from sphexa_tpu.tuning import (
        ReplaySpec, domains_for, make_entry, load_table, measure_candidate,
        new_table, run_sweep, save_table, spec_from_manifest,
        static_cost_candidate, upsert_entry,
    )

    try:
        if args.from_run:
            spec = spec_from_manifest(args.from_run)
        else:
            from sphexa_tpu.init import CASES, split_case_spec

            case = args.case or "sedov"
            base, _ = split_case_spec(case)
            if base not in CASES:
                raise ValueError(f"unknown case {case!r} "
                                 f"(known: {sorted(CASES)})")
            spec = ReplaySpec(case=case, side=args.side, prop=args.prop,
                              backend=args.backend, theta=args.theta,
                              devices=args.devices)
        domains = domains_for(
            [k for k in args.knobs.split(",") if k])
    except (FileNotFoundError, ValueError, KeyError, OSError,
            json.JSONDecodeError) as e:
        print(f"sphexa-tune: {e}", file=sys.stderr)
        return 2

    from sphexa_tpu.telemetry import (
        FlightRecorder, JsonlSink, Telemetry, write_manifest,
    )

    os.makedirs(args.out, exist_ok=True)
    telemetry = Telemetry(sinks=[JsonlSink(
        os.path.join(args.out, "events.jsonl"))])
    recorder = FlightRecorder(args.out, telemetry=telemetry)
    telemetry.sinks.append(recorder.sink)
    recorder.install()
    recorder.manifest = write_manifest(
        args.out,
        config={"case": spec.case, "side": spec.side, "prop": spec.prop,
                "backend": spec.backend, "theta": spec.theta,
                "devices": spec.devices, "knobs": args.knobs,
                "budget": args.budget, "steps": args.steps,
                "warmup": args.warmup, "objective": args.objective},
        particles=spec.n,
        extra={"case": spec.case, "prop": spec.prop, "sweep": True},
    )

    say = (lambda s: None) if args.quiet else \
        (lambda s: print(f"# tune {s}"))
    trace_root = os.path.join(args.out, "trace")
    counter = {"i": 0}

    def measure(knobs):
        if args.objective.startswith("static-cost:"):
            # chip-free: rank by the jaxcost roofline prediction of one
            # phase — no steps run, no trace captured
            return static_cost_candidate(
                spec, knobs, args.objective.split(":", 1)[1],
                device=args.cost_device)
        td = None
        if args.objective.startswith("phase:"):
            td = os.path.join(trace_root, f"cand{counter['i']}")
        counter["i"] += 1
        return measure_candidate(spec, knobs, steps=args.steps,
                                 warmup=args.warmup,
                                 objective=args.objective, trace_dir=td)

    result = run_sweep(measure, domains, args.budget,
                       telemetry=telemetry, objective=args.objective,
                       log=say)

    base = result["baseline"]
    best = result["best"]
    usable = base is not None and base.get("status") == "ok"
    win = None
    if usable and result["improved"]:
        win = (base["value"] - best["value"]) / base["value"]

    from sphexa_tpu.util.device import resolve_backend

    backend = resolve_backend(spec.backend)
    workload = args.workload or spec.case
    # the decision event: what the sweep concluded, in the same stream
    # as the per-candidate evidence
    telemetry.event(
        "tuning", source="sweep", workload=workload, backend=backend,
        n=spec.n, p=spec.devices or 1, objective=args.objective,
        knobs=best["knobs"], improved=result["improved"],
        candidates=result["candidates"],
        **({"win": round(win, 4)} if win is not None else {}),
    )

    wrote = None
    commit_knobs = best["knobs"]
    if args.write_table and args.commit == "best" and not commit_knobs:
        # baseline won but the caller wants a pinned measured config:
        # commit the best-scoring non-empty ok candidate
        ok = [r for r in result["history"]
              if r.get("status") == "ok" and r["knobs"]
              and isinstance(r.get("value"), (int, float))]
        if ok:
            commit_knobs = min(ok, key=lambda r: r["value"])["knobs"]
    if (args.write_table and args.commit != "none" and commit_knobs
            and (result["improved"] or args.commit == "best")):
        try:
            table = load_table(args.write_table)
        except (FileNotFoundError, ValueError):
            table = new_table()
        cand = next(r for r in result["history"]
                    if r["knobs"] == commit_knobs)
        entry = make_entry(
            workload, spec.n, spec.devices or 1, backend, commit_knobs,
            provenance={
                "source_run": os.path.abspath(args.out),
                "created": time.strftime("%Y-%m-%d"),
                "objective": args.objective,
                "baseline": base.get("value") if usable else None,
                "best": cand.get("value"),
                "win": round(win, 4) if win is not None else None,
            },
        )
        upsert_entry(table, entry)
        save_table(args.write_table, table)
        wrote = args.write_table

    recorder.close()
    telemetry.close()

    if args.format == "json":
        print(json.dumps({"spec": vars(args), "baseline": base,
                          "best": best if result["improved"] else None,
                          "win": win, "candidates": result["candidates"],
                          "table": wrote}, default=str))
    else:
        if usable:
            say(f"baseline {args.objective}={base['value']:.6g}")
        if result["improved"]:
            say(f"best {best['knobs']} -> {best['value']:.6g} "
                f"(win {100 * win:.1f}%)")
        else:
            say("no candidate beat the baseline")
        if wrote:
            say(f"table entry written to {wrote}")
    ok_any = any(r.get("status") == "ok" for r in result["history"])
    return 0 if ok_any else 1


if __name__ == "__main__":
    sys.exit(main())
