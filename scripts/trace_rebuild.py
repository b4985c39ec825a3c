"""Trace ONE forced pair-list rebuild of a list cell on the chip and break
it down by (phase, stage) and by device op (benchmarks/stage_times.py).

    python3 scripts/trace_rebuild.py --workload noh-std-1m.steady \
        [--cycles 6] [--out chiprun_out/rebuild.json]

The benchmark's traced cycle holds no rebuild since PR 26 (the planner
puts rebuilds at window boundaries, and the traced cycle is a clean one),
so `sort_nbr_ms_step` reads 0 in a list cell; this is the by-hand reading
PERF.md's rebuild breakdowns come from. Builds the cell as
benchmarks/run.py does, warms it up, runs ``--cycles`` traffic cycles to
leave the IC's transient, then captures `Simulation._rebuild_lists` and one
step. Every time it prints is a device time from the capture; run it
through the chip tool (`--side N` rehearses the control flow on the CPU,
with the Mosaic kernels interpreted, and prints no device time).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="noh-std-1m.steady")
    ap.add_argument("--cycles", type=int, default=6)
    ap.add_argument("--out", default=None)
    ap.add_argument("--side", type=int, default=None,
                    help="CPU rehearsal at this lattice side")
    args = ap.parse_args(argv)

    import run  # benchmarks/run.py; puts the checkout's root on sys.path
    import stage_times
    import trace_reduce
    if args.side:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import rehearse_lists_cpu

        rehearse_lists_cpu.steer_auto_to_pallas()
    import jax

    from sphexa_tpu.telemetry.sinks import MemorySink
    from sphexa_tpu.util.device import enable_compile_cache

    enable_compile_cache()
    _, _, config, traffic = run.load_cell(args.workload)
    if args.side:
        config["side"] = args.side
    sink = MemorySink()
    sim, _ = run.build_simulation(config, traffic, sink)
    run.warm_up(sim, sink, None)
    for _ in range(args.cycles):
        for _ in range(traffic["steps_per_cycle"]):
            sim.step()
        sim.flush()
    jax.block_until_ready(sim.state)

    trace_dir = os.path.join(ROOT, "benchmarks", "out", "trace_rebuild")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sim._rebuild_lists("proactive")
    sim.step()
    sim.flush()
    jax.block_until_ready(sim.state)
    jax.profiler.stop_trace()

    capture = trace_reduce.load_capture(trace_dir)
    span = [a for a in capture["annotations"]
            if a[0] == "sphexa:rebuild-lists"][-1]
    print(f"# event {sink.of_kind('rebuild_lists')[-1]}")
    if args.side:
        print("# CPU rehearsal: control flow only, no time is printed")
        return 0
    # the rebuild's span by (first phase, last scope token) and device op:
    # the benchmark's own reader, clipped to the span (one step = the span)
    table = stage_times.table_of_capture(
        capture, steps=1, with_ops=True, window=(span[1], span[1] + span[2]))
    print(f"# rebuild span {span[2] * 1e-6:.1f} ms")
    stage_times.print_table(table, floor_ms=0.5, ops=12)
    res = {"workload": args.workload, "span_ms": span[2] * 1e-6,
           "event": sink.of_kind("rebuild_lists")[-1],
           "ops_ms": {dev: sorted(
               ([ns * 1e-6, first, last, name]
                for (first, last, name), ns in d["ops"].items()),
               reverse=True)[:60] for dev, d in table["devices"].items()}}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
