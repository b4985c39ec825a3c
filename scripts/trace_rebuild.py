"""Trace ONE forced pair-list rebuild of a list cell on the chip and break
it down by device op and by scope.

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
    w0, w1 = span[1], span[1] + span[2]
    res = {"workload": args.workload, "span_ms": span[2] * 1e-6,
           "event": sink.of_kind("rebuild_lists")[-1]}
    for events in capture["devices"].values():
        events = [e for e in events if w0 <= e[1] < w1]
        self_ns, _ = trace_reduce._self_times(events)
        busy = trace_reduce._union([[e[1], e[1] + e[2]] for e in events])
        ops, scopes = {}, {}
        for i, e in enumerate(events):
            t = max(self_ns[i], 0.0) * 1e-6
            ops[e[0]] = ops.get(e[0], 0.0) + t
            scope = "/".join(e[3].split("/")[:6])
            scopes[scope] = scopes.get(scope, 0.0) + t
        top = lambda table, n: sorted(table.items(), key=lambda kv: -kv[1])[:n]
        res.update(busy_ms=sum(e - s for s, e in busy) * 1e-6,
                   ops_ms=top(ops, 40), scopes_ms=top(scopes, 30))
        break  # one chip: list cells run on one device
    print(f"# event {res['event']}")
    if args.side:
        print("# CPU rehearsal: control flow only, no time is printed")
        return 0
    print(f"# rebuild span {res['span_ms']:.1f} ms, device busy "
          f"{res['busy_ms']:.1f} ms")
    for name, ms in res.get("ops_ms", [])[:25]:
        print(f"  {ms:9.2f} ms  {name}")
    for name, ms in res.get("scopes_ms", [])[:12]:
        print(f"  {ms:9.2f} ms  {name}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
