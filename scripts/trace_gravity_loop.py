"""Trace one check window of a gravity cell on the chip and break the tree
solve down by the INNERMOST scope and, inside the block loop's body, by
device op.

    python3 scripts/trace_gravity_loop.py --workload evrard-ve-1m.steady \
        [--cycles 1] [--out chiprun_out/gravity_loop.json]

The benchmark's reader takes the FIRST ``sphexa/<phase>`` of an op's path
(benchmarks/trace_reduce.py), so the block loop reads whole as
``gravity-mac``; its body's stages keep their own scopes further down the
path (.../sphexa/gravity-mac/while/body/.../sphexa/gravity-m2p/...). This
is the by-hand reading PERF.md's loop-body tables come from. Builds the
cell as benchmarks/run.py does, warms it up, runs ``--cycles`` traffic
cycles, then captures one more. Every time it prints is a device time from
the capture, per step of the slowest device; run it through the chip tool
(`--side N` rehearses the control flow on the CPU and prints no time).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def loop_tables(events, steps):
    """(by innermost scope, by op inside the loop body, by op outside it)
    of one device's gravity ops, ms per step. An op is gravity's when its
    own path or its nearest enclosing op's names a ``gravity-`` scope."""
    import trace_reduce

    SCOPE_RE = trace_reduce.PHASE_RE  # every sphexa/<phase> of a path
    self_ns, parent = trace_reduce._self_times(events)

    def path_of(i):
        while i is not None:
            if SCOPE_RE.search(events[i][3]):
                return events[i][3]
            i = parent[i]
        return ""

    scopes, body, rest = {}, {}, {}
    for i, e in enumerate(events):
        path = path_of(i)
        names = SCOPE_RE.findall(path)
        if not names or not names[0].startswith("gravity-"):
            continue
        ms = max(self_ns[i], 0.0) * 1e-6 / steps
        in_body = "/while/body" in path or "/while/body" in e[3]
        key = ("loop:" if in_body else "") + names[-1]
        scopes[key] = scopes.get(key, 0.0) + ms
        table = body if in_body else rest
        tail = "/".join(path.split("/")[-4:])
        row = table.setdefault(e[0], [0.0, names[-1], tail])
        row[0] += ms
    top = lambda t: sorted(([k] + v for k, v in t.items()),
                           key=lambda r: -r[1])
    return (sorted(scopes.items(), key=lambda kv: -kv[1]), top(body),
            top(rest))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="evrard-ve-1m.steady")
    ap.add_argument("--cycles", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--side", type=int, default=None,
                    help="CPU rehearsal at this lattice side")
    args = ap.parse_args(argv)

    import run  # benchmarks/run.py; puts the checkout's root on sys.path
    import trace_reduce
    if args.side:
        os.environ["JAX_PLATFORMS"] = "cpu"
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
    steps = traffic["steps_per_cycle"]

    def cycle():
        for _ in range(steps):
            sim.step()
        sim.flush()
        jax.block_until_ready(sim.state)

    for _ in range(args.cycles):
        cycle()
    trace_dir = os.path.join(ROOT, "benchmarks", "out", "trace_gravity_loop")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    cycle()
    jax.profiler.stop_trace()
    g = sim._cfg.gravity
    caps = {"super_cap": g.super_cap, "m2p_cap": g.m2p_cap,
            "p2p_cap": g.p2p_cap, "let_cap": g.let_cap,
            "leaf_cap": g.leaf_cap}
    print(f"# caps {caps}")
    print(f"# window {sink.of_kind('window')[-1]}")  # carries the fills
    if args.side:
        print("# CPU rehearsal: control flow only, no time is printed")
        return 0

    capture = trace_reduce.load_capture(trace_dir)
    res = {"workload": args.workload, "steps": steps, "caps": caps,
           "window": sink.of_kind("window")[-1], "devices": {}}
    worst = None
    for dev, events in capture["devices"].items():
        scopes, body, rest = loop_tables(events, steps)
        total = sum(ms for _, ms in scopes)
        res["devices"][dev] = {"gravity_ms_step": total, "scopes": scopes,
                               "body_ops": body[:60], "other_ops": rest[:30]}
        if worst is None or total > res["devices"][worst]["gravity_ms_step"]:
            worst = dev
    d = res["devices"][worst]
    print(f"# device {worst}: gravity {d['gravity_ms_step']:.1f} ms per step "
          f"(window per_step_s {res['window'].get('per_step_s')})")
    print("# by innermost scope (loop: = inside the block loops' bodies)")
    for name, ms in d["scopes"]:
        print(f"  {ms:9.2f} ms  {name}")
    print("# ops inside the loop bodies")
    for name, ms, scope, tail in d["body_ops"][:45]:
        print(f"  {ms:9.2f} ms  {name:34s} {scope:16s} {tail}")
    print("# gravity ops outside them")
    for name, ms, scope, tail in d["other_ops"][:15]:
        print(f"  {ms:9.2f} ms  {name:34s} {scope:16s} {tail}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
