"""One run of one benchmark cell, in a fresh process that owns the chips.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``; the configuration, the traffic mix and every metric are
files found by that name (README.md). The run builds the case through the
program's own initialiser and ``Simulation`` constructor with the keyword
arguments ``sphexa_tpu/app/main.py`` passes for the same command line, warms
up this cell's programs, measures whole traffic cycles for ``--seconds`` on
the harness's own clock, checks the answers outside the clock, and prints
the contract's one JSON object as the last line of stdout. It fails, with no
result line, when jax finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the program is used uninstalled, from the checkout's root
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]

import windows  # noqa: E402  (sibling module)

#: a warm-up that has not settled after this many single flushed steps is a
#: fault of the program (every step recompiling), not something to wait out
MAX_WARM_STEPS = 12
#: cycles of the measured window wrapped in the profiler with --trace 1: the
#: second alone. One cycle, because stopping the profiler costs about 4 s per
#: traced Evrard step (PR 22: 32 s for two cycles, a 47 MB capture); not the
#: first, so that start-of-window effects stay out
TRACED_CYCLES = (1,)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name):
    """(benchmark, cell, configuration, traffic) for a cell name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_file))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def metrics_of(bench, group, cell_name):
    """The cell's metrics of one group (``end_to_end`` / ``per_layer``)."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(group, name):
    """The ``read(run)`` function of ``<group>/<metric>.py``, where group is
    ``end_to_end`` or ``layers``."""
    path = os.path.join(HERE, group, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{group}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(metrics, group, run):
    """{name: {"value", "unit"}} for every metric whose reader finds
    something to read."""
    out = {}
    for m in metrics:
        value = load_reader(group, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Spans:
    """The harness's own spans, on ``time.perf_counter``; ``annotate`` also
    writes the span into the profiler's trace as ``bench:<name>``."""

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def __call__(self, name, annotate=False):
        import jax

        ctx = (jax.profiler.TraceAnnotation("bench:" + name) if annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ctx:
            yield
        self.spans.append({"name": name, "t0": t0,
                           "t1": time.perf_counter()})


def build_simulation(config, traffic, sink):
    """Initialise the case and construct the Simulation as ``main()`` does
    for ``--init <init> -n <side> --prop <prop> --theta <theta>
    [--devices N] --check-every <k>``: every engine choice is the
    program's own (no ``tuned``, no ``backend``, no knob)."""
    import jax

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.simulation import Simulation
    from sphexa_tpu.telemetry import Telemetry

    state, box, const = make_initializer(config["init"])(config["side"])
    devices = config["devices"] if config["devices"] > 1 else None
    if devices and state.n % devices:
        # main() trims the trailing SFC rows to a mesh-divisible count
        n_full, keep = state.n, (state.n // devices) * devices
        state = jax.tree.map(
            lambda a: a[:keep] if getattr(a, "ndim", 0) >= 1
            and a.shape[0] == n_full else a, state)
    sim = Simulation(
        state, box, const, prop=config["prop"], theta=config["theta"],
        num_devices=devices, check_every=traffic["check_every"],
        obs_spec=make_observable_spec(config["init"]), science_rows=True,
        telemetry=Telemetry(sinks=[sink]), workload=config["init"])
    return sim, const


def make_dumper(sim, const, config, dump_dir, spans):
    """One restartable HDF5 dump with the full derived-field recompute, as
    ``main()``'s ``dump_now``; each dump replaces the one before."""
    from sphexa_tpu.analysis import compute_output_fields
    from sphexa_tpu.io import write_snapshot
    from sphexa_tpu.io.snapshot import write_snapshot_sharded

    path = os.path.join(dump_dir, f"dump_{config['init']}.h5")
    pipeline = "ve" if config["prop"] in ("ve", "turb-ve") else "std"
    writer = (write_snapshot_sharded
              if getattr(sim, "_mesh", None) is not None else write_snapshot)

    def dump():
        for f in os.listdir(dump_dir):
            os.remove(os.path.join(dump_dir, f))
        with spans("dump", annotate=True):
            with spans("dump-recompute", annotate=True):
                extra = compute_output_fields(sim.state, sim.box,
                                              sim.active_cfg,
                                              pipeline=pipeline)
            with spans("dump-write", annotate=True):
                writer(path, sim.state, sim.box, const,
                       iteration=sim.iteration, extra_fields=extra,
                       case=config["init"])

    dump.path = path
    return dump


def warm_up(sim, sink, dump):
    """Single flushed steps until one has passed with no retrace,
    reconfigure or rollback: the step program is per step, so this leaves
    no first-use compile for a whole window. Then one dump where the
    traffic dumps."""
    for _ in range(MAX_WARM_STEPS):
        mark = len(sink.events)
        sim.step()
        sim.flush()
        if not any(e["kind"] in windows.DIRTY for e in sink.events[mark:]):
            break
    else:
        raise RuntimeError(
            f"warm-up did not settle in {MAX_WARM_STEPS} steps")
    if dump is not None:
        dump()


def measure(sim, traffic, dump, spans, seconds, trace_dir):
    """Whole traffic cycles until ``seconds`` have elapsed. Returns the
    window's facts; the clock is ``perf_counter`` over everything between
    the first launch and the state being ready after the last flush."""
    import jax

    steps = traffic["steps_per_cycle"]
    dump_every = traffic["dump_every_cycles"]
    facts = {"cycles": 0, "raised": None, "traced_steps": 0,
             "cycle_facts": []}
    tracing = None  # (annotation, iteration at start) while the profiler runs

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        with spans("trace-start"):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation("bench:traced")
        ann.__enter__()
        return ann, sim.iteration

    def stop_trace(state):
        ann, it0 = state
        jax.block_until_ready(sim.state)
        ann.__exit__(None, None, None)
        facts["traced_steps"] = sim.iteration - it0
        with spans("trace-stop"):
            jax.profiler.stop_trace()

    def recovered():
        counters = sim.telemetry.counters
        return sum(counters.get("events." + k, 0) for k in windows.RECOVERY)

    it0 = sim.iteration
    t0 = time.perf_counter()
    try:
        while True:
            if trace_dir and facts["cycles"] == TRACED_CYCLES[0]:
                tracing = start_trace()
            before, c0 = recovered(), time.perf_counter()
            with spans("cycle", annotate=True):
                for _ in range(steps):
                    sim.step()
                sim.flush()
                dumps = bool(dump_every
                             and (facts["cycles"] + 1) % dump_every == 0)
                if dumps:
                    dump()
            facts["cycles"] += 1
            facts["cycle_facts"].append(
                {"wall_s": time.perf_counter() - c0,
                 "recoveries": recovered() - before, "dumped": dumps})
            if tracing and facts["cycles"] > TRACED_CYCLES[-1]:
                stop_trace(tracing)
                tracing = None
            if windows.should_close(time.perf_counter() - t0,
                                    facts["cycles"], seconds,
                                    traffic["min_cycles"]):
                break
    except RuntimeError as e:
        # a step lost for good (caps that did not converge, a failed
        # recompute): the window ends here and the run is not correct
        facts["raised"] = f"{type(e).__name__}: {e}"
    finally:
        if tracing:
            stop_trace(tracing)
    sim.flush()
    jax.block_until_ready(sim.state)
    t1 = time.perf_counter()
    facts.update(t0=t0, t1=t1, wall_s=t1 - t0,
                 steps_completed=sim.iteration - it0)
    return facts


def memory_peak_bytes(chips):
    """Peak bytes in use on the fullest chip, as the allocator reports."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(cell, config, traffic, seed, seconds, trace, out_dir, spans):
    """Set up, warm up, measure and check one cell; returns the run record
    the metric readers read. Does not care which platform it runs on: the
    caller does (``main`` demands a TPU; rehearse_cpu.py prints no
    metric). ``spans`` holds what the caller timed before (reaching the
    chip)."""
    import jax

    import correct
    from sphexa_tpu.telemetry.sinks import MemorySink

    os.makedirs(out_dir, exist_ok=True)
    sink = MemorySink()
    dump_dir = tempfile.mkdtemp(prefix="sphexa-bench-dump-")
    try:
        with spans("init-construct"):
            sim, const = build_simulation(config, traffic, sink)
        dump = (make_dumper(sim, const, config, dump_dir, spans)
                if traffic["dump_every_cycles"] else None)
        with spans("warm"):
            warm_up(sim, sink, dump)
            jax.block_until_ready(sim.state)
        setup_s = time.perf_counter() - T_START
        rows = sim.drain_science()
        setup_spans, spans.spans = spans.spans, []
        mark = len(sink.events)
        counters0 = dict(sim.telemetry.counters)

        trace_dir = os.path.join(out_dir, "trace") if trace else None
        window = measure(sim, traffic, dump, spans, seconds, trace_dir)

        peak = memory_peak_bytes(cell["chips"])
        rows += sim.drain_science()
        events = sink.events[mark:]
        window["attempted"] = windows.steps_attempted(events)
        run = {
            "cell": cell["name"], "chips": cell["chips"],
            "particles": int(sim.state.n), "seed": seed,
            "setup_s": setup_s, "setup_spans": setup_spans,
            "window": window, "events": events, "spans": spans.spans,
            "counters": {k: v - counters0.get(k, 0)
                         for k, v in sim.telemetry.counters.items()},
            "engine": next(e["engine"] for e in reversed(sink.events)
                           if e["kind"] == "reconfigure"),
            "memory_peak_bytes": peak, "trace": None,
        }
        if trace_dir:
            import trace_reduce

            run["trace"] = trace_reduce.reduce_capture(
                trace_reduce.load_capture(trace_dir),
                steps=window["traced_steps"])
        run["checks"] = correct.check_run(
            run, sim, const, config, rows,
            dump.path if dump and os.listdir(dump_dir) else None, seed)
        window["failed"] = correct.steps_failed(run)
    finally:
        shutil.rmtree(dump_dir, ignore_errors=True)
    return run


def cache_entries(cache_dir):
    return (len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir) else 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)

    spans = Spans()
    with spans("chip-reach"):
        # importing jax and bringing the TPU runtime up: about 10-20 s of
        # every run, and none of it the program's
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        dev = require_tpu("benchmarks/run.py")
    if dev.count < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chips; jax "
                         f"found {dev.count}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if dev.kind not in peaks:
        raise SystemExit(f"device_kind {dev.kind!r} is not in peaks.json")
    cache_dir = enable_compile_cache()
    cache_before = cache_entries(cache_dir)
    print(f"# {cell['name']}: platform={dev.platform} kind={dev.kind!r} "
          f"count={dev.count} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace}")
    print(f"# compile cache {cache_dir}: {cache_before} entries before",
          flush=True)

    out_dir = os.path.join(HERE, "out", cell["name"])
    run = run_cell(cell, config, traffic, args.seed, args.seconds,
                   bool(args.trace), out_dir, spans)
    run["peaks"] = peaks[dev.kind]
    cache_after = cache_entries(cache_dir)

    w = run["window"]
    print(f"# compile cache: {cache_after} entries after "
          f"(+{cache_after - cache_before})")
    print(f"# engine: {json.dumps(run['engine'])}")
    print(f"# set-up {run['setup_s']:.2f} s: " + " ".join(
        f"{s['name']}={s['t1'] - s['t0']:.2f}" for s in run["setup_spans"]))
    print(f"# window {w['wall_s']:.3f} s: cycles={w['cycles']} "
          f"steps={w['steps_completed']} attempted={w['attempted']} "
          f"failed={w['failed']} raised={w['raised']}")
    print("# cycles (wall s, recoveries): " + " ".join(
        f"{c['wall_s']:.4f}/{c['recoveries']}" for c in w["cycle_facts"])
        + f"; counted as {windows.window_seconds(w['cycle_facts']):.4f} s")
    print("# per-step s of clean windows: "
          f"{windows.clean_step_seconds(run['events'])}")
    print(f"# counters in window: {run['counters']}")
    for ok, what in run["checks"]:
        print(f"# [{'PASS' if ok else 'FAIL'}] {what}")
    if run["trace"]:
        t = run["trace"]
        print(f"# trace: window {t['window_s']:.3f} s busy {t['busy_s']:.3f} "
              f"s steps {t['steps']} coverage_min {t['coverage_min']:.4f} "
              f"per_device {json.dumps(t['per_device'])}")
        print("# trace phases (s, slowest device): " + json.dumps(
            dict(sorted(t["phase_s_max"].items(), key=lambda kv: -kv[1]))))

    group, folder = (("per_layer", "layers") if args.trace
                     else ("end_to_end", "end_to_end"))
    metrics = read_metrics(metrics_of(bench, group, cell["name"]), folder,
                           run)
    device = {"platform": dev.platform, "kind": dev.kind, "count": dev.count,
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": all(ok for ok, _ in run["checks"]),
              "attempted": w["attempted"], "failed": w["failed"],
              "metrics": metrics, "device": device}
    if run["trace"]:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    with open(os.path.join(out_dir, "last_run.json"), "w") as f:
        json.dump({**run, "result": result, "cache": {
            "dir": cache_dir, "before": cache_before, "after": cache_after}},
            f, indent=1, default=str)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
