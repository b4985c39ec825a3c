"""CPU rehearsal of one cell at a tiny size: control flow, not speed.

    python benchmarks/rehearse_cpu.py --workload <cell> [--side 16] [--seconds 2] [--trace 0|1]

Runs the same ``run_cell`` as run.py on the CPU backend (virtual devices for
a mesh cell), with the configuration's ``side`` replaced by a tiny one. The
override exists only here. Prints what ran and the checks, says
``platform=cpu``, and prints NO metric: a number from a CPU run is never a
device metric.
"""

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--side", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    import run  # sibling; puts the checkout's root on sys.path

    bench, cell, config, traffic = run.load_cell(args.workload)
    if config["devices"] > 1:
        flag = f"--xla_force_host_platform_device_count={config['devices']}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + flag).strip()
    import jax

    from sphexa_tpu.init import make_initializer

    n = make_initializer(config["init"])(args.side)[0].n
    config = {**config, "side": args.side,
              "particles": n - n % config["devices"]}
    platform = jax.devices()[0].platform
    out_dir = os.path.join(run.HERE, "out", "rehearsal-" + cell["name"])
    rec = run.run_cell(cell, config, traffic, args.seed, args.seconds,
                       bool(args.trace), out_dir, run.Spans())
    w = rec["window"]
    print(f"rehearsal {cell['name']}: platform={platform} side={args.side} "
          f"particles={rec['particles']} cycles={w['cycles']} "
          f"steps={w['steps_completed']} attempted={w['attempted']} "
          f"failed={w['failed']} raised={w['raised']}")
    print(f"  engine: {rec['engine']}")
    print(f"  event kinds in window: "
          f"{sorted({e['kind'] for e in rec['events']})}")
    print(f"  harness spans: {sorted({s['name'] for s in rec['spans']})}")
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layers")):
        metrics = run.metrics_of(bench, group, cell["name"])
        found = run.read_metrics(metrics, folder, rec)
        print(f"  {group} readers that found something to read: "
              f"{sorted(found)} of {[m['name'] for m in metrics]} (values "
              f"not printed: platform={platform})")
    for ok, what in rec["checks"]:
        print(f"  [{'PASS' if ok else 'FAIL'}] {what}")
    return 0 if all(ok for ok, _ in rec["checks"]) else 1


if __name__ == "__main__":
    sys.exit(main())
