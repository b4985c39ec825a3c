"""The list engine's std forces against the plain reference, after a cell's traffic.

    python3 benchmarks/check_forces_std.py --workload noh-std-1m.steady --seed <n> [--seconds 30] [--targets 256]

A builder's script, not a metric: it runs the cell exactly as run.py does
(the same ``run_cell``: initialiser, ``Simulation`` as ``main()`` builds it,
warm-up, the traffic's check windows for ``--seconds``, ``correct``) and
then, outside any clock, evaluates the std force stage once more on the
LIVE state with the LIVE pair lists (``propagator.std_forces``, the call
the next step would make) and compares ``rho`` and ``(ax, ay, az, du)`` at
``--targets`` seeded targets with reference_sph_std.py's all-pairs sums over
every particle. It fails without a TPU, like run.py; ``--side <n>`` instead
rehearses it on the CPU at a tiny size with the list engine interpreted
(rehearse_lists_cpu.py's steering) and then prints no device number.

``system_forces`` and ``compare`` are what the tier-1 tests call too
(tests/test_noh_lists_reference.py), so the chip and the CPU tier make the
same comparison.
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]


@functools.lru_cache(maxsize=None)
def _force_stage():
    """The jitted force stage, built once per process (the program is
    imported late: this module loads before the platform is chosen)."""
    import jax

    from sphexa_tpu.propagator import std_forces

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def stage(state, box, cfg, lists):
        (state, _, ax, ay, az, du, _, _, _, _, rho, _, diag,
         _) = std_forces(state, box, cfg, None, lists=lists)
        ok = diag["list_ok"] if lists is not None else 1
        return state, {"rho": rho, "ax": ax, "ay": ay, "az": az,
                       "du": du}, ok

    return stage


def system_forces(sim):
    """The program's std force stage on ``sim``'s live state under its live
    configuration and pair lists. Returns ``(state, fields, list_ok)``:
    the state in the order the fields are in (the frozen list order, or
    freshly sorted where the run has no lists), ``{"rho", "ax", "ay", "az",
    "du"}`` for every particle, and whether the lists still covered the
    state (always True without lists)."""
    state, fields, ok = _force_stage()(sim.state, sim.box, sim.active_cfg,
                                       sim.pair_lists)
    return state, fields, bool(int(ok))


def compare(sim, const, seed, count, block=64, product_dtype=None):
    """System against reference at ``count`` seeded targets of ``sim``'s
    live state: ``reference_sph_std.errors`` plus ``list_ok``, the ring
    sizes and ``finite`` (every reference value finite: the rings held)."""
    import numpy as np

    import reference
    import reference_sph_std

    state, fields, list_ok = system_forces(sim)
    targets = reference.seeded_targets(seed, state.n, count)
    ref = reference_sph_std.std_forces(
        targets, state.x, state.y, state.z, state.vx, state.vy, state.vz,
        state.h, state.m, state.temp, gamma=const.gamma, cv=const.cv,
        sinc_index=const.sinc_index, block=block,
        product_dtype=product_dtype)
    got = {k: np.asarray(v)[targets] for k, v in fields.items()}
    out = reference_sph_std.errors(got, ref)
    out.update(
        list_ok=list_ok, targets=len(targets), particles=int(state.n),
        ring_a=ref["ring_a"], ring_b=ref["ring_b"],
        finite=all(bool(np.all(np.isfinite(ref[k]))) for k in got))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--targets", type=int, default=256)
    ap.add_argument("--side", type=int, default=None,
                    help="CPU rehearsal at this tiny side (no device number)")
    args = ap.parse_args(argv)

    if args.side:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run  # sibling

    bench, cell, config, traffic = run.load_cell(args.workload)
    if config["prop"] != "std":
        raise SystemExit("the reference is the std formulation's; "
                         f"{cell['name']} runs --prop {config['prop']}")
    if args.side:
        import rehearse_lists_cpu
        from sphexa_tpu.init import make_initializer

        rehearse_lists_cpu.steer_auto_to_pallas()
        n = make_initializer(config["init"])(args.side)[0].n
        config = {**config, "side": args.side, "particles": n}
        platform = "cpu"
    else:
        from sphexa_tpu.util.device import enable_compile_cache, require_tpu

        platform = require_tpu("benchmarks/check_forces_std.py").platform
        enable_compile_cache()

    # run_cell keeps its Simulation to itself: take it as it is built
    built = []
    build = run.build_simulation

    def build_and_keep(*a, **kw):
        built.append(build(*a, **kw))
        return built[-1]

    run.build_simulation = build_and_keep
    out_dir = os.path.join(HERE, "out", "forces-" + cell["name"])
    rec = run.run_cell(cell, config, traffic, args.seed, args.seconds,
                       False, out_dir, run.Spans())
    sim, const = built[-1]
    w = rec["window"]
    for ok, what in rec["checks"]:
        print(f"# [{'PASS' if ok else 'FAIL'}] {what}")
    counts = {k: sum(1 for e in rec["events"] if e["kind"] == k)
              for k in ("rebuild_lists", "rollback", "replay", "reconfigure")}
    print(f"# {cell['name']}: platform={platform} particles="
          f"{rec['particles']} cycles={w['cycles']} steps="
          f"{w['steps_completed']} attempted={w['attempted']} {counts}")
    result = compare(sim, const, args.seed, args.targets)
    result.update(cell=cell["name"], platform=platform, seed=args.seed,
                  correct=all(ok for ok, _ in rec["checks"]),
                  iteration=sim.iteration, **counts)
    print(json.dumps(result))
    return 0 if result["correct"] and result["finite"] else 1


if __name__ == "__main__":
    sys.exit(main())
