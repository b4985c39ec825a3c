"""CPU rehearsal of a list cell WITH its lists: control flow, not speed.

    python benchmarks/rehearse_lists_cpu.py --workload noh-std-1m.steady [--side 16] [--seconds 2]

On the CPU the program resolves ``backend="auto"`` to the XLA gather path,
which has no persistent pair lists, so rehearse_cpu.py alone never runs a
list rebuild, a ``list-expiry`` rollback or a replay. This script steers
that one choice from outside the program (the on-chip-measurement guide's
rule: in the rehearsal, not through an option): ``auto`` resolves to
``pallas``, whose kernels run in interpret mode off-TPU, and everything
else is rehearse_cpu.py's. It prints NO metric: a number from a CPU run is
never a device metric.
"""

import os
import sys


def steer_auto_to_pallas():
    """Make ``Simulation``'s ``backend="auto"`` pick the Mosaic engine
    (interpreted off-TPU) instead of the XLA path, for this process."""
    import sphexa_tpu.simulation as simulation

    resolve = simulation.resolve_backend
    simulation.resolve_backend = (
        lambda backend="auto": "pallas" if backend == "auto"
        else resolve(backend))


def main(argv=None) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import rehearse_cpu  # sibling
    import run  # noqa: F401  (puts the checkout's root on sys.path)

    steer_auto_to_pallas()
    return rehearse_cpu.main(argv)


if __name__ == "__main__":
    sys.exit(main())
