"""Rehearsal 3 for a mesh cell: compile its step for a described v5e:2x2.

    python benchmarks/rehearse_compile_x4.py [--config sedov-std-8m-x4]

No chip is needed and nothing runs on one: the TPU compiler installed here
compiles for a topology that is described, not attached. The script builds
the configuration's ``Simulation`` at the real size on four virtual CPU
devices (so the program sizes its own halo caps from the real particle
distribution), then hands ``make_sharded_step`` a mesh of the described TPU
devices and the state's shapes, and compiles. What the chip's compiler would
refuse (a Mosaic kernel that cannot be partitioned or tiled, a program that
does not fit 16 GB) is refused here, at no chip time. It says nothing about
results or times, and is never reported as a chip run.

The program decides Mosaic-or-interpret and pallas-or-xla by asking
``sphexa_tpu.util.device`` which platform it is on; this script answers
"tpu" for it. That steering lives here, not in the program.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="sedov-std-8m-x4")
    ap.add_argument("--check-every", type=int, default=4)
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    chips = config["devices"]

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip()
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import sphexa_tpu.util.device as device
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables import make_observable_spec
    from sphexa_tpu.parallel import make_sharded_step
    from sphexa_tpu.simulation import Simulation

    # no persistent cache: a compile for a described chip is written to it
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    kind = topo.devices[0].device_kind
    device.device_info = lambda: device.DeviceInfo("tpu", kind, chips)

    t0 = time.perf_counter()
    state, box, const = make_initializer(config["init"])(config["side"])
    sim = Simulation(state, box, const, prop=config["prop"],
                     theta=config["theta"], num_devices=chips,
                     check_every=args.check_every,
                     obs_spec=make_observable_spec(config["init"]),
                     science_rows=True, workload=config["init"])
    info = sim._halo_info
    print(f"constructed on {chips} virtual CPU devices in "
          f"{time.perf_counter() - t0:.1f} s: n={sim.state.n} "
          f"backend={sim._cfg.backend} halo={info}", flush=True)

    mesh = Mesh(np.asarray(topo.devices[:chips]), ("p",))
    stepper = make_sharded_step(
        mesh, sim._cfg, sim._step_fn(),
        halo_window=0 if info["mode"] == "sparse" else info.get("wmax", 0),
        halo_cells=info.get("caps", ()))
    n = sim.state.n

    def shape_of(leaf):
        spec = P("p") if getattr(leaf, "ndim", 0) >= 1 \
            and leaf.shape[0] == n else P()
        return jax.ShapeDtypeStruct(np.shape(leaf), leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    sim_state = sim.sim_state
    shapes = jax.tree.map(shape_of, (sim_state.particles, sim_state.box))
    t0 = time.perf_counter()
    compiled = stepper._jitted.lower(*shapes, sim._gtree, None).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"compiled for {kind} x{chips} (described, not attached) in "
          f"{time.perf_counter() - t0:.1f} s")
    print(f"memory_analysis per device: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, code "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB")
    for op in ("tpu_custom_call", "collective-permute", "all-gather",
               "all-reduce", "all-to-all"):
        print(f"  {op}: {text.count(op)} mentions in the compiled HLO")
    return 0


if __name__ == "__main__":
    sys.exit(main())
