"""The list-walk kernels of the three cheap pair ops, COMPILED for a described
v5e at the list cells' real widths (nothing runs: no chip is attached).

Interpret mode cannot see what Mosaic refuses (a slice off the tiling, too
much VMEM or SMEM a kernel), and no lock entry holds a list kernel (on the
CPU ``auto`` is ``xla``). Since PR 43 ``density`` / ``xmass``, ``iad`` and
``gradh`` run ``group_pair_engine_lists`` with ONE staged sublane tile
(4-5 j-fields + the index row = 8 rows), a shape no other list op has, at
per-group SMEM blocks as wide as the widest cell's ``slot_cap``. Since PR 44
the one-chip gravity cells walk lists too: all seven ops at Evrard -n 128's
widths (an open box, 17,162 groups, the widest ``slot_cap`` of any cell).
About two seconds a compile. The topology is described inside a fixture, in this one
file (only one process may hold the TPU library: the on-chip-measurement
guide, section 2)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sphexa_tpu.dtypes import KEY_DTYPE
from sphexa_tpu.init import init_evrard, init_sedov
from sphexa_tpu.neighbors.cell_list import NeighborConfig
from sphexa_tpu.sph import pallas_pairs as pp
from sphexa_tpu.sph.pair_lists import build_pair_lists

#: Sedov 160^3, the 4.1M cells' configuration on the chip
#: (``make_propagator_config(..., backend="pallas", use_lists=True)``)
N = 4_096_000
NBR = NeighborConfig(level=5, cap=1536, ngmax=150, block=2048,
                     curve="hilbert", group=64, window=5, run_cap=1536,
                     gap=384)
#: (slot_cap, slots_cap): Sedov 160^3's and wind-shock -n 100's (the
#: widest per-group SMEM blocks of any cell)
CAPS = {"sedov-160": (96, 3_727_360), "wind-shock-100": (344, 6_242_304)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of the run's cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def small():
    """A box and constants of the right kind (periodic, a real grid); the
    shapes come from ``N`` and ``NBR``."""
    _, box, const = init_sedov(8)
    return box, const


def _abstract(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("caps", list(CAPS))
@pytest.mark.parametrize("op", ["density", "iad", "gradh"])
def test_cheap_ops_walk_kernel_compiles_for_v5e(one_chip, small, op, caps):
    box, const = small
    slot_cap, slots_cap = CAPS[caps]
    f = jax.ShapeDtypeStruct((N,), jnp.float32)
    lists = jax.eval_shape(
        lambda x, y, z, h, k: build_pair_lists(
            x, y, z, h, k, box, NBR, 0.01, slot_cap, slots_cap),
        f, f, f, f, jax.ShapeDtypeStruct((N,), KEY_DTYPE))
    assert lists.cnt.shape == (N // NBR.group, slot_cap)
    fn = {
        "density": lambda ls, x, y, z, h, m, a: pp.pallas_density(
            x, y, z, h, m, None, box, const, NBR, lists=ls),
        "iad": lambda ls, x, y, z, h, m, a: pp.pallas_iad(
            x, y, z, h, m, None, box, const, NBR, lists=ls),
        "gradh": lambda ls, x, y, z, h, m, a: pp.pallas_ve_def_gradh(
            x, y, z, h, m, a, None, box, const, NBR, lists=ls),
    }[op]
    assert pp.PAIR_OP_ENGINE[op][0] == "walk"
    args = _abstract((lists,) + (f,) * 6, one_chip)
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()  # raises what the chip's compiler would
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)


#: Evrard -n 128, the two one-chip gravity cells' configuration on the chip
#: (``scripts/count_list_runs.py --init evrard --side 128``: level 4,
#: window 7 with the open box's margin cell, sized for the hull's h 1.29 x up)
N_EVRARD = 1_098_340
NBR_EVRARD = NeighborConfig(level=4, cap=1536, ngmax=150, block=2048,
                            curve="hilbert", group=64, window=7,
                            run_cap=1536, gap=384)
#: (slot_cap, slots_cap): the widest per-group SMEM blocks of any cell
CAPS_EVRARD = {"evrard-128": (352, 2_449_408)}
OPS_EVRARD = ["density", "iad", "momentum-energy-std", "gradh",
              "divv-curlv", "av-switches", "momentum-energy-ve"]


@pytest.mark.parametrize("caps", list(CAPS_EVRARD))
@pytest.mark.parametrize("op", OPS_EVRARD)
def test_walk_kernels_compile_for_v5e_at_evrards_widths(one_chip, op, caps):
    """The seven list ops a one-chip step under self-gravity walks (std
    and VE), at widths no earlier PR put on the chip."""
    _, box, const = init_evrard(8)
    slot_cap, slots_cap = CAPS_EVRARD[caps]
    nbr, n = NBR_EVRARD, N_EVRARD
    f = jax.ShapeDtypeStruct((n,), jnp.float32)
    lists = jax.eval_shape(
        lambda x, y, z, h, k: build_pair_lists(
            x, y, z, h, k, box, nbr, 0.01, slot_cap, slots_cap),
        f, f, f, f, jax.ShapeDtypeStruct((n,), KEY_DTYPE))
    assert lists.cnt.shape == (-(-n // nbr.group), slot_cap)
    tail = (None, box, const, nbr)
    fn = {
        "density": lambda ls, a: pp.pallas_density(
            a, a, a, a, a, *tail, lists=ls),
        "iad": lambda ls, a: pp.pallas_iad(a, a, a, a, a, *tail, lists=ls),
        "momentum-energy-std": lambda ls, a: pp.pallas_momentum_energy_std(
            *(a,) * 17, *tail, lists=ls),
        "gradh": lambda ls, a: pp.pallas_ve_def_gradh(
            *(a,) * 6, *tail, lists=ls),
        "divv-curlv": lambda ls, a: pp.pallas_iad_divv_curlv(
            *(a,) * 9, *tail, lists=ls),
        "av-switches": lambda ls, a: pp.pallas_av_switches(
            *(a,) * 18, None, box, 1e-3, const, nbr, lists=ls),
        "momentum-energy-ve": lambda ls, a: pp.pallas_momentum_energy_ve(
            *(a,) * 19, *tail, nc=a.astype(jnp.int32), lists=ls),
    }[op]
    assert pp.PAIR_OP_ENGINE[op][0] == "walk"
    lowered = jax.jit(fn).lower(*_abstract((lists, f), one_chip))
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()  # raises what the chip's compiler would
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
