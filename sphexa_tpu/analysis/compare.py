"""L1-error comparison of a particle state against an analytic solution.

Counterpart of the reference's compare_solutions.py / compare_noh.py L1
metric (sum |sol - sim| / N, computed at every particle's radius) and of
the saveFields recompute pass (ve_hydro.hpp:225-286) that derives
rho/p/u/vel from the conserved fields before output.
"""

import functools
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.neighbors.cell_list import find_neighbors
from sphexa_tpu.propagator import PropagatorConfig
from sphexa_tpu.sfc.box import Box
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.sph import hydro_std, hydro_ve
from sphexa_tpu.sph.particles import ParticleState
from sphexa_tpu.telemetry.registry import span
from sphexa_tpu.util.phases import phase_scope


@functools.partial(jax.jit, static_argnames=("cfg", "pipeline"))
def _output_fields(
    state: ParticleState, box: Box, cfg: PropagatorConfig, pipeline: str
):
    # Neighbor search needs key order; results are scattered back to the
    # caller's particle order so they stay aligned with the conserved
    # fields of `state` (which a snapshot writes as-is).
    with phase_scope("output-fields"):
        keys = compute_sfc_keys(state.x, state.y, state.z, box,
                                curve=cfg.curve)
        order = jnp.argsort(keys)
        skeys = keys[order]
        g = lambda a: a[order]
        x, y, z, h, m = (g(state.x), g(state.y), g(state.z), g(state.h),
                         g(state.m))
        temp = g(state.temp)

    occ = jnp.int32(0)
    if cfg.backend == "pallas" and cfg.shard_axis is not None:
        # mesh run: a Mosaic call has no GSPMD partitioning rule ("wrap
        # the call in a shard_map"), so rho comes from the step's own
        # sharded force stage — per-shard kernels + the sized halo
        # exchange — at the price of its unused force outputs (a dump
        # costs about one step)
        from sphexa_tpu.propagator import (
            _std_forces_sharded,
            _ve_forces_sharded,
        )

        with phase_scope("output-fields"):
            sstate = jax.tree.map(
                lambda a: a[order] if getattr(a, "ndim", 0) >= 1 else a,
                state)
        if pipeline == "ve":
            rho, c, _, occ, *_ = _ve_forces_sharded(sstate, box, cfg, skeys)
            p = rho * cfg.const.cv * temp * (cfg.const.gamma - 1.0)
        else:
            rho, _, _, occ, *_ = _std_forces_sharded(sstate, box, cfg, skeys)
            p, c = hydro_std.compute_eos_std(temp, rho, cfg.const)
    elif cfg.backend == "pallas":
        # the fused engine avoids the XLA path's (N, W3*cap) candidate
        # materialization, which can exceed HBM for strongly compressed
        # states (e.g. Noh's center drives the cell cap into the 1000s)
        from sphexa_tpu.sph import pallas_pairs as pp

        interp = pp.pallas_interpret()
        ranges = pp.group_cell_ranges(x, y, z, h, skeys, box, cfg.nbr)
        if pipeline == "ve":
            xm, _, _ = pp.pallas_xmass(
                x, y, z, h, m, skeys, box, cfg.const, cfg.nbr,
                ranges=ranges, interpret=interp,
            )
            (kx, gradh), _ = pp.pallas_ve_def_gradh(
                x, y, z, h, m, xm, skeys, box, cfg.const, cfg.nbr,
                ranges=ranges, interpret=interp,
            )
            _, c, rho, p = hydro_ve.compute_eos_ve(
                temp, m, kx, xm, gradh, cfg.const
            )
        else:
            rho, _, _ = pp.pallas_density(
                x, y, z, h, m, skeys, box, cfg.const, cfg.nbr,
                ranges=ranges, interpret=interp,
            )
            p, c = hydro_std.compute_eos_std(temp, rho, cfg.const)
    elif pipeline == "ve":
        nidx, nmask, _, _ = find_neighbors(x, y, z, h, skeys, box, cfg.nbr)
        # VE-consistent density/EOS (the saveFields recompute pass,
        # ve_hydro.hpp:225-286): rho = kx m / xm with gradh normalization
        xm = hydro_ve.compute_xmass(
            x, y, z, h, m, nidx, nmask, box, cfg.const, cfg.block
        )
        kx, gradh = hydro_ve.compute_ve_def_gradh(
            x, y, z, h, m, xm, nidx, nmask, box, cfg.const, cfg.block
        )
        _, c, rho, p = hydro_ve.compute_eos_ve(temp, m, kx, xm, gradh, cfg.const)
    else:
        nidx, nmask, _, _ = find_neighbors(x, y, z, h, skeys, box, cfg.nbr)
        rho = hydro_std.compute_density(
            x, y, z, h, m, nidx, nmask, box, cfg.const, cfg.block
        )
        p, c = hydro_std.compute_eos_std(temp, rho, cfg.const)

    with phase_scope("output-fields"):
        unsort = lambda a: jnp.zeros_like(a).at[order].set(a)
        rho, p, c = unsort(rho), unsort(p), unsort(c)
        u = cfg.const.cv * state.temp
        vel = jnp.sqrt(state.vx**2 + state.vy**2 + state.vz**2)
        r = jnp.sqrt(state.x**2 + state.y**2 + state.z**2)
    return {"r": r, "rho": rho, "p": p, "u": u, "vel": vel, "c": c}, occ


def compute_output_fields(
    state: ParticleState, box: Box, cfg: PropagatorConfig, pipeline: str = "std"
) -> Dict[str, np.ndarray]:
    """Recompute the dependent output fields (rho, p, u, |v|, c) plus radii
    from a conserved-field state, as numpy arrays in the state's particle
    order. ``pipeline`` selects the density/EOS estimator consistent with
    the propagator that evolved the state ('std' or 've')."""
    with span("sphexa:dump-program"):
        # launched until ``occ`` is on the host: the program's outputs
        # become ready together, so this is its device time
        out, occ = _output_fields(state, box, cfg,
                                  "ve" if pipeline == "ve" else "std")
        occ = int(occ)
    if occ > cfg.nbr.cap:
        # only the sharded recompute reports it: the cell cap or the halo
        # window (cap + 1 sentinel) no longer covers the re-sorted state
        raise RuntimeError(
            f"output-field recompute overflowed its neighbor config "
            f"(occupancy {occ} > cap {cfg.nbr.cap}); step once more "
            "so the driver re-sizes, then dump")
    with span("sphexa:dump-fetch", fields=len(out)) as sp:
        fields = {k: np.asarray(v) for k, v in out.items()}
        sp["bytes"] = sum(v.nbytes for v in fields.values())
    return fields


def l1_error(sim: np.ndarray, sol: np.ndarray) -> float:
    """Reference L1 metric: mean absolute deviation (compare_noh.py:146)."""
    sim = np.asarray(sim, np.float64)
    sol = np.asarray(sol, np.float64)
    return float(np.abs(sol - sim).sum() / sim.shape[0])
