"""Device mesh + sharded step construction.

The particle axis is sharded over a 1-D mesh axis ``"p"``. Because every
step globally re-sorts by Hilbert key, shard k of the sorted arrays IS the
k-th contiguous key slab — the same ownership model as the reference's
SfcAssignment (domaindecomp.hpp:74-110), with the sort itself playing the
role of exchangeParticles. Interaction gathers that cross slab boundaries
become XLA-inserted collectives (the halo exchange analog); scalar
reductions (dt, box, energies) become psum/pmin over ICI.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sphexa_tpu.propagator import PropagatorConfig, step_hydro_std


def make_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D particle mesh over the first ``num_devices`` devices."""
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devices)} available"
            )
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), ("p",))


def shard_state(state, mesh: Mesh):
    """Place particle arrays sharded over the mesh; scalars replicated."""
    psharding = NamedSharding(mesh, P("p"))
    rsharding = NamedSharding(mesh, P())

    def place(leaf):
        if leaf.ndim >= 1:
            if leaf.shape[0] % mesh.size:
                raise ValueError(
                    f"particle count {leaf.shape[0]} not divisible by mesh size "
                    f"{mesh.size}; pad the state first"
                )
            return jax.device_put(leaf, psharding)
        return jax.device_put(leaf, rsharding)

    return jax.tree.map(place, state)


def _place_aux_leaf(leaf, n: int, place, pspec, rspec):
    """SINGLE placement rule for aux (turbulence/chemistry) pytree leaves:
    per-particle arrays (first dim == n) ride the slab sharding, other
    arrays replicate, scalars pass through. Shared by the input commit
    (device_put) and the output constraint so they can never drift apart
    into two executable variants."""
    nd = getattr(leaf, "ndim", 0)
    if nd >= 1 and leaf.shape[0] == n:
        return place(leaf, pspec)
    if nd >= 1:
        return place(leaf, rspec)
    return leaf


def make_sharded_step(mesh: Mesh, cfg: PropagatorConfig, step_fn=step_hydro_std,
                      halo_window: int = 0, halo_cells=(), grav_cells=(),
                      aux_cfg=None, halo_runs: int = 0):
    """Jit the full step with particle arrays sharded over the mesh.

    GSPMD partitions the entire program: the SFC sort's key exchange is the
    domain redistribution, neighbor gathers crossing shard boundaries
    lower to halo collectives, and jnp.min/sum reductions become pmin/psum
    (the reference's MPI_Allreduce at timestep.hpp:106 and
    conserved_quantities.hpp:118).

    When ``cfg.gravity`` is set, the returned stepper takes the gravity
    tree as a third argument: ``stepper(state, box, gtree)``; the (small)
    tree arrays stay replicated across the mesh, matching the reference's
    replicated global octree (assignment.hpp:51-53). ``grav_cells``
    (P-1 per-distance row caps from sizing.device_gravity_halo) switches
    the gravity near field to the MAC-sized sparse serve; empty ships
    full peer slabs. ``halo_runs`` (sizing.device_sparse_halo's second
    value) cuts the SPH halo's run axis to the sized high-water of live
    runs; 0 keeps the window's full width.

    turb-ve / std-cooling carry extra per-step state through the stepper
    (the reference runs every propagator under the full MPI domain,
    turb_ve.hpp:53 / std_hydro_grackle.hpp:56): pass their static config
    as ``aux_cfg`` and call ``stepper(state, box, gtree, aux)`` with the
    TurbulenceState / ChemistryData pytree; the stepper returns
    ``(state, box, diag, new_aux)``. Turbulence phases are replicated
    (they are global mode tables); chemistry arrays are per-particle and
    ride the slab sharding + the in-step SFC sort.

    Persistent pair lists (``cfg.list_slot_cap`` > 0, sized by the
    Simulation for every step family with a sharded pair stage, with or
    without self-gravity): ``stepper.rebuild(state, box, aux)`` is the
    jitted ``propagator.rebuild_pair_lists_sharded`` (global sort + every
    slab's list build and frozen send layout), and ``stepper(...,
    lists=)`` / ``step_sim(..., lists=)`` the steady list step, in which
    the particle arrays are the slabs of the lists' frozen order and,
    under ``cfg.gravity``, the tree solve takes a key-sorted copy of its
    five inputs through one global sort and sends the accelerations back
    through another (``propagator._add_gravity``); without ``lists`` the
    same stepper sorts the state and streams.
    """
    from sphexa_tpu.propagator import (
        STEP_AUX_SLOT,
        step_hydro_std_blockdt,
        step_hydro_std_cooling,
        step_hydro_ve,
        step_hydro_ve_blockdt,
        step_turb_ve,
    )

    aux_props = {step_turb_ve, step_hydro_std_cooling}
    # blockdt steps carry the BlockDtState through the aux slot (4-tuple
    # return like aux_props) but take no static aux_cfg; their bin math
    # runs OUTSIDE shard_map on GSPMD-sharded arrays, so the pallas force
    # stages and their pinned collective order are reused unchanged
    blockdt_props = {step_hydro_std_blockdt, step_hydro_ve_blockdt}
    carry_props = aux_props | blockdt_props
    # GSPMD has no auto-partitioning rule for Mosaic (pallas) custom calls,
    # so the pallas pair stage runs under an explicit shard_map: each
    # device executes the fused engine on its SFC slab with windowed
    # all_to_all halos (propagator._std_forces_sharded /
    # _ve_forces_sharded). turb-ve and std-cooling reuse those same force
    # stages; their extra physics (stirring accel, cooling source) is
    # plain XLA on sharded arrays, which GSPMD partitions. The nbody step
    # has no pair stage — it falls back to the GSPMD XLA gravity path.
    if cfg.backend == "pallas":
        if step_fn in ({step_hydro_std, step_hydro_ve} | carry_props):
            cfg = dataclasses.replace(cfg, mesh=mesh, shard_axis="p",
                                      halo_window=halo_window,
                                      halo_cells=tuple(halo_cells),
                                      halo_runs=int(halo_runs),
                                      grav_cells=tuple(grav_cells))
        else:
            cfg = dataclasses.replace(cfg, backend="xla")
    if (cfg.gravity is not None and cfg.gravity.use_pallas
            and cfg.shard_axis is None):
        # on the GSPMD path (nbody/turb/cooling/xla steps) gravity runs
        # outside any shard_map, where a Mosaic custom call has no
        # partitioning rule — fall back to the XLA near field there. The
        # fast-path steps instead run _gravity_sharded_stage (distributed
        # upsweep + windowed near-field halos, Ewald replica shells
        # included) with the engine inside shard_map.
        cfg = dataclasses.replace(
            cfg, gravity=dataclasses.replace(cfg.gravity, use_pallas=False)
        )

    pspec = NamedSharding(mesh, P("p"))

    rspec = NamedSharding(mesh, P())

    def inner(s, b, gtree=None, aux=None, lists=None):
        kw = {} if lists is None else {"lists": lists}
        if step_fn in aux_props:
            new_state, new_box, diag, new_aux = step_fn(
                s, b, cfg, gtree, aux, aux_cfg, **kw
            )
        elif step_fn in blockdt_props:
            new_state, new_box, diag, new_aux = step_fn(s, b, cfg, gtree, aux)
        else:
            new_state, new_box, diag = step_fn(s, b, cfg, gtree, **kw)
            new_aux = None
        new_state, new_box, new_aux = place(s.n, new_state, new_box, new_aux)
        return new_state, new_box, diag, new_aux

    def place(n, new_state, new_box, new_aux):
        """The placement every program of this stepper leaves its carry
        in, so that the next one starts from what it compiled for."""
        # keep the particle arrays sharded on the way out so the next step
        # starts from slab-owned arrays (no silent replication creep)...
        constrain = lambda l: (
            jax.lax.with_sharding_constraint(l, pspec) if l.ndim >= 1 else l
        )
        # ...and the (3,)-vector box replicated — a stray P('p') sharding
        # on it changes the call signature and forces a full recompile on
        # the second step
        rep = lambda l: (
            jax.lax.with_sharding_constraint(l, rspec)
            if getattr(l, "ndim", 0) >= 1 else l
        )
        # aux leaves: per-particle arrays (chemistry) stay slab-sharded,
        # global tables (turbulence modes/phases) stay replicated
        aux_place = lambda l: _place_aux_leaf(
            l, n, jax.lax.with_sharding_constraint, pspec, rspec
        )
        return (jax.tree.map(constrain, new_state),
                jax.tree.map(rep, new_box),
                jax.tree.map(aux_place, new_aux))

    # inputs are placed by shard_state; GSPMD propagates those shardings
    # through the whole program, one compiled executable reused every step
    jitted = jax.jit(inner)

    def commit(s, b, aux):
        # commit the box (and aux, same placement rule as aux_place)
        # replicated/sharded BEFORE the first call: an uncommitted input
        # on step 0 compiles a second executable variant vs the committed
        # step-1 outputs, and on CPU meshes two variants' collective
        # channels can collide mid-run
        b = jax.device_put(b, rspec)
        if aux is not None:
            aux = jax.tree.map(
                lambda l: _place_aux_leaf(
                    l, s.n, jax.device_put, pspec, rspec
                ),
                aux,
            )
        return b, aux

    def stepper(s, b, gtree=None, aux=None, lists=None):
        b, aux = commit(s, b, aux)
        out = jitted(s, b, gtree, aux, lists)
        return out if step_fn in carry_props else out[:3]

    aux_slot = STEP_AUX_SLOT.get(step_fn)

    def step_sim(sim, gtree=None, lists=None):
        """Advance one step on the unified ``state.SimState`` carry:
        the sharded face of ``propagator.step_sim_state``. Routes through
        ``stepper`` (same placement commits, same jitted executable —
        lowering-neutral by construction) and replaces only the aux slot
        this step function owns, so the carry treedef is closed under
        stepping (the JXA503 invariant)."""
        aux = getattr(sim, aux_slot) if aux_slot else None
        out = stepper(sim.particles, sim.box, gtree, aux, lists)
        new_sim = sim.with_slot(aux_slot, out[3] if aux_slot else None,
                                particles=out[0], box=out[1])
        return new_sim, out[2]

    stepper.step_sim = step_sim

    if cfg.list_slot_cap > 0 and cfg.shard_axis is not None:
        from sphexa_tpu.propagator import rebuild_pair_lists_sharded

        def rebuild_inner(s, b, aux=None):
            new_state, new_box, lists, new_aux = rebuild_pair_lists_sharded(
                s, b, cfg, aux)
            new_state, new_box, new_aux = place(s.n, new_state, new_box,
                                                new_aux)
            return new_state, new_box, lists, new_aux

        rebuild_jit = jax.jit(rebuild_inner)

        def rebuild(s, b, aux=None):
            """``propagator.rebuild_pair_lists`` of this mesh:
            (state in the frozen order, box, PairLists, aux)."""
            b, aux = commit(s, b, aux)
            return rebuild_jit(s, b, aux)

        stepper.rebuild = rebuild
        # (the jitted rebuild, as ``_jitted`` is the jitted step)
        stepper._rebuild_jitted = rebuild_jit

    # expose the underlying jit cache so the Simulation's compile
    # watchdog (telemetry retrace events) can probe sharded launches too;
    # optional like the consumer's getattr probe — a jax without the
    # private _cache_size just loses the watchdog, not the mesh path
    cache_size = getattr(jitted, "_cache_size", None)
    if cache_size is not None:
        stepper._cache_size = cache_size
    # ...and the jitted callable itself, so tests can .lower() the step
    # and pin lowering identities (the grav_window=0 byte-identity gate)
    stepper._jitted = jitted
    # the sharded config the step runs under (mesh, shard axis, halo
    # caps): the dump's output-field recompute reuses its force stages
    stepper.cfg = cfg
    return stepper
