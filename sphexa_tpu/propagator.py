"""Propagators: the per-step orchestration of SPH ops.

TPU-native counterpart of the reference's ``main/src/propagator/``
(ipropagator.hpp, std_hydro.hpp, ve_hydro.hpp): a propagator owns the
sequence of kernel calls for one time step. Where the reference interleaves
MPI halo exchanges between kernels, the jitted step here operates on the
full (sharded) arrays and XLA materializes whatever communication the
shardings imply; the host never orchestrates communication.

The whole step — SFC sort, neighbor search, hydro pipeline, time step,
integration — is ONE jitted function of the ParticleState pytree, so XLA
sees the complete dataflow and can fuse/schedule across op boundaries.
"""

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from sphexa_tpu.gravity.ewald import EwaldConfig, compute_gravity_ewald
from sphexa_tpu.gravity.traversal import (
    GRAV_SHARD_DIAG_KEYS,
    GravityConfig,
    compute_gravity,
    compute_gravity_on_mesh,
    finish_sharded_stage,
    near_field_windows,
    sharded_diag_specs,
)
from sphexa_tpu.gravity.tree import GravityTree, GravityTreeMeta
from sphexa_tpu.neighbors.cell_list import NeighborConfig, find_neighbors
from sphexa_tpu.observables.ledger import (
    NUM_DIAG_KEYS,
    OBS_DIAG_KEYS,
    ObservableSpec,
    ledger_diagnostics,
)
from sphexa_tpu.observables.snapshot import (
    SNAP_DIAG_KEYS,
    SnapshotSpec,
    snapshot_diagnostics,
)
from sphexa_tpu.sfc.box import Box, make_global_box, put_in_box
from sphexa_tpu.sfc.keys import compute_sfc_keys
from sphexa_tpu.sph import blockdt as bdt
from sphexa_tpu.sph import hydro_std, hydro_ve
from sphexa_tpu.sph.kernels import update_h
from sphexa_tpu.sph.particles import ParticleState, SimConstants
from sphexa_tpu.sph.positions import compute_positions
from sphexa_tpu.sph.timestep import (
    acceleration_timestep,
    compute_timestep,
    rho_timestep,
)
from sphexa_tpu.util.phases import phase_scope, stage_scope

#: Canonical scalar diagnostics every propagator's step emits — the
#: naming contract between the step functions, the Simulation driver's
#: overflow checks, and the telemetry layer (sphexa_tpu/telemetry/).
#: ``_integrate_and_finish`` is the single producer; propagator-specific
#: extras (egrav, dt_cool, list_slack, ...) ride alongside but consumers
#: must ``.get()`` them — only THESE keys may be assumed present.
STEP_DIAG_KEYS = ("dt", "nc_mean", "nc_max", "occupancy", "rho_max",
                  "h_max")

#: Per-shard (P,) diagnostics the SHARDED force stages ride alongside the
#: scalars — the distributed-telemetry contract (schema-v2 ``exchange`` /
#: ``shard_load`` events). All are cheap in-graph reductions all_gathered
#: to O(P) replicated arrays; the Simulation fetches them at its existing
#: flush boundary, so they add ZERO host syncs to the deferred happy path
#: (pinned by tests/test_telemetry.py). Present only on mesh runs through
#: the pallas fast path; consumers must .get() them.
SHARD_DIAG_KEYS = ("shard_rows", "shard_occ", "shard_work", "shard_trips",
                   "shard_runs")

#: GRAV_SHARD_DIAG_KEYS (imported above, gravity/traversal.py) is the
#: gravity stage's analog of SHARD_DIAG_KEYS.

#: OBS_DIAG_KEYS / NUM_DIAG_KEYS (imported above) complete the diag-key
#: families: the in-graph science ledger's conservation and
#: numerics-health scalars (observables/ledger.py) ride the diagnostics
#: dict and are fetched at the existing check/flush boundary exactly
#: like SHARD_DIAG_KEYS — zero added host syncs under deferral.

#: timestep-limiter attribution: ``diagnostics["dt_limiter"]`` indexes
#: this tuple — WHICH candidate bound the step's dt (growth = the 1.1x
#: previous-dt cap, then courant/rho/cool/accel as compute_timestep
#: combines them, timestep.hpp:97-112). One global order across all
#: propagators; inactive candidates rank as +inf.
DT_LIMITERS = ("growth", "courant", "rho", "cool", "accel")

#: block-timestep diagnostics the *_blockdt step builders ride alongside
#: STEP_DIAG_KEYS (consumers must .get() them): active-row count, the
#: (dt_bins,) bin occupancy histogram, the substep just executed, the
#: drift-aware resort decision + its inversion count, and the
#: active-rows neighbor-work proxy gathered through the compaction list.
BLOCKDT_DIAG_KEYS = ("bdt_active", "bdt_pop", "bdt_substep", "bdt_resort",
                     "bdt_drift", "bdt_work")


def _dt_limiter(min_dt_prev, const: SimConstants, courant=None, rho=None,
                cool=None, accel=None):
    """Index into DT_LIMITERS of the binding dt candidate — the in-graph
    attribution of ``compute_timestep``'s min-reduction (ties resolve to
    the earlier name, matching jnp.argmin)."""
    inf = jnp.asarray(jnp.inf, jnp.float32)
    cands = [const.max_dt_increase * min_dt_prev, courant, rho, cool, accel]
    stack = jnp.stack([inf if c is None else jnp.asarray(c, jnp.float32)
                       for c in cands])
    return jnp.argmin(stack).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class PropagatorConfig:
    """Static per-run configuration: physics constants + neighbor search.

    When self-gravity is on (const.g != 0), ``gravity`` holds the static
    solver caps and ``grav_meta`` the (hashable) tree-structure metadata;
    the matching GravityTree arrays are passed to the step function as a
    pytree argument (the structure is host-rebuilt at reconfiguration
    granularity, like the neighbor cell grid).
    """

    const: SimConstants
    nbr: NeighborConfig
    curve: str = "hilbert"
    block: int = 2048
    av_clean: bool = False
    gravity: Optional[GravityConfig] = None
    grav_meta: Optional[GravityTreeMeta] = None
    # periodic-box gravity: when set, the Barnes-Hut solve goes through the
    # Ewald path (replica near field + real/k-space corrections)
    ewald: Optional[EwaldConfig] = None
    # include the per-particle accelerations in the step diagnostics (the
    # gravitational-wave observable consumes them, gravitational_waves.hpp)
    keep_accels: bool = False
    # 'pallas': fused search+op TPU kernels for the std pipeline
    # (sph/pallas_pairs.py); 'xla': portable gather-based path
    backend: str = "xla"
    # multi-chip fast path: when set (with backend='pallas'), the pair-op
    # stage runs under shard_map over ``mesh`` — each device executes the
    # Mosaic engine on its SFC slab, with the windowed all_to_all halo
    # exchange supplying the j-side candidates (parallel/exchange.py)
    mesh: Optional[object] = None
    shard_axis: Optional[str] = None
    # per-peer halo window rows (Wmax). 0 = full peer slabs (the safe
    # all_gather-equivalent); sized tighter by estimate_halo_window
    halo_window: int = 0
    # sparse cell-granular halo exchange: P-1 per-DISTANCE row caps
    # (parallel/exchange.shard_halo_stage_sparse). Non-empty takes
    # precedence over halo_window for the SPH stages; comm volume is
    # sum(halo_cells) rows per serve and tracks the halo surface instead
    # of degenerating to whole slabs (docs/NEXT.md round-4 measurement)
    halo_cells: Tuple[int, ...] = ()
    # slots of the SPH halo's run axis: the sized high-water of live runs
    # a group (parallel/sizing.device_sparse_halo, beside halo_cells; a
    # sized value like them, never an option). The exchange's per-slot
    # index work and the pair kernels' range blocks are this wide, not
    # the window's W3; a group with more runs trips the halo sentinel.
    # 0 (a caller that sizes none) = W3, the full width
    halo_runs: int = 0
    # MAC-sized sparse gravity near-field exchange: P-1 per-DISTANCE row
    # caps (parallel/sizing.device_gravity_halo) for the leaf-granular
    # serve inside compute_gravity's shard path. () = full peer slabs
    # (the grav_window=0 fallback and the escape-retry ceiling)
    grav_cells: Tuple[int, ...] = ()
    # persistent-neighbor-list mode (sph/pair_lists.py): > 0 enables it
    # with this per-group chunk-slot budget; steady steps then skip the
    # global sort AND the candidate prologue, momentum ops lane-compact,
    # cheap ops chunk-skip. Sized at configure time like every cap.
    list_slot_cap: int = 0
    # rows of the lists' flat lane table (one per kept chunk, all groups
    # together), sized with list_slot_cap from the same host pass
    list_slots_cap: int = 0
    # case observable computed in-graph alongside the conservation
    # ledger (observables/ledger.py); None = energies only
    obs: Optional[ObservableSpec] = None
    # in-graph downsampled field-grid snapshot (observables/snapshot.py);
    # None is never read by the step builders, so unset leaves every
    # lowering byte-identical (the dt_bins pattern)
    snap: Optional[SnapshotSpec] = None
    # Verlet skin as a fraction of the 2*h_max search radius: larger =
    # fewer rebuilds but more candidate lanes per target
    list_skin_rel: float = 0.2
    # hierarchical block time steps (sph/blockdt.py): number of
    # power-of-two Δt bins the *_blockdt step builders use. None = the
    # global-dt path, bitwise unchanged (the field is never read outside
    # the blockdt builders); 1 = blockdt machinery with every particle
    # due every substep, pinned bitwise-equal to the global path
    dt_bins: Optional[int] = None
    # re-bin cadence in CYCLES at the sync substep (1 = every cycle);
    # larger amortizes the bin assignment at the cost of staler bins
    bin_sync_every: int = 1
    # drift-aware resort threshold: the blockdt sort keeps the incoming
    # order when the folded-key inversion count is <= this fraction of n
    # (0.0 = keep only when already perfectly sorted — exact)
    bin_resort_drift: float = 0.0


def _sort_by_keys(state: ParticleState, box: Box, curve: str, aux=None,
                  bins=None, resort_drift: float = 0.0, shards: int = 0):
    """Global SFC sort: the analog of domain.sync()'s keygen + radix sort
    (cstone/domain/assignment.hpp:84-122). Every field array is gathered
    into key order; scalars pass through untouched. ``aux``: an optional
    extra pytree of per-particle arrays (e.g. ChemistryData) permuted
    identically so it stays aligned with the persisted sorted state.

    ``bins``: block-timestep path — the bin index is folded below the
    spatial bits (blockdt.fold_bin_key) so one argsort groups equal-key
    particles by bin, and the permute goes DRIFT-AWARE: a cheap in-graph
    inversion count over the folded keys decides resort-now vs keep
    (``resort_drift`` = tolerated inversion fraction; ROADMAP item 2b —
    fixed resort cadence measured net-negative, the check is the new
    idea). Returns ``(state, keys, aux, resorted, inversions)``; the
    plain path keeps its 3-tuple and its lowering byte-identical.

    ``shards`` > 1 (plain path, an ``aux`` carried over that many equal
    slabs of a mesh): a fourth value, the rows whose sorted position lies
    on another slab than the one they came from (``_migrant_rows``). GSPMD
    makes the aux gather an all-gather of every slab's rows to every
    device; this says how many of them the sort really moved (telemetry
    ``exchange`` stage ``sort``).
    """
    # sphexa/sort: the whole keygen + argsort + permute program is one
    # attribution phase (profiler traces; util/phases.py taxonomy)
    with phase_scope("sort"):
        with stage_scope("sort", "keys"):
            keys = compute_sfc_keys(state.x, state.y, state.z, box,
                                    curve=curve)
        if bins is None:
            with stage_scope("sort", "order"):
                order = jnp.argsort(keys)
                sorted_keys = keys[order]
    n = state.n

    def permute_tree(tree, order):
        """Permute every (n,) leaf. Same-dtype leaves are stacked into one
        (n, F) matrix and gathered by ROW: XLA's TPU gather moves F
        contiguous elements per index, ~18x faster than F separate 1-D
        gathers (the reference's analogous trick is the byte-packed
        multi-array exchange, domaindecomp_mpi.hpp:62)."""
        if tree is None:
            return None
        leaves, treedef = jax.tree.flatten(tree)
        per_dtype: Dict = {}
        for i, a in enumerate(leaves):
            if getattr(a, "ndim", -1) == 1 and a.shape[0] == n:
                per_dtype.setdefault(a.dtype, []).append(i)
        for dtype, idxs in per_dtype.items():
            if len(idxs) == 1:
                leaves[idxs[0]] = leaves[idxs[0]][order]
                continue
            mat = jnp.stack([leaves[i] for i in idxs], axis=1)[order]
            for k, i in enumerate(idxs):
                leaves[i] = mat[:, k]
        return jax.tree.unflatten(treedef, leaves)

    if bins is None:
        with phase_scope("sort"):
            with stage_scope("sort", "permute"):
                state = permute_tree(state, order)
            # the aux pytree's gather (the chemistry of a std-cooling
            # step) apart from the state's
            with stage_scope("sort", "aux"):
                aux = permute_tree(aux, order)
                if shards > 1:
                    return (state, sorted_keys, aux,
                            _migrant_rows(order, shards))
            return state, sorted_keys, aux

    with phase_scope("dt-bins"):
        skey = bdt.fold_bin_key(keys, bins)
        inv = jnp.sum((skey[1:] < skey[:-1]).astype(jnp.int32))
        # static threshold: resort_drift and n are trace-time constants
        resort = inv > jnp.int32(int(resort_drift * n))

    def do_resort(state, keys, aux):
        with phase_scope("sort"):
            with stage_scope("sort", "order"):
                order = jnp.argsort(skey)
            with stage_scope("sort", "permute"):
                state = permute_tree(state, order)
            # after the state's gather, where it was: the ops keep their
            # order and the lowering its digest
            with stage_scope("sort", "order"):
                keys = keys[order]
            with stage_scope("sort", "aux"):
                aux = permute_tree(aux, order)
            return state, keys, aux

    def keep(state, keys, aux):
        return state, keys, aux

    # only the taken branch executes at runtime — the keep branch skips
    # the whole argsort + row-gather program, which is the entire point
    with phase_scope("sort"):
        state, keys, aux = jax.lax.cond(resort, do_resort, keep,
                                        state, keys, aux)
    return state, keys, aux, resort.astype(jnp.int32), inv


@functools.partial(jax.jit, static_argnames=("cfg",))
def rebuild_pair_lists(state: ParticleState, box: Box,
                       cfg: PropagatorConfig, aux=None):
    """Persistent-list rebuild: box regrow + global SFC sort + list build
    (sph/pair_lists.py). The returned state is the FROZEN sorted order
    every steady step runs in until the next rebuild; ``aux`` (e.g.
    ChemistryData) is permuted identically. The skin re-derives from the
    current h_max, so it tracks the evolving resolution."""
    from sphexa_tpu.sph.pair_lists import build_pair_lists

    with phase_scope("sort"):
        box = make_global_box(state.x, state.y, state.z, box)
    state, keys, aux = _sort_by_keys(state, box, cfg.curve, aux=aux)
    with phase_scope("neighbors"):
        skin = jnp.float32(cfg.list_skin_rel) * 2.0 * jnp.max(state.h)
        lists = build_pair_lists(
            state.x, state.y, state.z, state.h, keys, box, cfg.nbr,
            skin, cfg.list_slot_cap, cfg.list_slots_cap,
            interpret=_pallas_interpret(),
        )
    return state, box, lists, aux


def _slab_lists_specs(axis: str, P: int):
    """``shard_map`` specs of a mesh's PairLists: every per-group, per-row
    and per-slab leaf is the slabs' concatenation, the counters and the
    skin are one value for all (the rebuild reduces them over the axis)."""
    from jax.sharding import PartitionSpec
    from sphexa_tpu.parallel.exchange import FrozenHalo
    from sphexa_tpu.sph.pair_lists import PairLists
    from sphexa_tpu.sph.pallas_pairs import GroupRanges

    Pp, Pr = PartitionSpec(axis), PartitionSpec()
    return PairLists(
        ranges=GroupRanges(starts=Pp, lens=Pp, shift_x=Pp, shift_y=Pp,
                           shift_z=Pp, ncells=Pp, occupancy=Pr, boxl=Pr),
        gidx=Pp, seg=Pp, cnt=Pp, fill=Pp, emit=Pp, tail=Pp,
        overflow=Pr, slot_need=Pr, slots_live=Pr, chunks_live=Pr,
        runs_live=Pr, lanes_total=Pr, xb=Pp, yb=Pp, zb=Pp, hb=Pp, skin=Pr,
        halo=FrozenHalo(send=(Pp,) * (P - 1), rows=Pp, occ=Pp, runs=Pp))


def _slab_nbr(cfg: PropagatorConfig, S_shard: int):
    """The neighbour config a slab's stages run under: a merged run must
    fit in one source slab so the boundary split pass leaves at most one
    remainder per run (exchange._split_runs); a raw CELL wider than a
    slab still crosses and trips the split-overflow sentinel instead
    (pathological at any realistic shard size)."""
    nbr = cfg.nbr
    if nbr.run_cap > S_shard:
        nbr = dataclasses.replace(nbr, run_cap=S_shard)
    return nbr


def rebuild_pair_lists_sharded(state: ParticleState, box: Box,
                               cfg: PropagatorConfig, aux=None):
    """``rebuild_pair_lists`` on a mesh (``cfg`` the sharded stepper's;
    jitted by parallel/mesh.make_sharded_step): box regrow + the global
    sort as the streamed mesh step runs them (this is where rows migrate
    between slabs, and nowhere else), then ONE ``shard_map`` in which
    every slab runs the sparse halo stage on its skin-inflated windows,
    serves ``(x, y, z)`` once, builds its lists over [own | halo] rows
    and freezes the send layout of that negotiation with them
    (``PairLists.halo``). ``overflow``, ``slot_need``, ``slots_live`` and
    the occupancy leave as the max over slabs, ``chunks_live``,
    ``runs_live`` and ``lanes_total`` as sums; ``overflow`` carries 2
    where a slab's halo escaped its caps (a halo re-size, not a list
    re-size)."""
    from jax.sharding import PartitionSpec
    from sphexa_tpu.parallel import exchange as ex
    from sphexa_tpu.sph.pair_lists import build_pair_lists

    with phase_scope("sort"):
        box = make_global_box(state.x, state.y, state.z, box)
    state, keys, aux = _sort_by_keys(state, box, cfg.curve, aux=aux)
    axis = cfg.shard_axis
    P = cfg.mesh.shape[axis]
    S_shard = state.x.shape[0] // P
    nbr = _slab_nbr(cfg, S_shard)
    hmax = tuple(min(c, S_shard) for c in cfg.halo_cells)
    interpret = _pallas_interpret()
    with phase_scope("neighbors"):
        skin = jnp.float32(cfg.list_skin_rel) * 2.0 * jnp.max(state.h)

    def build(box, skin, keys, x, y, z, h):
        ranges, serve, jbuf, escaped, _, halo = ex.shard_halo_stage_sparse(
            x, y, z, h, keys, box, nbr, P, hmax, axis,
            run_slots=cfg.halo_runs, radius_pad=skin, freeze=True)
        with phase_scope("neighbors"):
            lists = build_pair_lists(
                x, y, z, h, None, box, nbr, skin, cfg.list_slot_cap,
                cfg.list_slots_cap, interpret=interpret, ranges=ranges,
                jdata=jbuf((x, y, z), serve((x, y, z))))
            # one value for all slabs: three reductions in one order
            top = jax.lax.pmax(jnp.stack(
                [lists.overflow, escaped.astype(jnp.int32), lists.slot_need,
                 lists.slots_live, lists.ranges.occupancy]), axis)
            count = jax.lax.psum(ex.chain_after(jnp.stack(
                [lists.chunks_live, lists.runs_live]), top), axis)
            lanes = jax.lax.psum(ex.chain_after(lists.lanes_total, count),
                                 axis)
        return lists._replace(
            ranges=lists.ranges._replace(occupancy=top[4]),
            overflow=top[0] + 2 * top[1], slot_need=top[2],
            slots_live=top[3], chunks_live=count[0], runs_live=count[1],
            lanes_total=lanes, halo=halo)

    Pp, Pr = PartitionSpec(axis), PartitionSpec()
    lists = shard_map(
        build, mesh=cfg.mesh, in_specs=(Pr, Pr, Pp, Pp, Pp, Pp, Pp),
        out_specs=_slab_lists_specs(axis, P), check_vma=False,
    )(box, skin, keys, state.x, state.y, state.z, state.h)
    return state, box, lists, aux


def _gravity_sharded_stage(x, y, z, m, h, keys, box, cfg, gtree):
    """Distributed gravity under shard_map over the step's mesh, on the
    five GLOBAL key-sorted, slab-sharded source arrays and their sorted
    keys (the state a streamed step just sorted, or the copy a list step
    makes, ``_add_gravity``): the open Barnes-Hut solve (any multipole
    order) is traversal.compute_gravity_on_mesh; the periodic Ewald path
    (cartesian quadrupole, traversal_ewald_cpu.hpp parity) the same shape
    round compute_gravity_ewald. Near-field halo sizing: cfg.grav_cells
    (traversal.near_field_windows)."""
    from jax.sharding import PartitionSpec

    axis = cfg.shard_axis
    gcfg = dataclasses.replace(cfg.gravity, G=cfg.const.g, use_pallas=True)
    if cfg.ewald is None:
        return compute_gravity_on_mesh(
            x, y, z, m, h, keys, box, gtree,
            cfg.grav_meta, gcfg, cfg.mesh, axis, cfg.grav_cells)
    P = cfg.mesh.shape[axis]
    win = near_field_windows(cfg.grav_cells, x.shape[0] // P)

    def stage(box, keys, x, y, z, m, h):
        gx, gy, gz, egrav, diag = compute_gravity_ewald(
            x, y, z, m, h, keys, box, gtree, cfg.grav_meta, gcfg,
            cfg.ewald, shard=(axis, P, win),
        )
        return finish_sharded_stage(gx, gy, gz, egrav, diag, axis)

    dspec = sharded_diag_specs(win, (
        "m2p_max", "p2p_max", "leaf_occ", "c_max", "let_max",
        "compact_width"))
    Pp, Pr = PartitionSpec(axis), PartitionSpec()
    return shard_map(
        stage,
        mesh=cfg.mesh,
        in_specs=(Pr, Pp, Pp, Pp, Pp, Pp, Pp),
        out_specs=(Pp, Pp, Pp, Pr, dspec),
        check_vma=False,
    )(box, keys, x, y, z, m, h)


def _on_slabs(cfg, arrays):
    """``arrays`` as they are on one device; on a mesh held to the slabs'
    sharding: a global sort's outputs lie where the partitioner left
    them, and what takes them next (the tree solve's ``shard_map``, the
    integrator on the state's rows) takes slabs."""
    if cfg.shard_axis is None:
        return arrays
    from jax.sharding import NamedSharding, PartitionSpec

    slabs = NamedSharding(cfg.mesh, PartitionSpec(cfg.shard_axis))
    return tuple(jax.lax.with_sharding_constraint(a, slabs) for a in arrays)


def _migrant_rows(order, shards: int):
    """Rows of a global sort over ``shards`` equal slabs whose sorted
    position lies on another slab than the row they came from
    (``order``: the sort's permutation)."""
    n = order.shape[0]
    slab = n // shards
    home = jnp.arange(n, dtype=order.dtype) // slab
    return jnp.sum((order // slab != home).astype(jnp.int32))


def _key_sorted_sources(state, box, cfg):
    """The tree solve's five inputs in key order, of a state that is not:
    ``(gbox, sorted_keys, order, x, y, z, m, h)``. A list step keeps the
    order its lists froze (and the hydro grid's box), so the solve sorts
    its own copy: the box regrown over the live positions as the streamed
    prologue regrows it, the keys, and ONE sort that carries the five
    fields and an iota as payloads. On a v5e at 1.1M rows that sort reads
    5 ms where an argsort, the sorted keys' gather and a row gather of
    the stacked fields read 2 + 8 + 5.6: a gather pays per index, a
    sort's payloads ride along (PERF.md, PR 44). On a mesh the operands
    are the slabs of the frozen order and the sort is global (GSPMD
    partitions it); its seven outputs leave as the slabs of the key
    order, which is what the mesh's solve takes."""
    with phase_scope("sort"):
        with stage_scope("sort", "keys"):
            gbox = make_global_box(state.x, state.y, state.z, box)
            keys = compute_sfc_keys(state.x, state.y, state.z, gbox,
                                    curve=cfg.curve)
        with stage_scope("sort", "order"):
            iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
            sorted_keys, x, y, z, m, h, order = _on_slabs(cfg, jax.lax.sort(
                (keys, state.x, state.y, state.z, state.m, state.h, iota),
                num_keys=1))
    return gbox, sorted_keys, order, x, y, z, m, h


def _to_frozen_order(order, gx, gy, gz, cfg):
    """The solve's accelerations back in the order the state is in: one
    sort keyed on ``order`` (a permutation) with the three as payloads
    (3.8 ms on a v5e at 1.1M rows; the inverse permutation and a row
    gather of the (N, 3) stack read 12.5); on a mesh a global sort again,
    out as the slabs of the frozen order."""
    with phase_scope("sort"), stage_scope("sort", "permute"):
        _, gx, gy, gz = jax.lax.sort((order, gx, gy, gz), num_keys=1)
        gx, gy, gz = _on_slabs(cfg, (gx, gy, gz))
    return gx, gy, gz


def _add_gravity(state, box, keys, cfg, gtree, ax, ay, az):
    """Self-gravity coupling: Barnes-Hut accel added to the hydro accel.

    The analog of mHolder_.upsweep + traverse inside computeForces
    (main/src/propagator/gravity_wrapper.hpp:97-123). The solve runs on
    key-sorted arrays: the state the step just sorted (``keys`` its
    sorted keys), or, in a list step (``keys`` None: the state is in the
    lists' frozen order, on one device or on a mesh), a key-sorted copy
    of ``x, y, z, m, h`` it makes itself, with the accelerations brought
    back to the frozen order. The sort work reads under phase ``sort``,
    the solve under its own phases. Returns updated accels, egrav, the
    acceleration dt candidate, and solver diagnostics (all order-free);
    a list step on a mesh adds ``sort_migrant_rows``, the rows of the
    copy that lie on another slab than their frozen row: what a list's
    age has done to the slabs' key ranges.
    """
    x, y, z, m, h = state.x, state.y, state.z, state.m, state.h
    order = None
    if keys is None:
        box, keys, order, x, y, z, m, h = _key_sorted_sources(
            state, box, cfg)
    if cfg.shard_axis is not None:
        gx, gy, gz, egrav, gdiag = _gravity_sharded_stage(
            x, y, z, m, h, keys, box, cfg, gtree
        )
    elif cfg.ewald is not None:
        gcfg = dataclasses.replace(cfg.gravity, G=cfg.const.g)
        gx, gy, gz, egrav, gdiag = compute_gravity_ewald(
            x, y, z, m, h, keys, box,
            gtree, cfg.grav_meta, gcfg, cfg.ewald,
        )
    else:
        gcfg = dataclasses.replace(cfg.gravity, G=cfg.const.g)
        gx, gy, gz, egrav, gdiag = compute_gravity(
            x, y, z, m, h, keys, box,
            gtree, cfg.grav_meta, gcfg,
        )
    if order is not None:
        gx, gy, gz = _to_frozen_order(order, gx, gy, gz, cfg)
        if cfg.shard_axis is not None:
            with phase_scope("sort"), stage_scope("sort", "order"):
                gdiag = {**gdiag, "sort_migrant_rows": _migrant_rows(
                    order, cfg.mesh.shape[cfg.shard_axis])}
    ax, ay, az = ax + gx, ay + gy, az + gz
    with phase_scope("timestep"):
        dt_acc = acceleration_timestep(ax, ay, az, cfg.const)
    return ax, ay, az, egrav, dt_acc, gdiag


def _integrate_and_finish(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    ax, ay, az, du, dt, nc, occ, rho, extra=None, extra_diag=None,
    update_smoothing=True, c=None, dt_limiter=None,
):
    """Shared step tail: drift/kick + PBC wrap, smoothing-length nudge,
    state rebuild, diagnostics. Every propagator's force stage funnels
    through here (the analog of the common trailing sequence of
    std_hydro.hpp/ve_hydro.hpp step()); the diagnostics dict it builds
    carries exactly the STEP_DIAG_KEYS scalars, the in-graph science
    ledger (OBS_DIAG_KEYS + NUM_DIAG_KEYS, observables/ledger.py — the
    reference's per-iteration conserved_quantities sweep moved inside
    the step program) plus whatever extras the caller rides along."""
    const = cfg.const
    with phase_scope("integrate"):
        fields = (state.x, state.y, state.z, state.x_m1, state.y_m1,
                  state.z_m1, state.vx, state.vy, state.vz, state.h,
                  state.temp, state.temp_lo, du, state.du_m1)
        (nx, ny, nz, dxm, dym, dzm, vx, vy, vz, h, temp, temp_lo, du,
         du_m1) = compute_positions(
            fields, ax, ay, az, dt, state.min_dt, box, const
        )
        new_h = update_h(const.ng0, nc + 1, h) if update_smoothing else h
        new_state = dataclasses.replace(
            state,
            x=nx, y=ny, z=nz, x_m1=dxm, y_m1=dym, z_m1=dzm,
            vx=vx, vy=vy, vz=vz, h=new_h, temp=temp, temp_lo=temp_lo,
            du=du, du_m1=du_m1,
            ttot=state.ttot + dt, min_dt=dt, min_dt_m1=state.min_dt,
            **(extra or {}),
        )
        diagnostics = {
            "dt": dt,
            "nc_mean": jnp.mean(nc.astype(jnp.float32)) + 1.0,
            "nc_max": jnp.max(nc) + 1,
            "occupancy": occ,
            "rho_max": jnp.max(rho),
            # computed in-step so the host never launches a separate
            # reduction (device->host round trips are expensive over
            # remote links)
            "h_max": jnp.max(new_h),
        }
    # conservation + numerics-health ledger over the post-integration
    # state (the pairing the app's eager recompute used: new positions/
    # velocities/temp with the force stage's rho/c); egrav is the force
    # stage's value, like the reference adds it to etot in-sweep.
    # Conditional like SHARD_DIAG_KEYS: cfg.obs = None skips
    # it (bare library steps stay ledger-free and compile leaner); the
    # app/bench always configure a spec, so every science-facing run
    # carries the full ledger
    if cfg.obs is not None:
        ed = extra_diag or {}
        diagnostics.update(ledger_diagnostics(
            new_state, rho, nc, const, cfg.nbr.ngmax, spec=cfg.obs,
            egrav=ed.get("egrav", 0.0), box=box, c=c,
            smoothing=update_smoothing,
            # sharded force stages chain their collectives and finish on
            # the shard-metrics gather (SHARD_DIAG_KEYS) — anchor the
            # ledger's reductions after it so the two collective families
            # stay totally ordered (the XLA:CPU rendezvous guard)
            token=ed.get("shard_trips"),
        ))
    # in-graph snapshot deposit over the same post-integration state
    # (observables/snapshot.py). Conditional exactly like cfg.obs: None
    # leaves the lowering byte-identical. Chained after the ledger's
    # last min sweep (rho_min) when the ledger runs, else after the
    # shard-metrics gather, keeping one total collective order
    if cfg.snap is not None:
        ed = extra_diag or {}
        diagnostics.update(snapshot_diagnostics(
            new_state, rho, box, cfg.snap,
            token=diagnostics.get("rho_min", ed.get("shard_trips")),
        ))
    if dt_limiter is not None:
        diagnostics["dt_limiter"] = dt_limiter
    if cfg.keep_accels:
        diagnostics.update({"ax": ax, "ay": ay, "az": az})
    diagnostics.update(extra_diag or {})
    return new_state, box, diagnostics


def _halo_stage_fn(cfg: PropagatorConfig, nbr, P: int, S_shard: int):
    """Choose the SPH stages' halo-exchange flavor: sparse cell-granular
    (cfg.halo_cells, the default sized by the Simulation) or contiguous
    per-peer windows (cfg.halo_window; also the 0 = full-slab fallback)."""
    from sphexa_tpu.parallel import exchange as ex

    axis = cfg.shard_axis
    if cfg.halo_cells:
        hmax = tuple(min(c, S_shard) for c in cfg.halo_cells)
        return lambda *a: ex.shard_halo_stage_sparse(
            *a, nbr, P, hmax, axis, run_slots=cfg.halo_runs)
    Wmax = min(cfg.halo_window, S_shard) or S_shard
    return lambda *a: ex.shard_halo_stage(*a, nbr, P, Wmax, axis,
                                          run_slots=cfg.halo_runs)


def exchange_fields_per_step(prop: str, av_clean: bool = False) -> int:
    """Total f32 fields served per step by the sharded force stage — the
    static multiplier that turns shipped rows into bytes/step
    (telemetry ``exchange.bytes_per_step``). Counts the serve() rounds:
    std/std-cooling = 4 (x,y,z,m) + 1 (m/rho) + 13 (h,v*,rho,p,c,cs*6);
    ve/turb-ve = 5 (x,y,z,h,m) + 1 (xm) + 6 (kx,prho,c,v*) + 1 (divv) +
    7 (alpha,cs*6), +3 with av_clean (gradv). Propagators without a
    sharded pair stage (nbody) ship through GSPMD: 0 here."""
    base = {"std": 18, "std-cooling": 18, "ve": 20, "turb-ve": 20}
    n = base.get(prop, 0)
    if av_clean and prop in ("ve", "turb-ve"):
        n += 3
    return n


def _shard_metrics(ranges, escaped, metrics, axis: str, token=None):
    """(P,) replicated per-shard telemetry arrays (SHARD_DIAG_KEYS) from
    one force stage's halo-exchange products: the five per-shard scalars
    are stacked and shipped in ONE all_gather — O(5P) floats over ICI,
    the Warren-Salmon per-processor work accounting riding the step's
    diagnostics. ``shard_work`` is the candidate rows this shard streams
    per pair op (the pair-stage work proxy); everything travels as f32
    (exact up to 2^24 — far beyond any CI-scale count, and an
    observability quantity beyond that). ``token``: optional predecessor
    value the gather chains on (exchange.chain_after — the XLA:CPU
    collective-rendezvous guard; see parallel/exchange.py)."""
    from sphexa_tpu.parallel.exchange import chain_after

    with phase_scope("shard-metrics"):
        work = jnp.sum(ranges.lens.astype(jnp.float32))
        packed = jnp.stack([
            metrics["halo_rows"].astype(jnp.float32),
            metrics["halo_occ"].astype(jnp.float32),
            work,
            jnp.asarray(escaped, jnp.float32),
            metrics["halo_runs"].astype(jnp.float32),
        ])
        if token is not None:
            packed = chain_after(packed, token)
        g = jax.lax.all_gather(packed, axis)  # (P, 5) replicated
        return {
            "shard_rows": g[:, 0].astype(jnp.int32),
            "shard_occ": g[:, 1],
            "shard_work": g[:, 2],
            "shard_trips": g[:, 3].astype(jnp.int32),
            "shard_runs": g[:, 4].astype(jnp.int32),
        }


#: what a list step's sharded stage returns beside SHARD_DIAG_KEYS
_LIST_DIAG_KEYS = ("list_slack", "list_ok")


def _sharded_head(keys, lists, axis: str, P: int):
    """What a sharded force stage is handed in the sorted keys' place, its
    ``shard_map`` spec and the diagnostics it returns: the keys of a
    streamed step, or the persistent PairLists that replace them."""
    from jax.sharding import PartitionSpec

    if lists is None:
        return keys, PartitionSpec(axis), SHARD_DIAG_KEYS
    return (lists, _slab_lists_specs(axis, P),
            SHARD_DIAG_KEYS + _LIST_DIAG_KEYS)


def _open_stage(stage, lists, head, x, y, z, h, box, P: int, axis: str):
    """Head of a sharded force stage's body: ``(serve, jbuf, the pair ops'
    ``ranges`` / ``lists`` keywords, exchange metrics, escaped)``. A
    streamed step negotiates its halo (``stage``, ``head`` the sorted
    keys); a list step (``head`` the slab's lists) ships over the layout
    frozen with them: nothing is negotiated, nothing can escape."""
    if lists is None:
        ranges, serve, jbuf, escaped, hmetrics = stage(x, y, z, h, head, box)
        return serve, jbuf, {"ranges": ranges}, hmetrics, escaped
    from sphexa_tpu.parallel.exchange import frozen_halo_stage

    serve, jbuf, hmetrics = frozen_halo_stage(head.halo, P, axis)
    return serve, jbuf, {"ranges": None, "lists": head}, hmetrics, False


def _close_stage(walk, escaped, hmetrics, x, y, z, h, occ, token, cap: int,
                 axis: str):
    """Tail of a sharded force stage's body after its last pmin
    (``token``): the occupancy's pmax with the halo's escape folded in,
    then the metrics gather, chained into one order (the tail collectives
    are mutually independent: rendezvous guard). A list step puts its
    validity between the two: each slab's own rows against its own
    build-time positions, pmin over the axis (a halo row's motion is
    checked by its owner). Returns (occ, the per-shard diagnostics)."""
    from sphexa_tpu.parallel import exchange as ex

    lists = walk.get("lists")
    if lists is None:
        occ = ex.fold_escape_sentinel(
            ex.chain_after(occ, token), escaped, cap, axis)
        return occ, _shard_metrics(walk["ranges"], escaped, hmetrics, axis,
                                   token=occ)
    from sphexa_tpu.sph.pair_lists import list_slack

    occ = jax.lax.pmax(ex.chain_after(occ, token), axis)
    with phase_scope("neighbors"):
        slack = jax.lax.pmin(
            ex.chain_after(list_slack(x, y, z, h, lists), occ), axis)
    sdiag = _shard_metrics(lists.ranges, escaped, hmetrics, axis, token=slack)
    sdiag.update(list_slack=slack,
                 list_ok=(slack >= 0.0).astype(jnp.int32))
    return occ, sdiag


def _std_forces_sharded(state, box, cfg: PropagatorConfig, keys, lists=None):
    """std pair-op stage under shard_map: per-device Mosaic kernels on the
    device's SFC slab, halos via the stage ``_halo_stage_fn`` chooses
    (sparse ppermute rounds by default, per-peer windows as the fallback).

    ``lists``: a mesh's persistent PairLists (``rebuild_pair_lists_sharded``)
    in place of ``keys``: every op walks the slab's lists over [own | halo]
    rows, and a serve is a row gather by the frozen send layout + the same
    ppermute rounds: no cell table, coverage, packing or localizing. The
    arrays are then the slabs of the lists' frozen order.

    The arrays arrive GLOBALLY sorted and slab-sharded (the sort is the
    domain redistribution, parallel/mesh.py). The shared prologue runs on
    the local slab against the psum-built global cell table; candidate
    runs outside the slab are served by SFC-peer shards through per-peer
    row windows (parallel/exchange.py — the exchangeHalos analog,
    std_hydro.hpp:131-151). Freshly computed fields the next op reads on
    the j side are re-exchanged over the SAME windows, mirroring the
    reference's per-stage halo choreography. Scalar guards/timesteps are
    pmax/pmin-reduced so every shard returns identical values.
    """
    from jax.sharding import PartitionSpec
    from sphexa_tpu.sph import pallas_pairs as pp

    axis = cfg.shard_axis
    const = cfg.const
    nbr = cfg.nbr
    interpret = _pallas_interpret()
    P = cfg.mesh.shape[cfg.shard_axis]
    S_shard = state.x.shape[0] // P
    nbr = _slab_nbr(cfg, S_shard)

    stage = _halo_stage_fn(cfg, nbr, P, S_shard)

    def forces(box, head, x, y, z, h, m, vx, vy, vz, temp):
        # ``head``: the sorted keys, or the lists that replace them
        serve, jbuf, walk, hmetrics, escaped = _open_stage(
            stage, lists, head, x, y, z, h, box, P, axis)

        halo1 = serve((x, y, z, m))
        rho, nc, occ = pp.pallas_density(
            x, y, z, h, m, None, box, const, nbr, **walk,
            jdata=jbuf((x, y, z, m), halo1), interpret=interpret,
        )
        p, c = hydro_std.compute_eos_std(temp, rho, const)
        halo2 = serve((m / rho,))
        cs, _ = pp.pallas_iad(
            x, y, z, h, m / rho, None, box, const, nbr, **walk,
            jdata=jbuf((x, y, z, m / rho), (halo1[0], halo1[1], halo1[2],
                                            halo2[0])),
            interpret=interpret,
        )
        halo3 = serve((h, vx, vy, vz, rho, p, c, *cs))
        ax, ay, az, du, dt_c, _ = pp.pallas_momentum_energy_std(
            x, y, z, vx, vy, vz, h, m, rho, p, c, *cs,
            None, box, const, nbr, **walk,
            jdata=jbuf((x, y, z, h, vx, vy, vz, m, rho, p, c, *cs),
                       (halo1[0], halo1[1], halo1[2], halo3[0], halo3[1],
                        halo3[2], halo3[3], halo1[3], halo3[4], halo3[5],
                        halo3[6], *halo3[7:])),
            interpret=interpret,
        )
        # the tail collectives in one order: this one follows the serves
        # by its data, _close_stage chains the rest on it
        dt_c = jax.lax.pmin(dt_c, axis)  # jaxlint: disable=JXL006 -- head of the tail chain (_close_stage)
        occ, smetrics = _close_stage(walk, escaped, hmetrics, x, y, z, h,
                                     occ, dt_c, cfg.nbr.cap, axis)
        return rho, c, nc, occ, ax, ay, az, du, dt_c, smetrics

    Pp, Pr = PartitionSpec(axis), PartitionSpec()
    head, head_spec, dkeys = _sharded_head(keys, lists, axis, P)
    # check_vma=False: pallas_call's out_shape carries no varying-axis
    # metadata, which the checker (correctly) refuses to infer; the pmax/
    # pmin reductions above guarantee the replicated outputs really are
    out = shard_map(
        forces,
        mesh=cfg.mesh,
        in_specs=(Pr, head_spec, Pp, Pp, Pp, Pp, Pp, Pp, Pp, Pp, Pp),
        out_specs=(Pp, Pp, Pp, Pr, Pp, Pp, Pp, Pp, Pr,
                   {k: Pr for k in dkeys}),
        check_vma=False,
    )(box, head, state.x, state.y, state.z, state.h, state.m,
      state.vx, state.vy, state.vz, state.temp)
    return out


def _ve_forces_sharded(state, box, cfg: PropagatorConfig, keys, lists=None):
    """VE pair-op stage under shard_map — the flagship propagator on the
    multi-chip fast path (HydroVeProp::computeForces, ve_hydro.hpp:131-208).

    Same structure as _std_forces_sharded: shared prologue on the local
    slab against the psum-built global cell table, candidate halos via
    the windowed all_to_all exchange, one serve round per reference halo
    epoch (xm; kx/prho/c/v; divv; alpha/gradv — ve_hydro.hpp:154-188).
    ``lists``: as ``_std_forces_sharded``'s.
    """
    from jax.sharding import PartitionSpec
    from sphexa_tpu.parallel import exchange as ex
    from sphexa_tpu.sph import pallas_pairs as pp

    axis = cfg.shard_axis
    const = cfg.const
    nbr = cfg.nbr
    interpret = _pallas_interpret()
    P = cfg.mesh.shape[cfg.shard_axis]
    S_shard = state.x.shape[0] // P
    nbr = _slab_nbr(cfg, S_shard)

    stage = _halo_stage_fn(cfg, nbr, P, S_shard)

    def forces(box, min_dt, head, x, y, z, h, m, vx, vy, vz, temp, alpha0):
        serve, jbuf, walk, hmetrics, escaped = _open_stage(
            stage, lists, head, x, y, z, h, box, P, axis)

        hx, hy, hz, hh, hm = serve((x, y, z, h, m))
        xm, nc, occ = pp.pallas_xmass(
            x, y, z, h, m, None, box, const, nbr, **walk,
            jdata=jbuf((x, y, z, m), (hx, hy, hz, hm)), interpret=interpret,
        )
        (hxm,) = serve((xm,))
        (kx, gradh), _ = pp.pallas_ve_def_gradh(
            x, y, z, h, m, xm, None, box, const, nbr, **walk,
            jdata=jbuf((x, y, z, m, xm), (hx, hy, hz, hm, hxm)),
            interpret=interpret,
        )
        prho, c, rho, p = hydro_ve.compute_eos_ve(temp, m, kx, xm, gradh, const)
        hkx, hprho, hc, hvx, hvy, hvz = serve((kx, prho, c, vx, vy, vz))
        cs, dvout, _ = pp.pallas_iad_divv_curlv(
            x, y, z, vx, vy, vz, h, kx, xm,
            None, box, const, nbr, **walk,
            with_gradv=cfg.av_clean,
            jdata=jbuf((x, y, z, xm / kx, xm, vx, vy, vz),
                       (hx, hy, hz, hxm / hkx, hxm, hvx, hvy, hvz)),
            interpret=interpret,
        )
        divv, curlv, gradv = _split_dvout(dvout, cfg.av_clean)
        dt_rho = rho_timestep(divv, const)
        (hdivv,) = serve((divv,))
        alpha = pp.pallas_av_switches(
            x, y, z, vx, vy, vz, h, c, kx, xm, divv, alpha0, *cs,
            None, box, min_dt, const, nbr, **walk,
            jdata=jbuf((x, y, z, c, vx, vy, vz, xm / kx, divv),
                       (hx, hy, hz, hc, hvx, hvy, hvz, hxm / hkx, hdivv)),
            interpret=interpret,
        )[0]
        halo5 = serve((alpha, *cs) + tuple(gradv or ()))
        halpha, *hcs_gv = halo5
        hcs, hgv = hcs_gv[:6], hcs_gv[6:]
        ax, ay, az, du, dt_c, _ = pp.pallas_momentum_energy_ve(
            x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha, *cs,
            None, box, const, nbr, nc=nc, gradv=gradv, **walk,
            jdata=jbuf(
                (x, y, z, h, vx, vy, vz, c, alpha, m, xm, kx, prho, *cs)
                + tuple(gradv or ()),
                (hx, hy, hz, hh, hvx, hvy, hvz, hc, halpha, hm, hxm, hkx,
                 hprho, *hcs) + tuple(hgv),
            ),
            interpret=interpret,
        )
        # the tail collectives in one order (the rest in _close_stage)
        dt_c = jax.lax.pmin(dt_c, axis)
        dt_rho = jax.lax.pmin(ex.chain_after(dt_rho, dt_c), axis)
        occ, smetrics = _close_stage(walk, escaped, hmetrics, x, y, z, h,
                                     occ, dt_rho, cfg.nbr.cap, axis)
        return rho, c, nc, occ, ax, ay, az, du, dt_c, dt_rho, alpha, smetrics

    Pp, Pr = PartitionSpec(axis), PartitionSpec()
    head, head_spec, dkeys = _sharded_head(keys, lists, axis, P)
    out = shard_map(
        forces,
        mesh=cfg.mesh,
        in_specs=(Pr, Pr, head_spec, Pp, Pp, Pp, Pp, Pp, Pp, Pp, Pp, Pp, Pp),
        out_specs=(Pp, Pp, Pp, Pr, Pp, Pp, Pp, Pp, Pr, Pr, Pp,
                   {k: Pr for k in dkeys}),
        check_vma=False,
    )(box, state.min_dt, head, state.x, state.y, state.z, state.h, state.m,
      state.vx, state.vy, state.vz, state.temp, state.alpha)
    return out


def _force_stage_prologue(state, box, cfg: PropagatorConfig, lists, aux=None,
                          keys=None):
    """Shared head of the force stages: list mode (frozen order, validity
    diagnostics; with or without self-gravity, on one device or on a
    mesh: the tree solve sorts a copy of its own inputs, ``_add_gravity``)
    vs per-step box regrow + global sort. Returns
    (state, box, keys, ldiag, aux); keys is None in list mode. ``ldiag``
    is the prologue's own diagnostics: the list's validity in list mode
    on one device (a mesh's slabs check theirs in the sharded stage),
    ``sort_migrant_rows`` where an aux state rides a mesh's sort, else
    None.

    ``keys`` non-None: the caller already regrew the box and sorted (the
    blockdt builders run the bin-folded drift-aware sort themselves) —
    pass everything through untouched."""
    if keys is not None:
        return state, box, keys, None, aux
    if lists is not None:
        from sphexa_tpu.sph.pair_lists import list_slack

        if cfg.shard_axis is not None:
            # each slab checks its own rows inside the force stage's
            # shard_map (_close_stage): no reduction out here
            return state, box, None, None, aux
        with phase_scope("neighbors"):
            slack = list_slack(state.x, state.y, state.z, state.h, lists)
            ldiag = {"list_slack": slack,
                     "list_ok": (slack >= 0.0).astype(jnp.int32)}
        return state, box, None, ldiag, aux
    # grow open-boundary dims to fit drifted particles (box_mpi.hpp
    # role); box limits are traced values, so this never recompiles
    with phase_scope("sort"):
        box = make_global_box(state.x, state.y, state.z, box)
    if aux is not None and cfg.shard_axis is not None:
        # an aux state through the mesh's global sort (std-cooling's
        # chemistry): count what the gather redistributes
        state, keys, aux, migrants = _sort_by_keys(
            state, box, cfg.curve, aux=aux, shards=cfg.mesh.size)
        return state, box, keys, {"sort_migrant_rows": migrants}, aux
    state, keys, aux = _sort_by_keys(state, box, cfg.curve, aux=aux)
    return state, box, keys, None, aux


def _std_forces(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree], aux=None, lists=None, keys=None,
):
    """The std-SPH force stage shared by the plain and cooling propagators
    (HydroProp::computeForces, std_hydro.hpp:123-157): box regrow -> sort ->
    neighbors -> density -> EOS -> IAD -> momentum/energy [-> gravity].
    ``aux`` is an optional per-particle pytree sorted along with the state
    and returned last.

    ``lists``: persistent PairLists — the steady-step fast path: NO box
    regrow, NO sort (the order is frozen at the last rebuild), NO
    prologue; a ``list_ok`` diagnostic reports the Verlet-skin validity
    of THIS step's input positions (an invalid step is discarded and
    replayed by the driver, like a cap overflow). Under self-gravity
    (one device or a mesh) the hydro stays in the frozen order and the
    tree solve takes a key-sorted copy of ``x, y, z, m, h``
    (``_add_gravity``)."""
    const = cfg.const
    state, box, keys, ldiag, aux = _force_stage_prologue(
        state, box, cfg, lists, aux, keys=keys
    )
    x, y, z, h, m = state.x, state.y, state.z, state.h, state.m

    sdiag = None
    if cfg.backend == "pallas" and cfg.shard_axis is not None:
        # multi-chip fast path: per-shard Mosaic kernels under shard_map
        (rho, c, nc, occ, ax, ay, az, du, dt_courant,
         sdiag) = _std_forces_sharded(state, box, cfg, keys, lists=lists)
    elif cfg.backend == "pallas":
        # fused search+op TPU kernels: one shared cell-range prologue,
        # neighbor lists never materialize (sph/pallas_pairs.py)
        from sphexa_tpu.sph import pallas_pairs as pp

        interp = _pallas_interpret()
        if lists is not None:
            ranges = None
            occ = lists.ranges.occupancy
        else:
            ranges = pp.group_cell_ranges(x, y, z, h, keys, box, cfg.nbr)
            occ = ranges.occupancy
        rho, nc, _ = pp.pallas_density(
            x, y, z, h, m, keys, box, const, cfg.nbr, ranges=ranges,
            interpret=interp, lists=lists,
        )
        p, c = hydro_std.compute_eos_std(state.temp, rho, const)
        (c11, c12, c13, c22, c23, c33), _ = pp.pallas_iad(
            x, y, z, h, m / rho, keys, box, const, cfg.nbr, ranges=ranges,
            interpret=interp, lists=lists,
        )
        ax, ay, az, du, dt_courant, _ = pp.pallas_momentum_energy_std(
            x, y, z, state.vx, state.vy, state.vz, h, m, rho, p, c,
            c11, c12, c13, c22, c23, c33, keys, box, const, cfg.nbr,
            ranges=ranges, interpret=interp, lists=lists,
        )
    else:
        nidx, nmask, nc, occ = find_neighbors(x, y, z, h, keys, box, cfg.nbr)

        rho = hydro_std.compute_density(
            x, y, z, h, m, nidx, nmask, box, const, cfg.block
        )
        p, c = hydro_std.compute_eos_std(state.temp, rho, const)
        c11, c12, c13, c22, c23, c33 = hydro_std.compute_iad(
            x, y, z, h, m / rho, nidx, nmask, box, const, cfg.block
        )
        ax, ay, az, du, dt_courant = hydro_std.compute_momentum_energy_std(
            x, y, z, state.vx, state.vy, state.vz, h, m, rho, p, c,
            c11, c12, c13, c22, c23, c33, nidx, nmask, box, const, cfg.block,
        )

    extra_dts, gdiag = (), None
    if cfg.gravity is not None:
        ax, ay, az, egrav, dt_acc, gdiag = _add_gravity(
            state, box, keys, cfg, gtree, ax, ay, az
        )
        extra_dts, gdiag = (dt_acc,), {**gdiag, "egrav": egrav}
    if ldiag is not None:
        gdiag = {**(gdiag or {}), **ldiag}
    if sdiag is not None:
        gdiag = {**(gdiag or {}), **sdiag}

    return (state, box, ax, ay, az, du, dt_courant, extra_dts, nc, occ,
            rho, c, gdiag, aux)


#: the std force stage under its public name: what evaluates it outside a
#: step (a force check against a plain reference) imports
std_forces = _std_forces


def _step_hydro_std(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree] = None, lists=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array]]:
    """One standard-SPH time step (std_hydro.hpp:123-175 sequence).

    Force stage -> timestep -> positions -> smoothing-length update.
    Returns (new_state, new_box, diagnostics).
    """
    (state, box, ax, ay, az, du, dt_courant, extra_dts, nc, occ, rho, c,
     gdiag, _) = _std_forces(state, box, cfg, gtree, lists=lists)
    with phase_scope("timestep"):
        dt = compute_timestep(state.min_dt, dt_courant, *extra_dts,
                              const=cfg.const)
        limiter = _dt_limiter(state.min_dt, cfg.const, courant=dt_courant,
                              accel=extra_dts[0] if extra_dts else None)
    return _integrate_and_finish(
        state, box, cfg, ax, ay, az, du, dt, nc, occ, rho, extra_diag=gdiag,
        c=c, dt_limiter=limiter,
    )


def _step_hydro_std_cooling(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree], chem, cool_cfg, lists=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array], object]:
    """One std-SPH step with radiative cooling
    (HydroGrackleProp::step, std_hydro_grackle.hpp:193-233): force stage ->
    timestep with the cooling-time limiter -> integrate the cooling source
    into du -> positions -> smoothing-length update.

    The per-particle chemistry rides the step's SFC sort and the permuted
    ChemistryData is returned so it stays aligned with the persisted state.
    """
    from sphexa_tpu.physics.cooling import cool_step, cool_timestep

    const = cfg.const
    (state, box, ax, ay, az, du, dt_courant, extra_dts, nc, occ, rho, c,
     gdiag, chem) = _std_forces(state, box, cfg, gtree, aux=chem,
                                lists=lists)

    with phase_scope("cooling"), stage_scope("cooling", "limiter"):
        u = const.cv * state.temp
        dt_cool = cool_timestep(rho, u, chem, cool_cfg)
    with phase_scope("timestep"):
        dt = compute_timestep(
            state.min_dt, dt_courant, dt_cool, *extra_dts, const=const
        )
    # evolved-network mode advances the species alongside u
    # (solve_chemistry, cooler.cpp:313); CIE mode passes chem through
    with phase_scope("cooling"), stage_scope("cooling", "network"):
        du_cool, chem = cool_step(dt, rho, u, chem, cool_cfg)
        du = du + du_cool

    # e_cool_rate: the power the source gives the gas (negative where it
    # radiates), sum(m du_cool) in the ledger's units (eint is sum(m u)).
    # The driver turns it into the energy of the step, with the
    # Adams-Bashforth weights the integrator gives du (Simulation.
    # _cooling_energy): the plain product with dt is half a step's
    # cooling off
    gdiag = {**(gdiag or {}), "dt_cool": dt_cool,
             "du_cool_min": jnp.min(du_cool),
             "e_cool_rate": jnp.sum(state.m * du_cool)}
    with phase_scope("timestep"):
        limiter = _dt_limiter(state.min_dt, const, courant=dt_courant,
                              cool=dt_cool,
                              accel=extra_dts[0] if extra_dts else None)
    new_state, box, diag = _integrate_and_finish(
        state, box, cfg, ax, ay, az, du, dt, nc, occ, rho, extra_diag=gdiag,
        c=c, dt_limiter=limiter,
    )
    return new_state, box, diag, chem


def _pallas_interpret() -> bool:
    """Run Mosaic kernels in interpret mode off-TPU (delegates to the
    engine's single policy)."""
    from sphexa_tpu.sph.pallas_pairs import pallas_interpret

    return pallas_interpret()


def _split_dvout(dvout, av_clean: bool):
    """Unpack the divv/curlv op's outputs (shared by both VE backends)."""
    if av_clean:
        divv, curlv, *gradv = dvout
        return divv, curlv, tuple(gradv)
    divv, curlv = dvout
    return divv, curlv, None


def _ve_forces(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree], lists=None, keys=None,
    raw_dts: bool = False,
):
    """The VE force stage shared by the plain and turbulence-stirred
    propagators (HydroVeProp::computeForces, ve_hydro.hpp:131-208):
    box regrow -> sort -> neighbors -> xmass -> ve_def_gradh -> EOS ->
    IAD -> divv/curlv -> AV switches -> momentum/energy [-> gravity].
    Returns the sorted state plus everything the step tail needs.
    ``lists``: persistent-list steady-step fast path, under self-gravity
    too (see _std_forces).
    """
    const = cfg.const
    state, box, keys, ldiag, _ = _force_stage_prologue(
        state, box, cfg, lists, keys=keys
    )
    x, y, z, h, m = state.x, state.y, state.z, state.h, state.m
    vx, vy, vz = state.vx, state.vy, state.vz

    sdiag = None
    if cfg.backend == "pallas" and cfg.shard_axis is not None:
        # multi-chip fast path: per-shard Mosaic kernels + windowed halos
        (rho, c, nc, occ, ax, ay, az, du, dt_courant, dt_rho,
         alpha, sdiag) = _ve_forces_sharded(state, box, cfg, keys,
                                            lists=lists)
    elif cfg.backend == "pallas":
        # fused search+op TPU engine for the full VE sequence — the
        # reference's flagship propagator (ve_hydro.hpp:131-208) on the
        # fast path, sharing one cell-range prologue across all five ops
        # (IAD and divv/curlv are one neighbour pass)
        from sphexa_tpu.sph import pallas_pairs as pp

        interp = _pallas_interpret()
        if lists is not None:
            ranges = None
            occ = lists.ranges.occupancy
        else:
            ranges = pp.group_cell_ranges(x, y, z, h, keys, box, cfg.nbr)
            occ = ranges.occupancy
        xm, nc, _ = pp.pallas_xmass(
            x, y, z, h, m, keys, box, const, cfg.nbr, ranges=ranges,
            interpret=interp, lists=lists,
        )
        (kx, gradh), _ = pp.pallas_ve_def_gradh(
            x, y, z, h, m, xm, keys, box, const, cfg.nbr, ranges=ranges,
            interpret=interp, lists=lists,
        )
        prho, c, rho, p = hydro_ve.compute_eos_ve(
            state.temp, m, kx, xm, gradh, const
        )
        (c11, c12, c13, c22, c23, c33), dvout, _ = pp.pallas_iad_divv_curlv(
            x, y, z, vx, vy, vz, h, kx, xm,
            keys, box, const, cfg.nbr, ranges=ranges,
            with_gradv=cfg.av_clean, interpret=interp, lists=lists,
        )
        divv, curlv, gradv = _split_dvout(dvout, cfg.av_clean)
        dt_rho = rho_timestep(divv, const)

        alpha, _ = pp.pallas_av_switches(
            x, y, z, vx, vy, vz, h, c, kx, xm, divv, state.alpha,
            c11, c12, c13, c22, c23, c33,
            keys, box, state.min_dt, const, cfg.nbr, ranges=ranges,
            interpret=interp, lists=lists,
        )
        ax, ay, az, du, dt_courant, _ = pp.pallas_momentum_energy_ve(
            x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha,
            c11, c12, c13, c22, c23, c33,
            keys, box, const, cfg.nbr, nc=nc, gradv=gradv, ranges=ranges,
            interpret=interp, lists=lists,
        )
    else:
        nidx, nmask, nc, occ = find_neighbors(x, y, z, h, keys, box, cfg.nbr)

        xm = hydro_ve.compute_xmass(x, y, z, h, m, nidx, nmask, box, const, cfg.block)
        kx, gradh = hydro_ve.compute_ve_def_gradh(
            x, y, z, h, m, xm, nidx, nmask, box, const, cfg.block
        )
        prho, c, rho, p = hydro_ve.compute_eos_ve(state.temp, m, kx, xm, gradh, const)

        c11, c12, c13, c22, c23, c33 = hydro_std.compute_iad(
            x, y, z, h, xm / kx, nidx, nmask, box, const, cfg.block
        )
        dvout = hydro_ve.compute_iad_divv_curlv(
            x, y, z, vx, vy, vz, h, kx, xm,
            c11, c12, c13, c22, c23, c33,
            nidx, nmask, box, const, cfg.block, with_gradv=cfg.av_clean,
        )
        divv, curlv, gradv = _split_dvout(dvout, cfg.av_clean)
        dt_rho = rho_timestep(divv, const)

        alpha = hydro_ve.compute_av_switches(
            x, y, z, vx, vy, vz, h, c, kx, xm, divv, state.alpha,
            c11, c12, c13, c22, c23, c33,
            nidx, nmask, box, state.min_dt, const, cfg.block,
        )

        ax, ay, az, du, dt_courant = hydro_ve.compute_momentum_energy_ve(
            x, y, z, vx, vy, vz, h, m, prho, c, kx, xm, alpha,
            c11, c12, c13, c22, c23, c33,
            nidx, nmask, nc, box, const, cfg.block, gradv=gradv,
        )

    extra_dts, gdiag = (), None
    if cfg.gravity is not None:
        ax, ay, az, egrav, dt_acc, gdiag = _add_gravity(
            state, box, keys, cfg, gtree, ax, ay, az
        )
        extra_dts, gdiag = (dt_acc,), {**gdiag, "egrav": egrav}
    if ldiag is not None:
        gdiag = {**(gdiag or {}), **ldiag}
    if sdiag is not None:
        gdiag = {**(gdiag or {}), **sdiag}

    if raw_dts:
        # blockdt builders combine the candidates themselves (only at
        # the sync substep); hand them back uncombined in the dt slot
        return (state, box, ax, ay, az, du, (dt_courant, dt_rho, extra_dts),
                alpha, nc, occ, rho, c, gdiag)
    with phase_scope("timestep"):
        dt = compute_timestep(state.min_dt, dt_courant, dt_rho, *extra_dts,
                              const=const)
        # limiter attribution rides gdiag into the step diagnostics (the
        # ve builders hand gdiag to the shared tail as extra_diag)
        gdiag = {**(gdiag or {}), "dt_limiter": _dt_limiter(
            state.min_dt, const, courant=dt_courant, rho=dt_rho,
            accel=extra_dts[0] if extra_dts else None)}
    return state, box, ax, ay, az, du, dt, alpha, nc, occ, rho, c, gdiag


def _step_hydro_ve(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree] = None, lists=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array]]:
    """One generalized-volume-element SPH time step.

    Mirrors HydroVeProp::step (ve_hydro.hpp:210-223): the VE force stage,
    then timestep -> positions -> smoothing-length update. The reference's
    halo exchanges between stages vanish: XLA materializes whatever
    communication the shardings imply.
    """
    (state, box, ax, ay, az, du, dt, alpha, nc, occ, rho, c, gdiag) = _ve_forces(
        state, box, cfg, gtree, lists=lists
    )
    return _integrate_and_finish(
        state, box, cfg, ax, ay, az, du, dt, nc, occ, rho,
        extra={"alpha": alpha}, extra_diag=gdiag, c=c,
    )


def _step_turb_ve(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree], turb, turb_cfg, lists=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array], object]:
    """One stirred VE step (TurbVeProp::step, turb_ve.hpp:70-86): VE forces
    -> timestep -> OU-driven stirring accelerations -> positions ->
    smoothing-length update. Returns the advanced TurbulenceState too."""
    from sphexa_tpu.sph.hydro_turb import drive_turbulence

    (state, box, ax, ay, az, du, dt, alpha, nc, occ, rho, c, gdiag) = _ve_forces(
        state, box, cfg, gtree, lists=lists
    )
    with phase_scope("turbulence"):
        ax, ay, az, turb = drive_turbulence(
            state.x, state.y, state.z, ax, ay, az, dt, turb, turb_cfg
        )
    new_state, box, diag = _integrate_and_finish(
        state, box, cfg, ax, ay, az, du, dt, nc, occ, rho,
        extra={"alpha": alpha}, extra_diag=gdiag, c=c,
    )
    return new_state, box, diag, turb


def _step_nbody(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree] = None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array]]:
    """One gravity-only N-body step (main/src/propagator/nbody.hpp:51-156).

    sort -> multipole upsweep -> Barnes-Hut traversal -> acceleration
    timestep -> position update. No hydro fields are touched (du = 0).
    """
    const = cfg.const
    with phase_scope("sort"):
        box = make_global_box(state.x, state.y, state.z, box)
    state, keys, _ = _sort_by_keys(state, box, cfg.curve)

    zero = jnp.zeros_like(state.x)
    ax, ay, az, egrav, dt_acc, gdiag = _add_gravity(
        state, box, keys, cfg, gtree, zero, zero, zero
    )
    with phase_scope("timestep"):
        dt = compute_timestep(state.min_dt, dt_acc, const=const)
        limiter = _dt_limiter(state.min_dt, const, accel=dt_acc)

    nc = jnp.zeros_like(state.x, dtype=jnp.int32)
    return _integrate_and_finish(
        state, box, cfg, ax, ay, az, zero, dt, nc, jnp.int32(0), zero,
        extra_diag={**gdiag, "egrav": egrav}, update_smoothing=False,
        dt_limiter=limiter,
    )


# ---------------------------------------------------------------------------
# hierarchical block time steps (sph/blockdt.py)
# ---------------------------------------------------------------------------


def _integrate_and_finish_blockdt(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    ax, ay, az, du, dt_min, dt_prev, due, bins, dt_eff, nc, occ, rho,
    extra=None, extra_diag=None, c=None, dt_limiter=None,
):
    """Block-timestep twin of _integrate_and_finish: the Press update is
    evaluated with PER-PARTICLE dt arrays (compute_positions is fully
    elementwise in dt/dt_m1) and applied to DUE rows only; inactive rows
    get the KDK-consistent drift ``x += v * dt_min`` (PBC-folded) with
    every other field frozen.  Due rows first rebase away the drift
    accumulated since their last kick, so the update runs from the
    kick-time position with the full ``dt_eff = dt_min * 2**k``.

    The conservation ledger still runs over ALL rows (deviation from the
    ISSUE's active-rows wording, by design: the energy totals need the
    frozen rows' contributions every substep — the active-rows saving is
    the UPDATE reduction, which is exactly what bdt_active records).
    """
    const = cfg.const
    with phase_scope("integrate"):
        # bins>0 gate: at k=0 the rebase term is exactly zero, but
        # a - 0.0 is not a bitwise identity for a = -0.0
        rebase = due & (bins > 0)
        dr = dt_eff - dt_min
        bx = jnp.where(rebase, state.x - state.vx * dr, state.x)
        by = jnp.where(rebase, state.y - state.vy * dr, state.y)
        bz = jnp.where(rebase, state.z - state.vz * dr, state.z)
        fields = (bx, by, bz, state.x_m1, state.y_m1, state.z_m1,
                  state.vx, state.vy, state.vz, state.h,
                  state.temp, state.temp_lo, du, state.du_m1)
        (nx, ny, nz, dxm, dym, dzm, vx, vy, vz, h, temp, temp_lo, ndu,
         du_m1) = compute_positions(
            fields, ax, ay, az, dt_eff, dt_prev, box, const
        )
        drift = put_in_box(box, jnp.stack(
            [state.x + state.vx * dt_min,
             state.y + state.vy * dt_min,
             state.z + state.vz * dt_min], axis=-1))
        sel = lambda a, b: jnp.where(due, a, b)
        new_h = sel(update_h(const.ng0, nc + 1, h), state.h)
        new_state = dataclasses.replace(
            state,
            x=sel(nx, drift[:, 0]), y=sel(ny, drift[:, 1]),
            z=sel(nz, drift[:, 2]),
            x_m1=sel(dxm, state.x_m1), y_m1=sel(dym, state.y_m1),
            z_m1=sel(dzm, state.z_m1),
            vx=sel(vx, state.vx), vy=sel(vy, state.vy),
            vz=sel(vz, state.vz),
            h=new_h, temp=sel(temp, state.temp),
            temp_lo=sel(temp_lo, state.temp_lo),
            du=sel(ndu, state.du), du_m1=sel(du_m1, state.du_m1),
            ttot=state.ttot + dt_min, min_dt=dt_min,
            min_dt_m1=state.min_dt,
            **(extra or {}),
        )
        diagnostics = {
            "dt": dt_min,
            "nc_mean": jnp.mean(nc.astype(jnp.float32)) + 1.0,
            "nc_max": jnp.max(nc) + 1,
            "occupancy": occ,
            "rho_max": jnp.max(rho),
            "h_max": jnp.max(new_h),
        }
    if cfg.obs is not None:
        ed = extra_diag or {}
        diagnostics.update(ledger_diagnostics(
            new_state, rho, nc, const, cfg.nbr.ngmax, spec=cfg.obs,
            egrav=ed.get("egrav", 0.0), box=box, c=c,
            smoothing=True,
            token=ed.get("shard_trips"),
        ))
    # snapshot deposit, conditional like cfg.obs (see
    # _integrate_and_finish); runs over ALL rows like the ledger — the
    # frame must show the frozen rows too
    if cfg.snap is not None:
        ed = extra_diag or {}
        diagnostics.update(snapshot_diagnostics(
            new_state, rho, box, cfg.snap,
            token=diagnostics.get("rho_min", ed.get("shard_trips")),
        ))
    if dt_limiter is not None:
        diagnostics["dt_limiter"] = dt_limiter
    if cfg.keep_accels:
        diagnostics.update({"ax": ax, "ay": ay, "az": az})
    diagnostics.update(extra_diag or {})
    return new_state, box, diagnostics


def _blockdt_prologue(state, box, cfg: PropagatorConfig, bst):
    """Box regrow + the blockdt sort.  dt_bins = 1 routes through the
    PLAIN _sort_by_keys call (no fold, no resort cond) so the whole step
    stays bitwise-identical to the global-dt path; deeper stacks get the
    bin-folded drift-aware sort.  The BlockDtState rides the aux channel
    (its (n,) leaves permute, its scalars pass through)."""
    with phase_scope("sort"):
        box = make_global_box(state.x, state.y, state.z, box)
    if cfg.dt_bins == 1:
        state, keys, bst = _sort_by_keys(state, box, cfg.curve, aux=bst)
        return state, box, keys, bst, jnp.int32(1), jnp.int32(0)
    state, keys, bst, resorted, inv = _sort_by_keys(
        state, box, cfg.curve, aux=bst, bins=bst.bins,
        resort_drift=cfg.bin_resort_drift)
    return state, box, keys, bst, resorted, inv


def _blockdt_tail(state, box, cfg: PropagatorConfig, ax, ay, az, du,
                  dt_sync, bst, resorted, inv, nc, occ, rho, c=None,
                  dt_limiter=None, gdiag=None, alpha=None):
    """Shared bin bookkeeping + due-rows integration of the blockdt step
    builders: sync-substep dt_min/bin refresh, due mask, bitmask-rank
    active compaction, BlockDtState advance, then the blockdt integrate
    tail.  All of it is elementwise or global-reduction math OUTSIDE
    shard_map — on mesh runs GSPMD partitions it and the shard_map
    collective order the JXA201 rule pins is untouched."""
    const = cfg.const
    B = cfg.dt_bins
    C = bdt.cycle_length(B)
    with phase_scope("dt-bins"):
        is_sync = bst.substep == 0
        dt_min = jnp.where(is_sync, dt_sync, bst.dt_min)
        grav = cfg.gravity is not None
        cand = bdt.particle_dt_candidates(
            state.h, c, const,
            ax=ax if grav else None, ay=ay if grav else None,
            az=az if grav else None)
        rebin = is_sync & (bst.cycle % cfg.bin_sync_every == 0)
        bins = jnp.where(rebin, bdt.assign_bins(cand, dt_min, B), bst.bins)
        due = bdt.due_mask(bins, bst.substep)
        # exact power-of-two scale: integer shift -> f32 (exp2 may not
        # hit integer points exactly on every backend; 1 << k does)
        dt_eff = dt_min * jnp.left_shift(1, bins).astype(jnp.float32)
        use_kernel = cfg.backend == "pallas" and cfg.shard_axis is None
        idx_act, n_active = bdt.compact_active(
            due, use_kernel=use_kernel, interpret=_pallas_interpret())
        pop = bdt.bin_populations(bins, B)
        lane = jnp.arange(state.n, dtype=jnp.int32)
        work = jnp.sum(jnp.where(lane < n_active,
                                 nc[idx_act], 0).astype(jnp.float32))
        bdiag = {"bdt_active": n_active, "bdt_pop": pop,
                 "bdt_substep": bst.substep, "bdt_resort": resorted,
                 "bdt_drift": inv, "bdt_work": work}
        wrap = bst.substep + 1 >= C
        new_bst = dataclasses.replace(
            bst, bins=bins,
            dt_prev=jnp.where(due, dt_eff, bst.dt_prev),
            substep=jnp.where(wrap, 0, bst.substep + 1),
            cycle=bst.cycle + wrap.astype(jnp.int32),
            dt_min=dt_min)
    extra = None if alpha is None else {
        "alpha": jnp.where(due, alpha, state.alpha)}
    extra_diag = {**(gdiag or {}), **bdiag}
    if B == 1:
        # one bin: every row is due every substep with dt_eff == dt_min,
        # so the step IS the global step. It goes through the global
        # tail, not through a twin fed the same values: the due-selects
        # and per-row dt operands of the twin change how XLA contracts
        # the Press update's multiply-adds, and dt_bins=1 is pinned
        # bitwise against the global path
        new_state, box, diag = _integrate_and_finish(
            state, box, cfg, ax, ay, az, du, dt_min, nc, occ, rho,
            extra=extra, extra_diag=extra_diag, c=c, dt_limiter=dt_limiter)
    else:
        new_state, box, diag = _integrate_and_finish_blockdt(
            state, box, cfg, ax, ay, az, du, dt_min, bst.dt_prev, due,
            bins, dt_eff, nc, occ, rho, extra=extra,
            extra_diag=extra_diag, c=c, dt_limiter=dt_limiter)
    return new_state, box, diag, new_bst


def _step_hydro_std_blockdt(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree] = None, bst=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array], object]:
    """One std-SPH step under hierarchical block time steps (Bonsai's
    block scheme, Bédorf et al. 2014 §3.4; sph/blockdt.py).

    Bin-folded drift-aware sort -> full-shape force sweep (inactive
    particles are sources at drifted positions; the fixed-shape engines
    are untouched) -> sync-substep dt_min refresh + re-binning -> active
    compaction -> due-rows-only integration.  The update REDUCTION is
    what bdt_active/bdt_pop record — the chip-free complexity proxy
    (docs/NEXT.md round 12).  Returns (state, box, diagnostics, bst).
    """
    const = cfg.const
    state, box, keys, bst, resorted, inv = _blockdt_prologue(
        state, box, cfg, bst)
    (state, box, ax, ay, az, du, dt_courant, extra_dts, nc, occ, rho, c,
     gdiag, _) = _std_forces(state, box, cfg, gtree, keys=keys)
    with phase_scope("timestep"):
        dt_sync = compute_timestep(state.min_dt, dt_courant, *extra_dts,
                                   const=const)
        limiter = _dt_limiter(state.min_dt, const, courant=dt_courant,
                              accel=extra_dts[0] if extra_dts else None)
    return _blockdt_tail(state, box, cfg, ax, ay, az, du, dt_sync, bst,
                         resorted, inv, nc, occ, rho, c=c,
                         dt_limiter=limiter, gdiag=gdiag)


def _step_hydro_ve_blockdt(
    state: ParticleState, box: Box, cfg: PropagatorConfig,
    gtree: Optional[GravityTree] = None, bst=None,
) -> Tuple[ParticleState, Box, Dict[str, jax.Array], object]:
    """One VE-SPH step under hierarchical block time steps — the same
    scheme as _step_hydro_std_blockdt over the VE force stage (raw dt
    candidates; the sync-substep combination below is the same
    compute_timestep expression the global ve path uses).  AV alpha
    freezes on inactive rows like every other evolved field."""
    const = cfg.const
    state, box, keys, bst, resorted, inv = _blockdt_prologue(
        state, box, cfg, bst)
    (state, box, ax, ay, az, du, (dt_courant, dt_rho, extra_dts), alpha,
     nc, occ, rho, c, gdiag) = _ve_forces(
        state, box, cfg, gtree, keys=keys, raw_dts=True)
    with phase_scope("timestep"):
        dt_sync = compute_timestep(state.min_dt, dt_courant, dt_rho,
                                   *extra_dts, const=const)
        limiter = _dt_limiter(state.min_dt, const, courant=dt_courant,
                              rho=dt_rho,
                              accel=extra_dts[0] if extra_dts else None)
    return _blockdt_tail(state, box, cfg, ax, ay, az, du, dt_sync, bst,
                         resorted, inv, nc, occ, rho, c=c,
                         dt_limiter=limiter, gdiag=gdiag, alpha=alpha)


# ---------------------------------------------------------------------------
# jitted step variants
# ---------------------------------------------------------------------------
# Every step builder ships as a PAIR of jits over the same impl:
#
# - the plain variant keeps every input alive: the Simulation's
#   discard-and-replay contract (cap overflow, expired lists, deferred
#   rollback) re-launches from the SAME state object, so the checked path
#   must never consume its input;
# - the ``*_donated`` twin donates the particle-state pytree, letting XLA
#   alias the step's output into the input buffers — no double-buffering
#   of the MB/GB-scale state, which is what bounds the largest runnable N
#   per chip. It is only launched on paths that can never need the input
#   again (Simulation deferred happy-path windows, which pin a COPY for
#   rollback) and is the variant the jaxaudit donation rule (JXA103)
#   holds the registry to.


def _step_pair(impl, static):
    plain = jax.jit(impl, static_argnames=static)
    donated = jax.jit(impl, static_argnames=static,
                      donate_argnames=("state",))
    return plain, donated


step_hydro_std, step_hydro_std_donated = _step_pair(
    _step_hydro_std, ("cfg",))
step_hydro_std_cooling, step_hydro_std_cooling_donated = _step_pair(
    _step_hydro_std_cooling, ("cfg", "cool_cfg"))
step_hydro_ve, step_hydro_ve_donated = _step_pair(
    _step_hydro_ve, ("cfg",))
step_turb_ve, step_turb_ve_donated = _step_pair(
    _step_turb_ve, ("cfg", "turb_cfg"))
step_nbody, step_nbody_donated = _step_pair(_step_nbody, ("cfg",))
# blockdt pairs donate the ParticleState only: the BlockDtState carry is
# small and the rollback window keeps the SAME object across a replay
step_hydro_std_blockdt, step_hydro_std_blockdt_donated = _step_pair(
    _step_hydro_std_blockdt, ("cfg",))
step_hydro_ve_blockdt, step_hydro_ve_blockdt_donated = _step_pair(
    _step_hydro_ve_blockdt, ("cfg",))


# ---------------------------------------------------------------------------
# the unified SimState carry contract
# ---------------------------------------------------------------------------
# Each family's step keeps its historical positional signature (the
# lowering lock pins those byte-identical), but the DISPATCH onto them is
# one table + one adapter: which SimState aux slot a step function
# carries, and whether it takes a static aux config. The driver
# (simulation.py), the sharded stepper (parallel/mesh.py) and the audit
# registry all route through this mapping, so the carry structure cannot
# drift per call site.

#: step function -> SimState aux slot it consumes/produces (absent =
#: plain 3-tuple family with no aux carry)
STEP_AUX_SLOT = {
    step_turb_ve: "turb",
    step_turb_ve_donated: "turb",
    step_hydro_std_cooling: "chem",
    step_hydro_std_cooling_donated: "chem",
    step_hydro_std_blockdt: "bdt",
    step_hydro_std_blockdt_donated: "bdt",
    step_hydro_ve_blockdt: "bdt",
    step_hydro_ve_blockdt_donated: "bdt",
}

#: aux-carrying steps that ALSO take a static aux config positional
#: (turbulence / cooling); the blockdt twins carry state only
STEP_AUX_CFG = {
    step_turb_ve,
    step_turb_ve_donated,
    step_hydro_std_cooling,
    step_hydro_std_cooling_donated,
}


def step_sim_state(step_fn, sim, cfg, gtree=None, aux_cfg=None, **kw):
    """Advance one step on a ``state.SimState`` carry.

    Maps the unified carry onto ``step_fn``'s positional contract and
    folds the outputs back: ``(new_sim, diagnostics)``. Only the slot
    ``step_fn`` owns is replaced — inactive slots pass through untouched,
    so the carry treedef is closed under stepping (the JXA503
    invariant). Pure and trace-safe: usable inside jit/vmap as well as
    from the host driver.
    """
    slot = STEP_AUX_SLOT.get(step_fn)
    if slot is None:
        s, b, diag = step_fn(sim.particles, sim.box, cfg, gtree, **kw)
        return sim.with_slot(None, None, particles=s, box=b), diag
    aux = getattr(sim, slot)
    if step_fn in STEP_AUX_CFG:
        s, b, diag, new_aux = step_fn(
            sim.particles, sim.box, cfg, gtree, aux, aux_cfg, **kw
        )
    else:
        s, b, diag, new_aux = step_fn(
            sim.particles, sim.box, cfg, gtree, aux, **kw
        )
    return sim.with_slot(slot, new_aux, particles=s, box=b), diag
