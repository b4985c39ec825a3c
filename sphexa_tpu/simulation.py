"""Host-side simulation driver: config selection, step loop, diagnostics.

Counterpart of the reference front-end main loop (main/src/sphexa/
sphexa.cpp:145-174). The host's only jobs are (a) choosing the static
neighbor-search configuration (grid level, cell cap) and re-choosing it
when particle motion invalidates it — the rare recompile boundary — and
(b) logging/IO. All physics runs inside the jitted step.
"""

import dataclasses
import functools
import os
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from sphexa_tpu.telemetry import Telemetry, emit_memory_event
from sphexa_tpu.telemetry.registry import set_current, span

from sphexa_tpu.gravity.traversal import GravityConfig, estimate_gravity_caps
from sphexa_tpu.neighbors.cell_list import (
    NeighborConfig,
    choose_grid_level,
)
from sphexa_tpu.propagator import (
    PropagatorConfig,
    step_hydro_std,
    step_hydro_std_blockdt,
    step_hydro_std_blockdt_donated,
    step_hydro_std_cooling,
    step_hydro_std_cooling_donated,
    step_hydro_std_donated,
    step_hydro_ve,
    step_hydro_ve_blockdt,
    step_hydro_ve_blockdt_donated,
    step_hydro_ve_donated,
    step_nbody,
    step_nbody_donated,
    step_sim_state,
    step_turb_ve,
    step_turb_ve_donated,
)
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sph.blockdt import make_blockdt_state
from sphexa_tpu.sph.particles import ParticleState, SimConstants
from sphexa_tpu.state import SimState
from sphexa_tpu.util.device import device_info, on_tpu, resolve_backend

_PROPAGATORS: Dict[str, Callable] = {
    "std": step_hydro_std,
    "ve": step_hydro_ve,
    "nbody": step_nbody,
    "turb-ve": step_turb_ve,
    "std-cooling": step_hydro_std_cooling,
}

# donated twins (propagator._step_pair): the particle-state pytree is
# consumed in place — ONLY safe on launch paths that can never need the
# input again (the deferred happy-path window, which pins a copy for
# rollback); the checked/replay paths always use _PROPAGATORS
_PROPAGATORS_DONATED: Dict[str, Callable] = {
    "std": step_hydro_std_donated,
    "ve": step_hydro_ve_donated,
    "nbody": step_nbody_donated,
    "turb-ve": step_turb_ve_donated,
    "std-cooling": step_hydro_std_cooling_donated,
}

# hierarchical block-timestep twins (Simulation(dt_bins=...)): the std/ve
# builders that carry a BlockDtState through the aux slot and return a
# 4-tuple; the donated variants consume the ParticleState ONLY, so the
# carry is safe to pin by reference for window rollback
_PROPAGATORS_BLOCKDT: Dict[str, Callable] = {
    "std": step_hydro_std_blockdt,
    "ve": step_hydro_ve_blockdt,
}
_PROPAGATORS_BLOCKDT_DONATED: Dict[str, Callable] = {
    "std": step_hydro_std_blockdt_donated,
    "ve": step_hydro_ve_blockdt_donated,
}

#: skin fraction a step must be predicted to FIND (its ``list_slack``)
#: for the plan to launch it. The plan extrapolates the skin use of the
#: last verified step over at most ``check_every`` further ones; what
#: it cannot see is the flow changing inside that horizon. 0.05 is a
#: third of the use of one step of the shortest-lived list measured
#: (Noh at 1.1M, 0.14-0.17 per step): it absorbs a 10 % change of that
#: use over three planned steps. A miss costs a rolled-back window (a
#: rebuild and a window of replays); the margin costs a list whose
#: last step would have found less than 0.05 one step of its life.
_LIST_COVER_MARGIN = 0.05


class WindowPlan(NamedTuple):
    """The next check window, planned from the skin the list has left."""
    #: skin fraction the next step is predicted to use (None: no trend)
    rate: Optional[float]
    #: further steps the list is predicted to serve (None: no trend)
    cover: Optional[int]
    #: steps to launch before the next fetch, 1..check_every
    steps: int


@dataclasses.dataclass(frozen=True)
class SkinTrend:
    """How fast the steps use a pair list's Verlet skin, read from the
    ``list_slack`` and ``dt`` every verified step's diagnostics carry.

    Step k of a list finds ``slack`` = the skin left after the k-1 steps
    before it and then moves the particles for its ``dt``, so two
    consecutive steps of one list give the use per unit of simulated
    time, ``(slack[k-1] - slack[k]) / dt[k-1]``. The largest drift is a
    maximum over particles of (nearly) linear functions of time, so the
    newest difference is the best estimate and errs low only when the
    flow itself speeds up. The rate survives a rebuild (the flow is
    continuous; a fresh list has slack 1 and no history of its own)."""

    #: skin fraction used per unit of simulated time (None: not seen yet)
    rate: Optional[float] = None
    #: the last verified step's dt, and by how much the next is taken to
    #: exceed it: the last observed ratio, never under 1 (a falling dt
    #: is not bet on), never over ``const.max_dt_increase``
    dt: Optional[float] = None
    growth: float = 1.0
    #: ``list_slack`` of the last verified step ON THE LIVE LIST; None
    #: where the next step is a list's first (its slack is exactly 1)
    slack: Optional[float] = None

    def observe(self, slack, dt, max_dt_increase: float) -> "SkinTrend":
        """Fold in one verified step (oldest first). A step that carries
        no ``list_slack`` ran without lists and says nothing."""
        if slack is None:
            return self
        slack, dt = float(slack), float(dt)
        rate, growth = self.rate, self.growth
        if self.dt:
            growth = min(max(dt / self.dt, 1.0), max_dt_increase)
            if self.slack is not None:
                rate = (self.slack - slack) / self.dt
        return SkinTrend(rate, dt, growth, slack)

    def rebuilt(self) -> "SkinTrend":
        """The list was replaced: the next step finds slack 1."""
        return dataclasses.replace(self, slack=None)

    def plan(self, check_every: int) -> WindowPlan:
        """``cover`` = how many further steps are predicted to find at
        least ``_LIST_COVER_MARGIN`` of the skin, the j-th of them
        ``now - use * (1 + g + ... + g**(j-2))``; the window is that
        many steps and never more than ``check_every``. With no trend
        (a run's first list, a flow at rest) it is the whole window and
        the rollback is the net."""
        if self.rate is None or self.rate <= 0.0:
            return WindowPlan(None, None, check_every)
        use = self.rate * self.dt * self.growth
        # the last verified step's own use is not in any fetched slack
        now = 1.0 if self.slack is None else self.slack - self.rate * self.dt
        room = (now - _LIST_COVER_MARGIN) / use
        if room < 0.0:
            cover = 0
        elif self.growth > 1.0:
            g = self.growth
            cover = 1 + int(np.log1p(room * (g - 1.0)) / np.log(g))
        else:
            cover = 1 + int(room)
        return WindowPlan(use, cover, max(1, min(check_every, cover)))


def make_propagator_config(
    state: ParticleState,
    box: Box,
    const: SimConstants,
    ngmax: Optional[int] = None,
    block: Optional[int] = None,
    curve: str = "hilbert",
    min_cap: int = 0,
    av_clean: bool = False,
    keep_accels: bool = False,
    backend: str = "auto",
    cell_target: Optional[int] = None,
    run_cap: Optional[int] = None,
    gap: Optional[int] = None,
    group: Optional[int] = None,
    device_sizing: bool = False,
    use_lists: bool = False,
    list_skin_rel: Optional[float] = None,
    list_slot_margin: float = 1.3,
    h_relax: float = 1.0,
    sizing_cache=None,
    obs_spec=None,
    snap_spec=None,
    tuned: object = None,
    workload: Optional[str] = None,
    dt_bins: Optional[int] = None,
    bin_sync_every: int = 1,
    bin_resort_drift: float = 0.0,
    mesh=None,
    halo_margin: float = 1.4,
) -> PropagatorConfig:
    """Size the static neighbor-search config from the current particle
    distribution (single source of truth — used by Simulation, tests and
    the driver entry points).

    ``cell_target`` picks the grid level by mean cell occupancy;
    ``run_cap``/``gap`` control the pallas engine's merged-run streaming
    (cell_list.NeighborConfig). Defaults tuned on v5e: ~128-per-cell
    grids beat finer levels (fragmented short runs waste 128-lane
    chunks), and aggressive run merging cuts the per-group DMA count
    ~3x.

    ``device_sizing``: compute every sizing statistic with jitted
    reductions on the (possibly sharded) device arrays and fetch only
    scalars — the O(N/P) path multi-device runs use (VERDICT r3 #3; the
    reference's rank-local assignment, assignment.hpp:84-122). The
    default host path keeps the native C++ runtime exercised
    single-device.

    ``sizing_cache``: optional precomputed (keys, order, box the keys
    were made in) device arrays for the device_sizing path, so a caller
    that also needs keys (the gravity reconfigure) computes them once.

    ``mesh`` (with ``device_sizing`` and ``use_lists``): the run's mesh.
    Persistent lists on a mesh are sized per slab, and with them the halo
    caps of the skin-inflated windows their rebuild negotiates over
    (``halo_cells`` / ``halo_runs`` of the returned config, under
    ``halo_margin``): parallel/sizing.device_list_caps, each count taken
    under ``shard_map`` as the rebuild takes it.

    ``h_relax`` (>= 1): how far ``h`` still is from the fixed point of
    its update (kernels.h_fixed_point, read by the driver from a
    verified step). Persistent lists outlive many steps, so their
    window and slot budget are sized for ``h * h_relax``; 1 sizes for
    ``h`` as it stands.
    """
    backend = resolve_backend(backend)
    # tuned knob resolution (docs/TUNING.md): the engine knobs default to
    # None so an explicit kwarg stays detectable; precedence is explicit
    # kwarg > table entry (``tuned=``) > the measured defaults below.
    # Table lookups here are single-device (P=1) — Simulation resolves
    # with the real mesh size and passes the winners explicitly.
    _defaults = {"block": 2048, "cell_target": 128, "run_cap": 1536,
                 "gap": 384, "group": 64, "list_skin_rel": 0.2}
    _explicit = {
        k: v for k, v in (("block", block), ("cell_target", cell_target),
                          ("run_cap", run_cap), ("gap", gap),
                          ("group", group),
                          ("list_skin_rel", list_skin_rel))
        if v is not None
    }
    _tuned = {}
    if tuned is not None:
        from sphexa_tpu.tuning.table import resolve_knobs

        _tuned, _ = resolve_knobs(tuned, workload=workload, n=state.n,
                                  p=1, backend=backend,
                                  explicit=_explicit)
    block, cell_target, run_cap, gap, group, list_skin_rel = (
        _explicit.get(k, _tuned.get(k, _defaults[k]))
        for k in ("block", "cell_target", "run_cap", "gap", "group",
                  "list_skin_rel"))
    from sphexa_tpu.neighbors.cell_list import pad_cap, window_cells

    if device_sizing:
        from sphexa_tpu.parallel import sizing

        lengths = np.asarray(sizing.fetch(box.lengths))
        h_max = float(sizing.fetch(jnp.max(state.h)))
        level = choose_grid_level(lengths, h_max)
        level_occ = max(
            1, round(np.log2(max(state.n / float(cell_target), 1.0)) / 3.0)
        )
        level = min(level, level_occ)
        occ, ext_d = sizing.sizing_stats(
            state.x, state.y, state.z, box, level, group, curve,
            *(sizing_cache[:2] if sizing_cache else (None, None))
        )
        cap = pad_cap(int(sizing.fetch(occ)))
        ext = np.asarray(sizing.fetch(ext_d))
        if min_cap > 0:
            cap = max(cap, pad_cap(min_cap))
        ncell = 1 << level
    else:
        lengths = np.asarray(box.lengths)
        h = np.asarray(state.h)
        h_max = float(h.max())
        level = choose_grid_level(lengths, h_max)
        # group-window search covers the 2h radius at ANY level, so the
        # level is free to target cell occupancy instead; below
        # ~cell_target particles per cell the extra window cells stop
        # paying for the tighter candidate volume
        level_occ = max(
            1, round(np.log2(max(state.n / float(cell_target), 1.0)) / 3.0)
        )
        level = min(level, level_occ)

        # host-side sizing pass: one device->host transfer of the
        # coordinates, then the native C++ runtime (sphexa_tpu/native)
        # does keygen, sort and occupancy/window accounting (numpy/jax
        # fallback inside)
        from sphexa_tpu import native

        xa = np.asarray(state.x)
        ya = np.asarray(state.y)
        za = np.asarray(state.z)
        keys = native.compute_keys(xa, ya, za, np.asarray(box.lo), lengths,
                                   curve)
        order = native.argsort_keys(keys)

        cap = pad_cap(native.max_cell_occupancy(keys[order], level))
        if min_cap > 0:
            cap = max(cap, pad_cap(min_cap))  # quantized so retry caps cache
        ncell = 1 << level
        ext = native.group_extents(xa, ya, za, order, group)
    # The Mosaic engine only CLIPS a cell's run at ``cap``: every buffer it
    # allocates is sized by NeighborConfig.dma_cap = max(cap, run_cap), so
    # a cap under run_cap bounds nothing, and outgrowing it re-sizes into
    # identical shapes (a recompile for no new buffer: Noh's central cells
    # gain 4 % a step). A mesh clamps run_cap to the slab AFTER this
    # sizing (parallel/sizing.py), so there the cap stays as measured,
    # unless it walks lists: a list outlives many steps, and the clamp is
    # known here (a slab's rows).
    if backend == "pallas" and not device_sizing:
        cap = max(cap, run_cap)
    elif backend == "pallas" and use_lists and mesh is not None:
        cap = max(cap, min(run_cap, state.n // mesh.size))

    # 10% radius slack absorbs drift between reconfigurations; a whole
    # margin cell costs ~2x window cells (every cell is a kernel iteration),
    # and the window_ok guard reconfigures if the slack is ever outgrown.
    def size_window(radius, margin_cells=0):
        w = 1
        for e, edge in zip(ext, lengths / ncell):
            w = max(w, window_cells(e, radius, float(edge), ncell,
                                    margin_cells=margin_cells))
        return w

    def make_nbr(window):
        return NeighborConfig(
            level=level, cap=cap, ngmax=ngmax or const.ngmax, block=block,
            curve=curve, group=group, window=window,
            run_cap=run_cap, gap=gap,
        )

    nbr = make_nbr(size_window(4.0 * h_max * 1.1))
    slot_cap = slots_cap = 0
    halo_cells, halo_runs = (), 0
    if use_lists and backend == "pallas" and (
            mesh is not None or not device_sizing):
        from sphexa_tpu.sph.pair_lists import estimate_list_caps
        from sphexa_tpu.sph.pallas_pairs import engine_fold

        # fold-mode eligibility is checked on the UNinflated window: the
        # skin inflation only pays off when lists actually engage
        if not engine_fold(box, nbr):
            import jax.numpy as _jnp

            # a list is built once and walked for many steps, so it is
            # sized for the h its particles are relaxing to (h_relax),
            # and its window covers what the build inflates a group's
            # bbox by: 2h + skin on EACH side
            h_to = h_max * h_relax
            skin = list_skin_rel * 2.0 * h_to
            # Where no dimension is periodic the particles need not tile
            # the grid: SFC-consecutive groups straddle stretches of the
            # curve that lie outside them, and their extent moves with
            # the flow across the fixed grid (Noh's sphere: the largest
            # goes 0.20 -> 0.29 of the box in 30 steps), which no slack
            # on the radius covers: one margin cell. Only the build's
            # prologue walks a list's window (culled cells reach no
            # kernel), so list mode pays nothing per step for it.
            open_box = not any(b == BoundaryType.periodic
                               for b in box.boundaries)
            nbr = make_nbr(size_window((4.0 * h_to + 2.0 * skin) * 1.1,
                                       margin_cells=int(open_box)))
            if engine_fold(box, nbr):
                nbr = make_nbr(size_window(4.0 * h_max * 1.1))
            elif mesh is not None:
                # per slab, and the halo of the inflated windows with it
                from sphexa_tpu.parallel import sizing
                from sphexa_tpu.sfc.keys import compute_sfc_keys

                keys_d, _, gbox = sizing_cache or (
                    compute_sfc_keys(state.x, state.y, state.z, box,
                                     curve=curve), None, box)
                halo_cells, halo_runs, slot_cap, slots_cap = (
                    sizing.device_list_caps(
                        state.x, state.y, state.z,
                        state.h * _jnp.float32(h_relax), keys_d, gbox, nbr,
                        skin, mesh, halo_margin=halo_margin,
                        slot_margin=list_slot_margin))
            else:
                # reuse the native sizing pass's keys/order (a second
                # device keygen+argsort at 1M costs tens of ms per
                # reconfigure for nothing)
                skeys = _jnp.asarray(keys[order])
                slot_cap, slots_cap = estimate_list_caps(
                    _jnp.asarray(xa[order]), _jnp.asarray(ya[order]),
                    _jnp.asarray(za[order]),
                    _jnp.asarray(h[order] * np.float32(h_relax)),
                    skeys, box, nbr, skin, margin=list_slot_margin,
                )
    return PropagatorConfig(
        const=const, nbr=nbr, curve=curve, block=block, av_clean=av_clean,
        keep_accels=keep_accels, backend=backend,
        list_slot_cap=slot_cap, list_slots_cap=slots_cap,
        halo_cells=halo_cells, halo_runs=halo_runs,
        list_skin_rel=list_skin_rel, obs=obs_spec,
        snap=snap_spec,
        dt_bins=dt_bins, bin_sync_every=bin_sync_every,
        bin_resort_drift=bin_resort_drift,
    )


#: how many of the particles of largest ``h`` hull_h_relax counts around
HULL_TOP = 32


def hull_h_relax(state: ParticleState, box: Box, ng0: int) -> float:
    """Where the largest smoothing lengths of an open box are heading,
    over ``h`` as it stands (>= 1): counted on the host before any step.

    A cloud in an open box has a hull whose particles find half their
    neighbours (fewer at an edge), and ``update_h`` stops moving at
    ``h * cbrt(ng0 / nc)`` (kernels.h_fixed_point). Lists sized for the
    IC's ``h`` are re-sized after the first verified step shows the
    growth (``Simulation._lists_cover_h``): a second set of step and
    rebuild programs in every start-up. So the ``HULL_TOP`` particles of
    largest ``h`` (ties: farthest from the centroid, the hull of a cloud
    of one ``h``) have their neighbours inside ``2 h`` counted here,
    O(HULL_TOP x N) on the host arrays, and the FIRST sizing starts from
    the largest fixed point among them. Called once, by the constructor:
    from the first verified step on the step's own ``nc`` says it better
    (``_lists_cover_h``, which also remains the net under this estimate
    of which particles matter). A periodic box has no hull: 1."""
    if any(b == BoundaryType.periodic for b in box.boundaries):
        return 1.0
    xyz = np.stack([np.asarray(a) for a in (state.x, state.y, state.z)],
                   axis=1)
    h = np.asarray(state.h)
    away = np.sum((xyz - xyz.mean(axis=0)) ** 2, axis=1)
    h_to = 0.0
    for i in np.lexsort((away, h))[-HULL_TOP:]:
        near = np.abs(xyz[:, 0] - xyz[i, 0]) < 2.0 * h[i]
        d2 = np.sum((xyz[near] - xyz[i]) ** 2, axis=1)
        nc = np.count_nonzero(d2 < 4.0 * h[i] ** 2)  # itself included
        h_to = max(h_to, float(h[i]) * np.cbrt(ng0 / nc))
    return max(1.0, h_to / float(h.max()))


def _dealias_leaves(tree):
    """Copy pytree leaves that are the SAME array object as an earlier
    leaf, so the whole tree is donatable (XLA: `f(donate(a), donate(a))`
    is an error)."""
    seen = set()

    def fix(a):
        if not hasattr(a, "ndim"):
            return a
        if id(a) in seen:
            return jnp.copy(a)
        seen.add(id(a))
        return a

    return jax.tree.map(fix, tree)


#: tuned knobs the configure paths forward wholesale (rather than
#: resolving through ``_knob`` in the constructor): neighbor-engine
#: shape into make_propagator_config, gravity-solver shape into the
#: gravity_tuning override
_NBR_FORWARDED = ("cell_target", "run_cap", "gap", "group")
_GRAV_FORWARDED = ("target_block", "blocks_per_chunk", "super_factor")

#: every knob name the Simulation constructor actually consumes — the
#: ``_knob``-resolved set plus the forwarded groups above. This is the
#: LIVE consumption surface ``tuning.knobs.validate_off_sentinels``
#: cross-checks the off-sentinel declarations against: rename a
#: resolution site without updating this tuple (or vice versa) and the
#: registry validation fails at import, instead of JXA402's inertness
#: probe passing vacuously because ``tuned={name: ...}`` stopped
#: reaching the lowering.
CONSUMED_KNOBS = (
    "block", "list_skin_rel", "m2p_cap_margin", "check_every",
    "grav_window", "grav_window_margin", "dt_bins", "bin_sync_every",
    "bin_resort_drift", "donate",
) + _NBR_FORWARDED + _GRAV_FORWARDED


def _under_construct_span(init):
    """``Simulation.__init__`` whole under the host span
    ``sphexa:construct``: opened without a handle, it reports to the
    registry the constructor names current, after the initial
    ``sphexa:reconfigure`` (and its sizing passes) closed inside it."""

    @functools.wraps(init)
    def construct(self, *args, **kwargs):
        with span("sphexa:construct"):
            init(self, *args, **kwargs)

    return construct


class Simulation:
    """Owns state + static configs; reconfigures (recompiles) only when the
    cell grid no longer covers the interaction radius or a cell overflows
    its candidate cap."""

    #: leaf bucket size of the gravity tree
    grav_bucket = 64

    @_under_construct_span
    def __init__(
        self,
        state: ParticleState,
        box: Box,
        const: SimConstants,
        prop: str = "std",
        ngmax: Optional[int] = None,
        block: Optional[int] = None,
        curve: str = "hilbert",
        av_clean: bool = False,
        theta: float = 0.5,
        keep_accels: bool = False,
        backend: str = "auto",
        turb_cfg=None,
        turb_state=None,
        turb_settings: Optional[Dict] = None,
        cooling_cfg=None,
        chem=None,
        check_every: Optional[int] = None,
        num_devices: Optional[int] = None,
        use_lists: bool = True,
        list_skin_rel: Optional[float] = None,
        halo_mode: str = "sparse",
        grav_window: Optional[int] = None,
        grav_window_margin: Optional[float] = None,
        m2p_cap_margin: Optional[float] = None,
        donate: object = "auto",
        debug_checks: bool = False,
        telemetry: Optional[Telemetry] = None,
        imbalance_ratio: float = 1.5,
        obs_spec=None,
        snap_spec=None,
        snap_every: Optional[int] = None,
        snap_keep: Optional[int] = None,
        snap_dir: Optional[str] = None,
        drift_budget: Optional[float] = None,
        science_rows: bool = False,
        tuned: object = None,
        workload: Optional[str] = None,
        dt_bins: Optional[int] = None,
        bin_sync_every: Optional[int] = None,
        bin_resort_drift: Optional[float] = None,
    ):
        # telemetry registry: every driver-visible control-flow event
        # (reconfigure/rollback/replay/retrace) and step timing reports
        # here. A sink-less default keeps counters for free; pass a
        # Telemetry with sinks (app --telemetry-dir) to persist them.
        # Hot-loop contract: the instrumentation below is host-only —
        # spans (perf_counter stamps), Counter bumps, jit-cache-size
        # reads — and must NEVER add a device->host transfer to the
        # deferred happy path (pinned by tests/test_telemetry.py's
        # no-sync guard).
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        # the dump's spans (analysis/compare.py, io/snapshot.py) are
        # opened without a handle: they report to the latest Simulation's
        set_current(self.telemetry)
        self._window_t0 = None  # host stamp of the open window's 1st launch
        # tuned knob resolution (sphexa_tpu/tuning): precedence is
        # explicit kwarg > table entry > gravity_tuning/default heuristic,
        # resolved ONCE here and applied through the normal configure
        # paths below. The tuning-covered constructor params default to
        # None so explicitness is detectable; ``tuned`` is None / "auto" /
        # a table path / a knob dict (the sweep's candidate path) and
        # ``workload`` keys the table lookup (the init case name).
        explicit_knobs = {
            k: v for k, v in (("block", block),
                              ("list_skin_rel", list_skin_rel),
                              ("m2p_cap_margin", m2p_cap_margin),
                              ("check_every", check_every),
                              ("grav_window", grav_window),
                              ("grav_window_margin", grav_window_margin),
                              ("dt_bins", dt_bins),
                              ("bin_sync_every", bin_sync_every),
                              ("bin_resort_drift", bin_resort_drift),
                              # "auto" is donate's unset marker (the
                              # param predates the knob registry and
                              # keeps its legacy default)
                              ("donate", None if donate == "auto"
                               else donate))
            if v is not None
        }
        from sphexa_tpu.tuning.table import resolve_knobs

        tuned_knobs, self.tuning_provenance = resolve_knobs(
            tuned, workload=workload, n=state.n, p=num_devices or 1,
            backend=resolve_backend(backend), explicit=explicit_knobs,
        )

        def _knob(name, default):
            return explicit_knobs.get(name, tuned_knobs.get(name, default))

        block = _knob("block", 2048)
        list_skin_rel = _knob("list_skin_rel", 0.2)
        m2p_cap_margin = _knob("m2p_cap_margin", 1.3)
        check_every = _knob("check_every", 1)
        donate = _knob("donate", "auto")
        # MAC-sized sparse gravity near field (parallel/sizing.
        # device_gravity_halo): grav_window is the per-distance cap
        # padding quantum in rows (caps cache across retries at its
        # multiples); 0 = ship full peer slabs (the pre-sizing behavior,
        # byte-identical lowering). grav_window_margin pads the measured
        # MAC need and is GROWN 1.5x per escape-sentinel trip, with full
        # slabs as the retry ceiling.
        self.grav_window = int(_knob("grav_window", 256))
        if self.grav_window < 0:
            raise ValueError(
                f"grav_window must be >= 0, got {self.grav_window}")
        self._grav_halo_margin = float(_knob("grav_window_margin", 1.4))
        # hierarchical block time steps (sph/blockdt.py): dt_bins=None is
        # today's global-dt path, bitwise unchanged; dt_bins=1 runs the
        # blockdt machinery pinned bitwise-equal to it (tests/
        # test_blockdt.py); dt_bins>1 activates per-particle Δt bins
        dt_bins = _knob("dt_bins", None)
        bin_sync_every = int(_knob("bin_sync_every", 1))
        bin_resort_drift = float(_knob("bin_resort_drift", 0.0))
        self._blockdt = dt_bins is not None
        if self._blockdt:
            if prop not in _PROPAGATORS_BLOCKDT:
                raise ValueError(
                    f"dt_bins (hierarchical block time steps) supports "
                    f"the std/ve propagators, not prop={prop!r}"
                )
            dt_bins = int(dt_bins)
            if dt_bins < 1:
                raise ValueError(f"dt_bins must be >= 1, got {dt_bins}")
            if bin_sync_every < 1:
                raise ValueError(
                    f"bin_sync_every must be >= 1, got {bin_sync_every}")
            if bin_resort_drift < 0.0:
                raise ValueError(
                    f"bin_resort_drift must be >= 0, got {bin_resort_drift}")
        self.dt_bins = dt_bins
        self.bin_sync_every = bin_sync_every
        self.bin_resort_drift = bin_resort_drift
        # host-side block-dt accounting across fetch boundaries — the
        # chip-free complexity proxy (docs/NEXT.md): particle updates
        # actually performed vs what global-dt would have performed over
        # the same substeps (each substep advances dt_min either way)
        self.bdt_updates = 0
        self.bdt_updates_full = 0
        self.bdt_resorts = 0
        self.bdt_keeps = 0
        # reconfigure-cost knobs the configure paths consume each time
        self._nbr_knobs = {k: tuned_knobs[k]
                           for k in _NBR_FORWARDED if k in tuned_knobs}
        self._grav_knobs = {k: tuned_knobs[k]
                            for k in _GRAV_FORWARDED if k in tuned_knobs}
        if tuned is not None:
            # the decision is itself telemetry: which knobs are active
            # and WHY (table entry key + its provenance, or the
            # heuristic fallthrough on a coverage miss)
            self.telemetry.event("tuning", workload=workload,
                                 **self.tuning_provenance)
        # distributed observability (schema v2): the imbalance watchdog
        # fires a first-class event when max/mean of a per-shard metric
        # (pair work, halo rows, halo occupancy) crosses this ratio —
        # the runtime mirror of the retrace watchdog, for the quantity
        # the tree-code lineage says scaling lives on (Warren-Salmon
        # per-processor work accounting, PAPERS.md)
        self._imbalance_ratio = float(imbalance_ratio)
        # static shape of the active halo exchange (mode + shipped rows),
        # stamped by _configure_sharded for the exchange events
        self._halo_info: Optional[Dict] = None
        # gravity-stage analog (schema-v7 stage="gravity" events): the
        # MAC-sized sparse near-field caps + volume, or the full-slab
        # fallback's shape; None when no explicit gravity exchange runs
        self._grav_halo_info: Optional[Dict] = None
        self._grav_cells: Tuple[int, ...] = ()
        self._mem_post_compile = False  # one "post-compile" HBM snapshot
        # physics observability (schema v3): the in-graph science ledger
        # (propagator OBS/NUM_DIAG_KEYS) is fetched with the step
        # diagnostics at the existing check/flush boundaries and emitted
        # as physics/numerics events. Two watchdogs mirror the imbalance
        # one: conservation drift (|etot - etot0| / |etot0| past
        # ``drift_budget``; None = report-only) and field health (any
        # nonfinite rho/h/du — the pointer to --debug-checks for
        # localization).
        self._obs_spec = obs_spec
        self._drift_budget = (None if drift_budget is None
                              else float(drift_budget))
        self._etot0: Optional[float] = None
        #: |Δetot|/|etot0| at the last fetch boundary (bench stamps it)
        self.energy_drift: Optional[float] = None
        # per-step science rows (constants.txt material) accumulated at
        # verified boundaries for drain_science(); opt-in so library
        # drivers that never drain don't grow an unbounded list
        self._collect_science = bool(science_rows)
        self._science: list = []
        #: energy the cooling source has given the gas over every
        #: VERIFIED step so far (negative: radiated), in etot's units;
        #: etot - e_cool is what a std-cooling run conserves. None where
        #: no step carries the source (schema v16, _cooling_energy)
        self.e_cool: Optional[float] = None
        # the last verified step's (e_cool_rate, dt): the lagged half of
        # the integrator's Adams-Bashforth energy update
        self._e_cool_last = (0.0, float(state.min_dt))
        # live science surface (schema v8, observables/snapshot.py): the
        # in-graph field-grid deposit rides the diagnostics dict and is
        # fetched at the SAME check/flush boundaries — zero added host
        # syncs under deferral (pinned by the no-sync guard). Frames go
        # to a sidecar ``snapshots/`` ring of .npz files (capped by
        # snap_keep); (it, path) pairs accumulate for drain_snapshots()
        # (the --insitu consumer).
        self._snap_spec = snap_spec
        self._snap_every = max(1, int(snap_every)) if snap_every else 1
        self._snap_keep = int(snap_keep) if snap_keep else 0
        self._snap_dir = snap_dir
        if snap_spec is not None and snap_dir is None and telemetry is not None:
            # default the ring next to events.jsonl (the JsonlSink's dir)
            for sink in getattr(telemetry, "sinks", ()) or ():
                p = getattr(sink, "path", None)
                if p:
                    self._snap_dir = os.path.join(
                        os.path.dirname(str(p)) or ".", "snapshots")
                    break
        self._snap_frames: list = []   # (iteration, path) for drain
        self._snap_ring: list = []     # paths live in the ring, oldest first
        self.state = state
        self.box = box
        self.const = const
        self.prop_name = prop
        self.block = block
        self.curve = curve
        self.av_clean = av_clean
        self.keep_accels = keep_accels
        self.backend = backend
        self.ngmax = ngmax or const.ngmax
        self.theta = theta
        self.m2p_cap_margin = m2p_cap_margin
        # multi-chip: shard the state over a device mesh and drive the
        # sharded step (parallel/mesh.py) through the SAME loop —
        # reconfiguration re-sizes the per-peer halo window exactly like
        # the neighbor caps (the sphexa.cpp main loop never special-cases
        # rank count either)
        self._mesh = None
        self._halo_margin = 1.4
        # sparse: cell-granular per-distance halo buffers (the measured
        # fix for the degenerate contiguous windows, docs/NEXT.md);
        # windowed: contiguous per-peer row windows (kept for equivalence
        # tests and as a fallback)
        if halo_mode not in ("sparse", "windowed"):
            raise ValueError(f"halo_mode must be sparse|windowed, got "
                             f"{halo_mode!r}")
        self._halo_mode = halo_mode
        # buffer donation (propagator step_*_donated): the deferred
        # happy-path windows launch the donated twins so XLA aliases the
        # step output into the input state buffers (no double-buffering
        # of the dominant allocation). "auto" engages on TPU only — CPU
        # honors donation too, but tier-1 discard-and-replay semantics
        # are pinned to the undonated path there; donate=True opts in
        # anywhere (the rollback pin becomes a copy, see step()).
        if donate not in ("auto", True, False):
            raise ValueError(f"donate must be 'auto'|True|False, got "
                             f"{donate!r}")
        self._donate_active = donate is True or (
            donate == "auto" and on_tpu()
        )
        # runtime sanitizer (--debug-checks): the step runs under
        # jax.experimental.checkify with NaN/Inf + out-of-bounds-index
        # checks; the first triggered check is surfaced through the step
        # diagnostics as ``check_error``. Synchronous checking only (the
        # sanitizer exists to LOCALIZE failures, deferral would smear
        # them across a window), lists/donation fast paths disabled.
        self.debug_checks = bool(debug_checks)
        self._check_err = None
        self._checked_cache: Dict = {}
        if self.debug_checks:
            if num_devices is not None and num_devices > 1:
                raise ValueError(
                    "debug_checks is single-device (wrap the sharded "
                    "stepper is future work); drop num_devices or the flag"
                )
            check_every = 1
            use_lists = False
            self._donate_active = False
        if num_devices is not None and num_devices > 1:
            from sphexa_tpu.parallel import make_mesh, shard_state

            if state.n % num_devices:
                raise ValueError(
                    f"particle count {state.n} not divisible by "
                    f"{num_devices} devices; pad the state first"
                )
            self._mesh = make_mesh(num_devices)
            self.state = shard_state(state, self._mesh)
            # donation is wired on the single-device launch paths only;
            # the sharded stepper (make_sharded_step) owns its own jit
            self._donate_active = False
        if self._donate_active:
            # take ownership: donated launches consume state buffers in
            # place, and the INITIAL state belongs to the caller (tests
            # and restart flows reuse it) — one construction-time copy
            # keeps the caller's arrays alive
            self.state = jax.tree.map(
                lambda a: jnp.copy(a) if hasattr(a, "ndim") else a,
                self.state,
            )
        # block-dt carry: per-particle bins + cycle scalars, built AFTER
        # sharding so the (n,) leaves come from the placed state; never
        # donated (the blockdt donated twins consume the ParticleState
        # only), so window rollback pins it by reference
        self._bstate = (make_blockdt_state(self.state, dt_bins)
                        if self._blockdt else None)
        if prop == "nbody" and const.g == 0.0:
            raise ValueError(
                "prop='nbody' needs a gravitational constant: set SimConstants(g=...)"
            )
        self.gravity_on = const.g != 0.0
        any_periodic = any(b == BoundaryType.periodic for b in box.boundaries)
        all_periodic = all(b == BoundaryType.periodic for b in box.boundaries)
        self.ewald_on = self.gravity_on and all_periodic
        if self.gravity_on and any_periodic and not all_periodic:
            raise NotImplementedError(
                "self-gravity supports fully periodic (Ewald) or fully "
                "open boundaries, not mixed ones (same restriction as the "
                "reference's computeGravityEwald)"
            )
        if self.ewald_on:
            lx = np.asarray(box.lengths)
            if not np.allclose(lx, lx[0]):
                raise ValueError(
                    "Ewald gravity requires a cubic periodic box "
                    "(traversal_ewald_cpu.hpp:366)"
                )
        # turbulence stirring state (turb-ve propagator): built from the
        # case settings unless an explicit (cfg, state) pair is given,
        # e.g. restored from a checkpoint
        self.turb_cfg = turb_cfg
        self.turb_state = turb_state
        if prop == "turb-ve" and self.turb_cfg is None:
            from sphexa_tpu.init.turbulence import turbulence_constants
            from sphexa_tpu.sph.hydro_turb import create_stirring_modes

            s = dict(turbulence_constants(), **(turb_settings or {}))
            self.turb_cfg, fresh_state = create_stirring_modes(
                lbox=float(np.max(np.asarray(box.lengths))),
                st_max_modes=int(s["stMaxModes"]),
                energy_prefac=s["stEnergyPrefac"],
                mach_velocity=s["stMachVelocity"],
                sol_weight=s["solWeight"],
                spect_form=int(s["stSpectForm"]),
                seed=int(s["rngSeed"]),
                power_law_exp=float(s.get("powerLawExp", 5.0 / 3.0)),
                angles_exp=float(s.get("anglesExp", 2.0)),
            )
            # a caller-provided state (checkpoint restore) overrides the
            # fresh OU phases but keeps the derived static config
            if self.turb_state is None:
                self.turb_state = fresh_state
        # radiative cooling (std-cooling propagator): the evolved
        # six-species network with the metal residual, upstream's
        # HydroGrackleProp role; the CIE table with pass-through
        # fractions is CoolingConfig(evolve_species=False), handed in
        self.cooling_cfg = cooling_cfg
        self.chem = chem
        if prop == "std-cooling":
            from sphexa_tpu.physics.cooling import ChemistryData, CoolingConfig

            if self.cooling_cfg is None:
                self.cooling_cfg = CoolingConfig(gamma=const.gamma,
                                                 evolve_species=True)
            if self.chem is None:
                self.chem = ChemistryData.ionized(state.n)
            if self._mesh is not None:
                from sphexa_tpu.parallel import shard_state

                # per-particle chemistry rides the slab sharding like the
                # state (std_hydro_grackle.hpp runs under the full domain)
                self.chem = shard_state(self.chem, self._mesh)
        # persistent neighbor lists (sph/pair_lists.py): steady steps skip
        # the global sort + prologue and lane-compact the momentum ops;
        # enabled on the pallas path, on one device and on a mesh (each
        # slab's lists over own + halo rows, _lists_eligible), with or
        # without self-gravity (the tree solve sorts a copy of its five
        # inputs, propagator._add_gravity). The eligibility re-derives at
        # every _configure (fold mode depends on the sized grid).
        self._want_lists = use_lists
        self._list_skin_rel = list_skin_rel
        self._lists = None
        # iteration at which the live (or last dropped) list was built;
        # None until the run's first build
        self._lists_built_it = None
        self._layout_age = 0
        # why the next launch finds no list: the run's first, a
        # reconfigure dropped it, or a build that raised is retried
        self._list_reason = "first"
        # how fast the verified steps use a list's skin, the check window
        # planned from it (whole windows until a trend is seen), and the
        # life that plan gave the live list when it was fresh
        self._trend = SkinTrend()
        self._plan = WindowPlan(None, None, max(1, check_every))
        self._list_cover = None
        self._slot_margin = 1.3
        # list mode sizes for the h its particles are relaxing to:
        # (max h at the last configure, not yet checked against a
        # verified step | None), the max h that configure sized for, and
        # the newest estimate of fixed point / h (_lists_cover_h)
        self._h_configured = None
        self._h_sized = None
        self._h_relax = 1.0
        self.iteration = 0
        # deferred cap-checking (check_every > 1): the happy path launches
        # steps without any device->host sync; diagnostics of the last
        # ``check_every`` steps are fetched in ONE batched transfer at the
        # check boundary. JAX arrays are immutable, so the rollback point
        # costs one pinned state: we keep the window-start pytree refs
        # alive and replay the window if a deferred check finds an
        # overflow.
        self.check_every = max(1, check_every)
        # executable signatures THIS run has launched (compile-watchdog
        # per-run baseline; see _launch_signature)
        self._launched_sigs: set = set()
        self._pending = []  # per-step diagnostics of the open window
        self._window_prior = None  # (SimState pin, iteration) at window start
        self._last_diag: Dict[str, float] = {"reconfigured": 0.0}
        self._cfg: Optional[PropagatorConfig] = None
        self._gtree = None
        if self._lists_eligible and resolve_backend(backend) == "pallas":
            # lists outlive many steps: the first sizing is for where the
            # hull's h is heading, before any step has shown it
            self._h_relax = hull_h_relax(self.state, self.box,
                                         self.const.ng0)
        self._configure(reason="initial")

    # -- static config management ------------------------------------------
    @property
    def _lists_eligible(self) -> bool:
        # blockdt steps run their own fold-key sort prologue and have no
        # frozen-order fast path — lists stay off under dt_bins. On a mesh
        # every step family walks lists (each slab's over its own + halo
        # rows, the send layout frozen with them; under self-gravity the
        # mesh's tree solve takes a key-sorted copy of its five inputs,
        # propagator._add_gravity); only the sparse halo mode freezes a
        # send layout, so the windowed mode keeps streaming
        return (
            self._want_lists
            and not (self._mesh is not None and self._halo_mode != "sparse")
            and self.prop_name != "nbody"
            and not self._blockdt
        )

    def _configure(self, min_cap: int = 0, grav_margin: float = 1.5,
                   reason: str = "reconfigure"):
        with self.telemetry.span("sphexa:reconfigure", reason=reason):
            self._configure_impl(min_cap, grav_margin)
        # a reconfigure used to be visible only as one dict entry
        # (``reconfigured``) on one step's diagnostics — as telemetry it
        # is a first-class event with the WHY attached; the expected
        # construction-time sizing stays out of the health counter
        if reason != "initial":
            self.telemetry.count("reconfigures")
        self.telemetry.event("reconfigure", it=self.iteration, reason=reason,
                             engine=self._engine_facts())

    @property
    def pair_lists(self):
        """The live persistent pair lists the next step would walk, or
        None: lists off, or dropped and not yet rebuilt."""
        return self._lists if self._use_lists else None

    @property
    def active_cfg(self) -> PropagatorConfig:
        """The config the launched step runs under: on a mesh the
        sharded stepper's (mesh, shard axis, sized halo caps), else the
        plain one. What a pass that re-runs step stages outside the step
        (the dump's derived-field recompute) must be given."""
        return self._stepper.cfg if self._mesh is not None else self._cfg

    def _engine_facts(self) -> Dict:
        """What this configure resolved, for the run record: the engine
        behind the step, whether its kernels are compiled or interpreted,
        donation, the list engine, the gravity solver shape. Read by
        chip_smoke.py so a run reports what ran, not what was assumed."""
        from sphexa_tpu.sph.pallas_pairs import pallas_interpret

        cfg = self._cfg
        g = cfg.gravity
        return {
            "backend": cfg.backend,
            "interpret": (pallas_interpret() if cfg.backend == "pallas"
                          else None),
            "donate": self._donate_active,
            "lists": self._use_lists,
            "gravity": None if g is None else {
                "compaction": g.compaction,
                "target_block": g.target_block,
                "super_factor": g.super_factor,
                "use_pallas": g.use_pallas,
            },
        }

    def _configure_impl(self, min_cap: int = 0, grav_margin: float = 1.5):
        self._lists = None  # any static re-size invalidates the lists
        self._trend = self._trend.rebuilt()  # the next launch builds one
        if self._lists_built_it is not None:
            self._list_reason = "reconfigure"
        if self._mesh is not None:
            # drain in-flight steps before dispatching the sizing jits:
            # those jits contain their own collectives, and on CPU meshes
            # two concurrently executing programs' collective channels can
            # collide (observed as an all-reduce rendezvous hang when a
            # mid-run reconfigure overlapped the previous step)
            jax.block_until_ready(jax.tree.leaves(self.state))
        # multi-device: every sizing statistic comes from jitted device
        # reductions (O(N/P) transfers, parallel/sizing.py); single-device
        # keeps the native C++ host sizing pass. Multi-device consumers
        # of device keys (sizing_stats, the gravity tree build/need
        # sizing, AND _configure_sharded's halo-need scan) share ONE
        # keygen+argsort over N computed here (the round-4 reviewer's
        # double-keygen finding — _configure_sharded used to redo the
        # pair). Keys are generated against the make_global_box fit so
        # the shared cache matches what the halo scan keyed on; open
        # dims only ever expand to the particle extrema, so on a
        # post-step state (box already refit by the step prologue) the
        # values coincide with self.box.
        sizing_cache = None
        if self.gravity_on or (self._mesh is not None
                               and self._halo_sizing_needed()):
            from sphexa_tpu.sfc.box import make_global_box
            from sphexa_tpu.sfc.keys import compute_sfc_keys

            gbox = make_global_box(
                self.state.x, self.state.y, self.state.z, self.box
            )
            keys_d = compute_sfc_keys(
                self.state.x, self.state.y, self.state.z, gbox,
                curve=self.curve,
            )
            sizing_cache = (keys_d, jnp.argsort(keys_d), gbox)
        with self.telemetry.span("sphexa:size-neighbors"):
            self._cfg = make_propagator_config(
                self.state, self.box, self.const,
                ngmax=self.ngmax, block=self.block, curve=self.curve,
                min_cap=min_cap,
                av_clean=self.av_clean, keep_accels=self.keep_accels,
                backend=self.backend,
                device_sizing=self._mesh is not None,
                use_lists=self._lists_eligible,
                list_skin_rel=self._list_skin_rel,
                list_slot_margin=self._slot_margin,
                h_relax=self._h_relax,
                sizing_cache=sizing_cache,
                mesh=self._mesh, halo_margin=self._halo_margin,
                obs_spec=self._obs_spec,
                snap_spec=self._snap_spec,
                dt_bins=self.dt_bins, bin_sync_every=self.bin_sync_every,
                bin_resort_drift=self.bin_resort_drift,
                # table-resolved neighbor-engine knobs (cell_target/
                # run_cap/gap/group); absent keys fall to the factory
                # defaults
                **self._nbr_knobs,
            )
        if self._use_lists:
            self._h_configured = float(jnp.max(self.state.h))
            self._h_sized = self._h_configured * self._h_relax
        if self.gravity_on:
            with self.telemetry.span("sphexa:size-gravity"):
                self._configure_gravity(grav_margin, keys_cache=sizing_cache)
        if self._mesh is not None:
            with self.telemetry.span("sphexa:size-halo"):
                self._configure_sharded(sizing_cache)

    def _halo_sizing_needed(self) -> bool:
        """Whether _configure_sharded will run the explicit halo-need
        scan (pallas fast path) — i.e. whether it consumes device keys
        and should share _configure_impl's keygen cache."""
        if self.prop_name == "nbody":
            return False
        return resolve_backend(self.backend) == "pallas"

    def _configure_sharded(self, sizing_cache=None):
        """(Re)build the sharded stepper: size the per-peer halo window
        from the current distribution (estimate_halo_window) and bind it
        into make_sharded_step. Called at every reconfiguration, so an
        escape-sentinel overflow grows the window via _halo_margin.
        ``sizing_cache``: _configure_impl's shared (keys, order, gbox)
        so the halo-need scan reuses the one keygen+argsort over N
        instead of redoing it (the round-4 double-keygen finding)."""
        from sphexa_tpu.parallel import make_sharded_step
        from sphexa_tpu.sfc.box import make_global_box

        wmax = 0
        hcells, hruns = (), 0
        if self._cfg.backend == "pallas" and self.prop_name != "nbody":
            # device-side discovery: the needs scan runs as jitted
            # reductions over the sharded arrays and only P-1 scalars
            # reach the host (parallel/sizing.py — the rank-local
            # assignment analog, assignment.hpp:84-122)
            from sphexa_tpu.parallel.sizing import (
                device_halo_window, device_sparse_halo,
            )
            from sphexa_tpu.sfc.keys import compute_sfc_keys

            s = self.state
            if sizing_cache is not None:
                keys, _, gbox = sizing_cache
            else:
                gbox = make_global_box(s.x, s.y, s.z, self.box)
                keys = compute_sfc_keys(s.x, s.y, s.z, gbox,
                                        curve=self.curve)
            if self._cfg.halo_cells:
                # sized with the pair lists, for their inflated windows
                hcells, hruns = self._cfg.halo_cells, self._cfg.halo_runs
            elif self._halo_mode == "sparse":
                hcells, hruns = device_sparse_halo(
                    s.x, s.y, s.z, s.h, keys, gbox, self._cfg.nbr,
                    P=self._mesh.size, margin=self._halo_margin,
                    mesh=self._mesh,
                )
            else:
                wmax = device_halo_window(
                    s.x, s.y, s.z, s.h, keys, gbox, self._cfg.nbr,
                    P=self._mesh.size, margin=self._halo_margin,
                )
        aux_cfg = None
        if self.prop_name == "turb-ve":
            aux_cfg = self.turb_cfg
        elif self.prop_name == "std-cooling":
            aux_cfg = self.cooling_cfg
        # static exchange shape for telemetry: shipped rows per serve is
        # a config-time constant (the measure_multichip.py size formula),
        # bytes/step = rows x per-propagator serve fields x 4B
        from sphexa_tpu.propagator import exchange_fields_per_step

        P = self._mesh.size
        S = self.state.n // P
        nf = exchange_fields_per_step(self.prop_name, self.av_clean)
        if hcells:
            shipped = int(sum(min(c, S) for c in hcells))
            self._halo_info = {"mode": "sparse", "caps": tuple(hcells),
                               "shipped_rows": shipped, "run_slots": hruns}
        elif self._cfg.backend == "pallas" and self.prop_name != "nbody":
            w = min(wmax, S) or S
            self._halo_info = {"mode": "windowed", "wmax": w,
                               "shipped_rows": (P - 1) * w}
        else:
            # GSPMD path: XLA owns the collectives, no explicit exchange
            self._halo_info = {"mode": "gspmd", "shipped_rows": 0}
        self._halo_info["bytes_per_step"] = (
            self._halo_info["shipped_rows"] * nf * 4)
        # gravity-stage exchange shape (schema-v7 stage="gravity"
        # events): the explicit near-field serve runs only on the pallas
        # fast path (the GSPMD/nbody fallback leaves collectives to XLA)
        self._grav_halo_info = None
        if (self.gravity_on and self._cfg.backend == "pallas"
                and self.prop_name != "nbody"):
            # the Ewald replica loop serves once per shell — the volume
            # accounting scales with the static shell count
            nshell = 1
            if self._cfg.ewald is not None:
                r = self._cfg.ewald.num_replica_shells
                nshell = (2 * r + 1) ** 3
            if self._grav_cells:
                caps = tuple(min(int(c), S) for c in self._grav_cells)
                shipped = int(sum(caps))
                g = self._cfg.gravity
                self._grav_halo_info = {
                    "mode": "sparse", "caps": caps, "shipped_rows": shipped,
                    "run_slots": g.p2p_run_cap or g.p2p_cap}
            else:
                self._grav_halo_info = {"mode": "windowed", "wmax": S,
                                        "shipped_rows": (P - 1) * S}
            # 5 served fields (x, y, z, m, h) x f32
            self._grav_halo_info["bytes_per_step"] = (
                self._grav_halo_info["shipped_rows"] * 5 * 4 * nshell)
        self._stepper = make_sharded_step(
            self._mesh, self._cfg, self._step_fn(),
            halo_window=wmax, halo_cells=hcells, halo_runs=hruns,
            grav_cells=self._grav_cells, aux_cfg=aux_cfg,
        )

    def _configure_gravity(self, margin: float, keys_cache=None):
        """(Re)build the gravity tree structure from the current particle
        distribution and size the interaction-list caps (the gravity analog
        of re-sizing the neighbor cell grid — reconfiguration granularity
        only). The histogram-pyramid device build
        (sizing.leaf_array_from_device_keys — the update_mpi.hpp
        node-count allreduce transposed) plus device-side sort/multipoles
        is the ONLY build path, single- and multi-device alike, so only
        O(#cells) histograms and O(tree) arrays ever reach the host; the
        host-numpy ``build_gravity_tree`` survives purely as the test
        oracle the pyramid is pinned equal to. ``keys_cache`` carries
        _configure's (keys, order) so keygen+argsort over N runs once per
        reconfigure, not once per consumer."""
        s = self.state
        from sphexa_tpu.gravity.tree import linkage_from_leaves
        from sphexa_tpu.parallel.sizing import leaf_array_from_device_keys
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        # the box the keys were made in: in list mode self.box is the
        # lists' (regrown at a rebuild, not by the steps), and the caps
        # are sized in the box the step's solve will regrow
        gbox = self.box
        if keys_cache is not None:
            keys_d, order, gbox = keys_cache
        else:
            keys_d = compute_sfc_keys(s.x, s.y, s.z, self.box,
                                      curve=self.curve)
            order = jnp.argsort(keys_d)
        leaf_tree = leaf_array_from_device_keys(
            keys_d, bucket_size=self.grav_bucket
        )
        gtree, meta = linkage_from_leaves(leaf_tree, curve=self.curve)
        skeys = keys_d[order]
        xs, ys, zs, ms = s.x[order], s.y[order], s.z[order], s.m[order]
        # scale-dependent solver shape (target_block / hierarchical
        # bitmask compaction at >= 500k, gravity_tuning)
        from sphexa_tpu.gravity.traversal import gravity_tuning

        shape = gravity_tuning(self.state.n,
                               self._cfg.backend == "pallas",
                               telemetry=self.telemetry)
        if self._grav_knobs:
            shape.update(self._grav_knobs)
            if "super_factor" in self._grav_knobs:
                # keep the heuristic's invariant under overrides: the
                # two-level classification exists only as the pallas
                # bitmask compaction; sf=0 means the flat sort path
                shape["compaction"] = (
                    "bitmask" if shape["super_factor"] > 0
                    and shape["use_pallas"] else "sort")
        gcfg = estimate_gravity_caps(
            xs, ys, zs, ms, skeys, gbox, gtree, meta,
            GravityConfig(theta=self.theta, bucket_size=self.grav_bucket,
                          G=self.const.g,
                          m2p_cap_margin=self.m2p_cap_margin,
                          **shape),
            margin=margin,
            # sharded solves classify against the per-shard essential
            # node set (LET analog) instead of the full replicated tree
            let_shards=self._mesh.size if self._mesh is not None else 0,
            run_margin=self._grav_halo_margin,
        )
        self._gtree = gtree
        ewald = None
        if self.ewald_on:
            from sphexa_tpu.gravity.ewald import EwaldConfig

            ewald = EwaldConfig()
        # MAC-need sizing of the sparse gravity near-field exchange
        # (parallel/sizing.device_gravity_halo — the Warren-Salmon
        # essential-set volume): per-distance row caps for the leaf-
        # granular serve inside compute_gravity's shard path. Skipped at
        # grav_window=0 (full peer slabs, the pre-sizing lowering) and
        # on the GSPMD fallback, where no explicit serve runs.
        self._grav_cells = ()
        if (self._mesh is not None and self._mesh.size > 1
                and self.grav_window > 0 and self._halo_sizing_needed()):
            from itertools import product

            from sphexa_tpu.parallel.sizing import device_gravity_halo

            shifts = None
            if ewald is not None:
                # union the opened set over the replica-shell offsets:
                # a shifted target slab reaches wrap-around leaves the
                # base pass never opens
                r = ewald.num_replica_shells
                shells = np.array(
                    [sh for sh in product(range(-r, r + 1), repeat=3)],
                    np.float32,
                )
                shifts = jnp.asarray(shells) * self.box.lengths[0]
            self._grav_cells = device_gravity_halo(
                xs, ys, zs, ms, skeys, self.box, gtree, meta,
                theta=self.theta, P=self._mesh.size, shifts=shifts,
                margin=self._grav_halo_margin, quantum=self.grav_window,
            )
        if (self._mesh is not None and self._mesh.size > 1
                and ewald is None and self._halo_sizing_needed()):
            # the steps run this solve under shard_map (make_sharded_step's
            # fast path): the config says where, so that a solve OUTSIDE
            # the step (a check of the live state against a reference) is
            # the same program over the same mesh with the same serve
            gcfg = dataclasses.replace(gcfg, on_mesh=(
                self._mesh, self._mesh.axis_names[0], self._grav_cells))
        self._cfg = dataclasses.replace(
            self._cfg, gravity=gcfg, grav_meta=meta, ewald=ewald
        )

    def _gravity_overflowed(self, diagnostics) -> bool:
        # with full-slab windows (grav_cells empty) the near field's
        # escape sentinel cannot fire — the run splitter sizes its slots
        # from the mesh (exchange._split_runs extra=max(8, P-1)) and
        # every remote row is in reach — so any p2p_max > p2p_cap is a
        # REAL interaction-list overflow and cap regrowth is the right
        # recovery. Under the MAC-sized sparse serve the sentinel CAN
        # fire (encoded as p2p_cap + 1, see _grav_window_blown): the
        # recovery is then a halo-margin regrowth, not a cap ratchet.
        if not self.gravity_on:
            return False
        g = self._cfg.gravity
        return (
            int(diagnostics["m2p_max"]) > g.m2p_cap
            or int(diagnostics["p2p_max"]) > g.p2p_cap
            or int(diagnostics["leaf_occ"]) > g.leaf_cap
            or int(diagnostics.get("c_max", 0)) > g.super_cap
            or (g.let_cap > 0
                and int(diagnostics.get("let_max", 0)) > g.let_cap)
        )

    def _cap_report(self, diagnostics) -> str:
        """'name high-water/cap' of every cap a step's diagnostics are held
        to: what the error of a run that could not be re-sized names."""
        pairs = [("occupancy", diagnostics["occupancy"], self._cfg.nbr.cap)]
        if self.gravity_on:
            g = self._cfg.gravity
            pairs += [("m2p", diagnostics["m2p_max"], g.m2p_cap),
                      ("p2p", diagnostics["p2p_max"], g.p2p_cap),
                      ("leaf", diagnostics["leaf_occ"], g.leaf_cap),
                      ("super", diagnostics.get("c_max", 0), g.super_cap),
                      ("let", diagnostics.get("let_max", 0), g.let_cap)]
        return ", ".join(f"{name} {int(v)}/{cap}" for name, v, cap in pairs)

    def _grav_window_blown(self, diagnostics) -> bool:
        """The MAC-sized gravity serve's escape sentinel: exactly
        p2p_cap + 1 while the sparse caps are active. Same cap+1
        ambiguity contract as the SPH window sentinel (occupancy ==
        nbr.cap + 1): a real overflow landing exactly on cap+1 is
        handled identically — the margin regrowth converges to full
        slabs, where need <= S guarantees the sentinel cannot fire and
        a persisting overflow is then re-attributed to the caps."""
        if not self.gravity_on or not self._grav_cells:
            return False
        return int(diagnostics["p2p_max"]) == self._cfg.gravity.p2p_cap + 1

    def _config_still_valid(self, diagnostics) -> bool:
        nbr = self._cfg.nbr
        if int(diagnostics["occupancy"]) > nbr.cap:
            return False
        if self.prop_name == "nbody":
            return True
        # h_max is part of the step diagnostics (one batched transfer)
        h_max = float(diagnostics["h_max"])
        cell_edge = float(np.min(np.asarray(self.box.lengths))) / (1 << nbr.level)
        return 2.0 * h_max <= cell_edge

    def _lists_cover_h(self, first, last) -> bool:
        """List mode, once after each configure: do the lists' window and
        slot budget still cover the h the particles are heading for?

        A configure sizes for the h it sees; the first verified step
        after it (``first``; ``last`` ends the same verified stretch) is
        the earliest the driver can know how far that h is from the
        fixed point of its update (kernels.h_fixed_point, on the h_max
        both fetches already carry). Past the sizing's 10 % radius slack
        the lists would outgrow their caps rebuild by rebuild — an IC
        whose rim has half its neighbours relaxes by 30 % — so the
        driver re-sizes now, once, for where h is going."""
        h0, self._h_configured = self._h_configured, None
        if h0 is None or not self._use_lists:
            return True
        from sphexa_tpu.sph.kernels import h_fixed_point

        h_to = h_fixed_point(h0, float(first["h_max"]))
        self._h_relax = max(1.0, h_to / float(last["h_max"]))
        return h_to <= 1.1 * self._h_sized

    @property
    def _use_lists(self) -> bool:
        # slot_cap == 0 also covers the fold-mode grids where lists are
        # structurally unavailable (make_propagator_config leaves it 0)
        return self._lists_eligible and self._cfg.list_slot_cap > 0

    def _observe_lists(self, verified) -> None:
        """Fold the ``list_slack`` and ``dt`` of steps just verified
        (oldest first) into the skin trend: values the fetch that
        verified them already brought."""
        for d in verified:
            self._trend = self._trend.observe(
                d.get("list_slack"), d.get("dt"),
                self.const.max_dt_increase)

    def _plan_lists(self) -> None:
        """At a verified boundary: plan the next check window from the
        skin the live list has left (SkinTrend.plan). A list predicted
        to serve no further step is rebuilt now, at the boundary,
        instead of expiring inside the next window and rolling it back;
        the window is then planned from the fresh list. One rule for
        ``check_every`` 1 (a horizon of one step) and N; inert where
        the steps run without lists."""
        if not self._use_lists:
            self._plan = WindowPlan(None, None, self.check_every)
            return
        self._plan = self._trend.plan(self.check_every)
        if self._plan.cover == 0 and self._trend.slack is not None:
            # (a fresh list that covers no step cannot be bettered)
            self._rebuild_lists("proactive", slack=self._trend.slack)
            self._plan = self._trend.plan(self.check_every)

    def _rebuild_lists(self, reason: str, slack: Optional[float] = None,
                       served_to: Optional[int] = None):
        """(Re)build the persistent lists: one jitted sort + mark pass.
        Replaces the per-step rebuild the reference does
        (find_neighbors.cuh) — between rebuilds the steady steps run on
        the frozen order. A slot-cap overflow re-sizes the static budget
        (recompile) and retries, like every other cap. On a mesh the
        program is the sharded stepper's (``stepper.rebuild``: the global
        sort, then every slab's halo stage, list build and frozen send
        layout in one ``shard_map``), its counts the fullest slab's
        (``slot_need``, ``slots_live``) or the slabs' sums, and a halo
        that escaped its caps there re-sizes like a list cap, under a
        grown halo margin.

        One ``rebuild_lists`` event per call that built a list, with the
        WHY: ``reason`` (first | proactive | expiry | rollback |
        reconfigure), ``age_steps`` (verified steps the outgoing list
        served, counted to ``served_to``, default the current
        iteration), ``slack`` (the fetched ``list_slack`` that triggered
        it, None where none did), ``slot_need``/``slot_cap`` (chunk slots
        of the fullest group against the per-group budget),
        ``slots_live``/``slots_cap`` (rows of the flat lane table in use
        against its budget: the occupancy), ``chunks_live``/``runs_live``/
        ``run_rows`` (kept chunks, runs and the rows a run's copy
        fetches: the share of a pass's fetched rows that a lane is taken
        from) and ``attempts``. All of it is host state or rides the
        overflow fetch the rebuild always made."""
        import jax as _jax

        from sphexa_tpu.propagator import rebuild_pair_lists
        from sphexa_tpu.sph.pallas_pairs import list_run_rows

        self._list_reason = reason  # a build that raises retries as itself
        if served_to is None:
            served_to = self.iteration
        age = (0 if self._lists_built_it is None
               else served_to - self._lists_built_it)
        for attempt in range(1, 4):
            if not self._use_lists:
                # a reconfigure flipped the grid into fold mode or left
                # list_slot_cap == 0: fall back to per-step streaming
                # (self._lists stays None; steps run with lists=None,
                # nothing was built and no event says otherwise)
                return
            aux = self.chem if self.prop_name == "std-cooling" else None
            # the outgoing list is dead weight from here on: let go of it
            # before the build allocates its successor (at 1.1M Noh a
            # list was 1.8-3.6 GiB with a dense lane table, and a
            # rollback's rebuild beside the old one and the pin ran a
            # 16 GB chip out of memory; the flat table is 1.3 GB)
            self._lists = None
            with self.telemetry.span("sphexa:rebuild-lists"):
                if self._mesh is not None:
                    state, box, lists, aux = self._drain(
                        self._stepper.rebuild(self.state, self.box, aux))
                else:
                    state, box, lists, aux = rebuild_pair_lists(
                        self.state, self.box, self._cfg, aux
                    )
                overflow, need, live, chunks, runs = (
                    int(v) for v in _jax.device_get(
                        (lists.overflow, lists.slot_need, lists.slots_live,
                         lists.chunks_live, lists.runs_live)))
            if not overflow:
                self.state, self.box, self._lists = state, box, lists
                if aux is not None:
                    self.chem = aux
                self._lists_built_it = self.iteration
                self._trend = self._trend.rebuilt()
                cover, self._list_cover = (
                    self._list_cover, self._trend.plan(self.check_every).cover)
                rate = self._plan.rate
                self.telemetry.event(
                    "rebuild_lists", it=self.iteration, reason=reason,
                    age_steps=age,
                    slack=None if slack is None else round(slack, 6),
                    slot_need=need, slot_cap=self._cfg.list_slot_cap,
                    slots_live=live, slots_cap=self._cfg.list_slots_cap,
                    chunks_live=chunks, runs_live=runs,
                    run_rows=list_run_rows(self._cfg.nbr),
                    attempts=attempt,
                    rate=None if rate is None else round(rate, 6),
                    cover_steps=cover,
                )
                return
            if overflow & 2:
                # a slab's halo escaped the caps sized for the inflated
                # windows: the mesh's sentinel, met at the rebuild
                self._halo_margin *= 1.5
                self.telemetry.count("halo_trips")
            if overflow & 1:
                self._slot_margin *= 1.5
            self._configure(reason="list-slot")
        raise RuntimeError("pair-list slot cap failed to converge")

    def _step_fn(self, donated: bool = False):
        """Active step builder for the configured mode: the blockdt twin
        when ``dt_bins`` is set, the plain propagator otherwise."""
        if self._blockdt:
            table = (_PROPAGATORS_BLOCKDT_DONATED if donated
                     else _PROPAGATORS_BLOCKDT)
        else:
            table = _PROPAGATORS_DONATED if donated else _PROPAGATORS
        return table[self.prop_name]

    # -- main loop ----------------------------------------------------------
    def _drain(self, out):
        """CPU-mesh collective serialization: a program's scalar outputs
        can materialize before its trailing collectives retire, and a
        second program entering the per-thread queues mid-flight deadlocks
        the all-reduce rendezvous (observed: evrard-cooling CLI hang).
        Real TPU meshes execute programs FIFO per core — no drain there."""
        if self._mesh is not None and device_info().platform == "cpu":
            jax.block_until_ready(
                [a for a in jax.tree.leaves(out) if hasattr(a, "block_until_ready")]
            )
        return out

    def _checkified_step(self):
        """jit(checkify(step)) with the static configs closed over —
        rebuilt whenever the active config changes (reconfigure), cached
        otherwise so steady debug steps reuse one executable."""
        from jax.experimental import checkify

        key = (self.prop_name, self._cfg, self.turb_cfg, self.cooling_cfg)
        if self._checked_cache.get("key") != key:
            step_fn = self._step_fn()
            cfg = self._cfg
            if self.prop_name == "turb-ve":
                aux_cfg = self.turb_cfg
                base = lambda s, b, g, aux: step_fn(s, b, cfg, g, aux,
                                                    aux_cfg)
            elif self.prop_name == "std-cooling":
                aux_cfg = self.cooling_cfg
                base = lambda s, b, g, aux: step_fn(s, b, cfg, g, aux,
                                                    aux_cfg)
            elif self._blockdt:
                # the BlockDtState rides the aux slot; 4-tuple return
                base = lambda s, b, g, aux: step_fn(s, b, cfg, g, aux)
            else:
                base = lambda s, b, g, aux: step_fn(s, b, cfg, g)
            errors = checkify.float_checks | checkify.index_checks
            self._checked_cache = {
                "key": key,
                "fn": jax.jit(checkify.checkify(base, errors=errors)),
            }
        return self._checked_cache["fn"]

    @property
    def _aux_slot(self) -> Optional[str]:
        """The SimState aux slot the active propagator family carries
        (None for the plain 3-tuple families) — the driver-level mirror
        of propagator.STEP_AUX_SLOT, keyed on the configured mode."""
        if self.prop_name == "turb-ve":
            return "turb"
        if self.prop_name == "std-cooling":
            return "chem"
        if self._blockdt:
            return "bdt"
        return None

    @property
    def sim_state(self) -> SimState:
        """The driver's state attributes as the unified carry pytree
        (state.SimState): what every launch path consumes and returns."""
        return SimState(particles=self.state, box=self.box,
                        turb=self.turb_state, chem=self.chem,
                        bdt=self._bstate)

    def _set_sim_state(self, sim: SimState) -> None:
        """Write a SimState carry back onto the driver attributes —
        the single commit point for step outputs AND window rollbacks."""
        self.state = sim.particles
        self.box = sim.box
        self.turb_state = sim.turb
        self.chem = sim.chem
        self._bstate = sim.bdt

    def _launch_debug(self):
        """Sanitizer-mode launch: run the checkified step and stash the
        checkify Error for _step_checked to surface."""
        sim = self.sim_state
        slot = self._aux_slot
        aux = getattr(sim, slot) if slot else None
        self._check_err, out = self._checkified_step()(
            sim.particles, sim.box, self._gtree, aux
        )
        if slot:
            new_state, new_box, diagnostics, new_aux = out
        else:
            (new_state, new_box, diagnostics), new_aux = out, None
        return sim.with_slot(slot, new_aux, particles=new_state,
                             box=new_box), diagnostics

    def _compiled_cache_size(self) -> int:
        """Total jit-cache entries behind the ACTIVE launch path — the
        compile-watchdog's probe (the runtime analog of jaxaudit JXA102's
        cache-size-delta check, tests/test_audit.py). Pure host-side
        metadata: safe on the sync-free deferred happy path."""
        if self.debug_checks:
            fns = [self._checked_cache.get("fn")]
        elif self._mesh is not None:
            fns = [getattr(self, "_stepper", None)]
        else:
            fns = [self._step_fn(), self._step_fn(donated=True)]
        total = 0
        for f in fns:
            size = getattr(f, "_cache_size", None)
            if size is not None:
                total += size()
        return total

    def _launch_signature(self, donate_now: bool):
        """Hashable identity of the executable THIS launch needs — the
        per-run half of the compile watchdog. The jit caches are
        process-global, so the cache-size delta alone under-counts when
        another Simulation in the same process already compiled the
        identical config (the suite-order coupling between
        test_simulation_async and the telemetry retrace pin): this run
        still *traces differently than its own previous launches*, and
        in any fresh process it would compile. Baselining per Simulation
        on the signature set makes the watchdog count THIS run's
        (re)traces under any suite order."""
        if self.debug_checks:
            return ("debug", self.prop_name, self._cfg, self.turb_cfg,
                    self.cooling_cfg)
        if self._mesh is not None:
            info = self._halo_info or {}
            ginfo = self._grav_halo_info or {}
            return ("sharded", self.prop_name, self._cfg,
                    info.get("caps"), info.get("wmax"),
                    info.get("run_slots"),
                    ginfo.get("caps"), ginfo.get("wmax"),
                    self._use_lists and self._lists is not None)
        return (self.prop_name, self._cfg, self.turb_cfg,
                self.cooling_cfg, donate_now,
                self._use_lists and self._lists is not None)

    def _launch(self, donate_ok: bool = False):
        """Instrumented dispatch: the compile watchdog samples the active
        jit cache around the launch — any growth means THIS launch traced
        (first compile or a silent retrace) and is recorded as a
        first-class ``retrace`` event instead of vanishing into an
        unexplained slow step. A launch whose executable signature this
        Simulation has never used counts too, even when the
        process-global cache was pre-warmed by another run (``warm``
        rides the event payload): the watchdog reports per-RUN compile
        behavior, independent of suite order."""
        c0 = self._compiled_cache_size()
        # debug_checks rebuilds the checkified jit INSIDE the launch on a
        # config change (new object, cache size resets to 1) — identity
        # drift is a from-scratch compile the size delta alone would miss
        fn0 = id(self._checked_cache.get("fn")) if self.debug_checks \
            else None
        donate_now = donate_ok and self._donate_active
        with self.telemetry.span("sphexa:launch", donated=donate_now) as sp:
            out = self._launch_impl(donate_ok)
            delta = self._compiled_cache_size() - c0
            if (self.debug_checks and delta <= 0
                    and id(self._checked_cache.get("fn")) != fn0):
                delta = 1
            sig = self._launch_signature(donate_now)
            warm = delta <= 0 and sig not in self._launched_sigs
            self._launched_sigs.add(sig)
            n = max(delta, 1) if delta > 0 or warm else 0
            sp["retrace"] = n
        if n:
            self.telemetry.count("retraces", n)
            self.telemetry.event("retrace", it=self.iteration, delta=n,
                                 warm=warm)
        return out

    def _launch_impl(self, donate_ok: bool = False):
        """Dispatch one jitted step on the current state (no host sync
        beyond the CPU-mesh drain). Returns the unified carry:
        ``(new SimState, diagnostics)`` on every launch path.

        ``donate_ok``: the caller guarantees it will never need the
        CURRENT input state again (deferred happy-path windows pin a
        rollback copy first) — with donation active, launch the donated
        twin so the state is updated in place."""
        if self.debug_checks:
            return self._launch_debug()
        lists = None
        if self._use_lists:
            if self._lists is None:
                self._rebuild_lists(self._list_reason)
            # (None where the rebuild found lists unavailable: stream)
            lists = self._lists
        if self._mesh is not None:
            # steps the send layout this launch ships over has served
            self._layout_age = (0 if lists is None
                                else self.iteration - self._lists_built_it)
            return self._drain(self._stepper.step_sim(
                self.sim_state, self._gtree, lists=lists))
        donate_now = donate_ok and self._donate_active
        if donate_now:
            # freshly-built states alias leaves (build_state shares one
            # zeros array across temp_lo/du/du_m1; restarts may too) and
            # XLA refuses to donate the same buffer twice — copy the
            # duplicates once (step outputs are always distinct, so this
            # only ever pays on the first donated launch of a state)
            self.state = _dealias_leaves(self.state)
        step_fn = self._step_fn(donated=donate_now)
        kw = {} if lists is None else {"lists": lists}
        aux_cfg = (self.turb_cfg if self.prop_name == "turb-ve"
                   else self.cooling_cfg if self.prop_name == "std-cooling"
                   else None)
        return step_sim_state(step_fn, self.sim_state, self._cfg,
                              self._gtree, aux_cfg, **kw)

    def _apply(self, out):
        sim, _diagnostics = out
        self._set_sim_state(sim)

    @staticmethod
    def _scalar_view(diagnostics) -> Dict:
        """Scalars + the tiny (P,) per-shard telemetry arrays
        (SHARD_DIAG_KEYS), (B,) bin populations (BLOCKDT_DIAG_KEYS) and
        the (F, G, G)-sized snapshot grids (SNAP_DIAG_KEYS) —
        everything the flush boundary fetches in one batch. Per-particle
        arrays (keep_accels) stay on device."""
        from sphexa_tpu.propagator import (
            BLOCKDT_DIAG_KEYS, GRAV_SHARD_DIAG_KEYS, SHARD_DIAG_KEYS,
            SNAP_DIAG_KEYS)

        return {
            k: v for k, v in diagnostics.items()
            if getattr(v, "ndim", 0) == 0 or k in SHARD_DIAG_KEYS
            or k in BLOCKDT_DIAG_KEYS or k in GRAV_SHARD_DIAG_KEYS
            or k in SNAP_DIAG_KEYS
        }

    @classmethod
    def _fetch_scalars(cls, diagnostics) -> Dict:
        """ONE batched device->host transfer for all scalar diagnostics
        (separate float()/int() conversions each pay a full round trip,
        which dominates on remote-attached TPUs)."""
        return jax.device_get(cls._scalar_view(diagnostics))

    def _overflowed(self, diagnostics) -> bool:
        return (
            int(diagnostics["occupancy"]) > self._cfg.nbr.cap
            or self._gravity_overflowed(diagnostics)
            or not self._lists_fresh(diagnostics)
        )

    @staticmethod
    def _list_fills(fetched) -> Dict[str, float]:
        """Payload of a verified window's (or checked step's) event where
        the steps solved gravity, averaged over the steps: ``cand_fill`` /
        ``m2p_fill`` / ``p2p_fill`` (schema v13), the tree solve's list
        occupancies (live slots ÷ lists x cap), and ``prepass_chunk_live``
        / ``compact_chunk_live`` (v17), the compaction kernel's live
        chunks ÷ the chunks its two walks visit
        (traversal.compute_gravity). ``fetched`` is already on the host:
        no transfer is added."""
        return {
            k: round(float(np.mean([float(d[k]) for d in fetched])), 6)
            for k in ("cand_fill", "m2p_fill", "p2p_fill",
                      "prepass_chunk_live", "compact_chunk_live")
            if all(k in d for d in fetched)
        }

    def _emit_distributed(self, diagnostics, steps: int) -> None:
        """Schema-v2 distributed telemetry at the fetch boundary: one
        ``shard_load`` + one ``exchange`` event per checked step / clean
        window, plus the imbalance watchdog. ``diagnostics`` is the
        already-FETCHED dict — everything here is host arithmetic on
        (P,) numpy arrays, so the deferred-window zero-sync contract is
        untouched (pinned by tests/test_telemetry.py)."""
        if self._mesh is None:
            return
        tel = self.telemetry
        P = self._mesh.size
        particles = [self.state.n // P] * P  # equal SFC slabs by design

        def arr(key):
            v = diagnostics.get(key)
            return None if v is None else np.asarray(v)

        work, rows, occ = arr("shard_work"), arr("shard_rows"), \
            arr("shard_occ")

        def run_fields(info, runs):
            # schema-v14: the run axis of a sparse exchange, the sized
            # slots beside the fullest group's live runs (fullest shard)
            if runs is None or not info.get("run_slots"):
                return {}
            return {"run_slots": int(info["run_slots"]),
                    "live_runs_max": int(runs.max())}

        # per-shard trips reaching this point are always zero — a tripped
        # sentinel folds into occupancy==cap+1 and the step/window is
        # discarded before any emit; halo_trips is counted at the ONE
        # place that sees the sentinel (_reconfigure_after_overflow)
        load = {"it": self.iteration, "steps": steps,
                "particles": particles, "stage": "sph"}
        if work is not None:
            load["work"] = [float(w) for w in work]
        tel.event("shard_load", **load)
        info = self._halo_info or {}
        if rows is not None:
            tel.event(
                "exchange", it=self.iteration, steps=steps,
                mode=info.get("mode", "?"),
                shipped_rows=int(info.get("shipped_rows", 0)),
                rows=[int(r) for r in rows],
                occ=None if occ is None else [round(float(o), 4)
                                              for o in occ],
                bytes_per_step=int(info.get("bytes_per_step", 0)),
                trips=int(tel.counters.get("halo_trips", 0)),
                stage="sph", **run_fields(info, arr("shard_runs")),
                # schema-v20: steps the layout the newest launch shipped
                # over had served: a list step's is frozen at its rebuild
                layout_age_steps=self._layout_age,
            )
        # schema-v7: the gravity near field gets its own exchange event
        # when the MAC-sized sparse serve is active (gshard_* diagnostics
        # present) — same fetch, zero added syncs
        grows, gocc = arr("gshard_rows"), arr("gshard_occ")
        ginfo = self._grav_halo_info or {}
        if grows is not None:
            tel.event(
                "exchange", it=self.iteration, steps=steps,
                mode=ginfo.get("mode", "?"),
                shipped_rows=int(ginfo.get("shipped_rows", 0)),
                rows=[int(r) for r in grows],
                occ=None if gocc is None else [round(float(o), 4)
                                               for o in gocc],
                bytes_per_step=int(ginfo.get("bytes_per_step", 0)),
                trips=int(tel.counters.get("grav_halo_trips", 0)),
                stage="gravity", **run_fields(ginfo, arr("gshard_runs")),
            )
        # schema-v19: the per-step global sort of a mesh step. Streamed
        # with an aux state (std-cooling's chemistry): GSPMD ships every
        # slab's rows to every device for that gather (``shipped_rows``,
        # per device), and ``migrant_rows`` of the sorted ``rows`` really
        # changed slab in the window's last step. On lists under
        # self-gravity: the sort of the tree solve's key-ordered copy,
        # and ``migrant_rows`` the rows of it that lie on another slab
        # than their frozen row (what the list's age did to the slabs'
        # key ranges)
        migrants = diagnostics.get("sort_migrant_rows")
        if migrants is not None:
            tel.event(
                "exchange", it=self.iteration, steps=steps, mode="gspmd",
                shipped_rows=(P - 1) * particles[0], rows=self.state.n,
                migrant_rows=int(migrants), stage="sort",
            )
        # the watchdog: max/mean per metric against the configured ratio
        for metric, a in (("work", work), ("halo_rows", rows),
                          ("halo_occ", occ)):
            if a is None or a.size == 0:
                continue
            mean = float(a.mean())
            if mean <= 0.0:
                continue
            ratio = float(a.max()) / mean
            if ratio >= self._imbalance_ratio:
                tel.count("imbalances")
                tel.event("imbalance", it=self.iteration, metric=metric,
                          ratio=round(ratio, 4),
                          threshold=self._imbalance_ratio)

    def _emit_memory(self, point: str) -> None:
        """Per-device HBM snapshot event (telemetry/memory.py): host
        allocator metadata only, never a device sync. ``post-compile``
        fires once (after the first fetched step/window — executable +
        workspace resident); ``flush`` at every window flush."""
        if point == "post-compile":
            if self._mem_post_compile:
                return
            self._mem_post_compile = True
        devices = None
        if self._mesh is not None:
            devices = list(self._mesh.devices.flat)
        emit_memory_event(self.telemetry, point, devices=devices,
                          it=self.iteration)

    def drain_science(self) -> list:
        """Per-step science rows (constants.txt material: it, t, dt,
        energies, momenta, the case extra) accumulated since the last
        drain — one dict per VERIFIED step, in iteration order, built
        from the already-fetched ledger scalars (no device access).
        Rows appear only at check/flush boundaries, so under deferral a
        whole window's rows land at once; rolled-back windows never
        produce rows (their replay does). Requires
        ``Simulation(science_rows=True)``."""
        rows, self._science = self._science, []
        return rows

    def _cooling_energy(self, d) -> Optional[float]:
        """The energy the cooling source gave the gas in one verified
        step, from the step's fetched ``e_cool_rate`` = sum(m du_cool)
        and ``dt``: host arithmetic. The integrator advances u by
        ``du (dt + a) - du_m1 a`` with ``a = dt^2 / (2 dt_m1)``
        (positions.energy_update), and du holds du_cool, so the source's
        share of the step is the same form of its own rates (the frozen
        rows of a fixed boundary and the exponential fallback of a
        negative u aside). Called once per verified step, in order: a
        rolled-back step never reaches it, so its share is dropped with
        the step. After a restart the lagged rate starts at zero (half a
        step's cooling, once)."""
        if "e_cool_rate" not in d:
            return None
        rate, dt = float(d["e_cool_rate"]), float(d["dt"])
        rate_m1, dt_m1 = self._e_cool_last
        a = 0.5 * dt * dt / dt_m1
        step = rate * (dt + a) - rate_m1 * a
        self._e_cool_last = (rate, dt)
        self.e_cool = (self.e_cool or 0.0) + step
        return step

    def _emit_science(self, fetched, its) -> None:
        """Schema-v3 physics observability at the fetch boundary: one
        ``physics`` + one ``numerics`` event per checked step / clean
        window (per-step parallel lists, like the v2 shard events), the
        science rows for drain_science(), and the two watchdogs.
        ``fetched`` holds the already-FETCHED per-step diagnostics —
        host arithmetic only, the deferred-window zero-sync contract is
        untouched (pinned by tests/test_telemetry.py)."""
        from sphexa_tpu.propagator import DT_LIMITERS

        steps = [(it, d) for it, d in zip(its, fetched)
                 if "obs_etot" in d]
        if not steps:
            return
        tel = self.telemetry
        rows = []
        for it, d in steps:
            row = {"it": int(it), "t": float(d["obs_ttot"]),
                   "dt": float(d["dt"]), "etot": float(d["obs_etot"]),
                   "ecin": float(d["obs_ecin"]),
                   "eint": float(d["obs_eint"]),
                   "egrav": float(d["obs_egrav"]),
                   "linmom": float(d["obs_linmom"]),
                   "angmom": float(d["obs_angmom"])}
            if "obs_extra" in d:
                row["extra"] = float(d["obs_extra"])
            e_step = self._cooling_energy(d)
            if e_step is not None:
                row["e_cool_step"], row["e_cool"] = e_step, self.e_cool
            rows.append(row)
        if self._collect_science:
            self._science.extend(rows)
        if self._etot0 is None and np.isfinite(rows[0]["etot"]):
            self._etot0 = rows[0]["etot"]
        payload = {k: [r[k] for r in rows]
                   for k in ("dt", "etot", "ecin", "eint", "egrav",
                             "linmom", "angmom")}
        # simulated time travels as t_sim: the envelope already owns "t"
        # (epoch seconds), and a payload key must never shadow it
        payload["t_sim"] = [r["t"] for r in rows]
        if all("extra" in r for r in rows):
            payload["extra"] = [r["extra"] for r in rows]
        tel.event("physics", it=rows[-1]["it"], steps=len(rows),
                  its=[r["it"] for r in rows], **payload)

        # numerics: limiter histogram + window-aggregate health scalars
        lim: Dict[str, int] = {}
        bad = {"rho": 0, "h": 0, "du": 0}
        first_bad = None
        for it, d in steps:
            if "dt_limiter" in d:
                name = DT_LIMITERS[int(d["dt_limiter"])]
                lim[name] = lim.get(name, 0) + 1
            step_bad = {f: int(d.get(f"n_bad_{f}", 0)) for f in bad}
            for f in bad:
                bad[f] = max(bad[f], step_bad[f])
            if first_bad is None and sum(step_bad.values()) > 0:
                first_bad = (it, step_bad)
        ds = [d for _, d in steps]

        def ext(key, fn):
            # aggregate over the window's FINITE samples only: Python
            # min/max NaN-propagation is order-dependent (a NaN would be
            # sticky or masked depending on which step produced it) —
            # corruption is reported by the nonfinite counts/field_health
            # event, the extrema stay deterministic
            arr = np.asarray([float(d.get(key, np.nan)) for d in ds])
            finite = arr[np.isfinite(arr)]
            return float(fn(finite)) if finite.size else float("nan")

        agg = {
            "nc_mean_min": ext("nc_mean", np.min),
            "nc_mean_max": ext("nc_mean", np.max),
            "nc_clip": max(int(d.get("n_nc_clip", 0)) for d in ds),
            "h_sat": max(int(d.get("n_h_sat", 0)) for d in ds),
            "rho_min": ext("rho_min", np.min),
            "rho_max": ext("rho_max", np.max),
            "h_min": ext("h_min", np.min),
            "h_max": ext("h_max", np.max),
            "du_max": ext("du_max", np.max),
        }
        # schema v15: the cooling step's limiter and source, where the
        # step carries them (std-cooling)
        for name, key in (("dt_cool_min", "dt_cool"),
                          ("du_cool_min", "du_cool_min")):
            if any(key in d for d in ds):
                agg[name] = ext(key, np.min)
        # schema v16: what the cooling source gave the gas (negative:
        # radiated), per verified step of the window and in all so far
        if all("e_cool" in r for r in rows):
            agg["e_cool"] = rows[-1]["e_cool"]
            agg["e_cool_step"] = [r["e_cool_step"] for r in rows]
        tel.event("numerics", it=rows[-1]["it"], steps=len(rows),
                  limiter=lim, nonfinite=bad, **agg)

        # conservation-drift watchdog: relative total-energy excursion
        # vs the run's first verified step, evaluated over EVERY step of
        # the window (a mid-window spike that relaxes by the flush must
        # still fire — the offline science --budget gate checks the full
        # series, the runtime watchdog must agree); energy_drift exposes
        # the latest verified value (the bench stamp)
        if self._etot0 is not None:
            denom = abs(self._etot0) or 1.0
            drifts = [abs(r["etot"] - self._etot0) / denom for r in rows]
            self.energy_drift = drifts[-1]
            worst = max(range(len(rows)), key=lambda i: (
                drifts[i] if np.isfinite(drifts[i]) else -1.0))
            if (self._drift_budget is not None
                    and drifts[worst] > self._drift_budget):
                tel.count("drifts")
                tel.event("drift", it=rows[worst]["it"],
                          drift=drifts[worst],
                          budget=self._drift_budget, etot0=self._etot0,
                          etot=rows[worst]["etot"])
        # field-health watchdog: any nonfinite rho/h/du is a first-class
        # event naming the first bad step; --debug-checks localizes it
        if first_bad is not None:
            it_bad, step_bad = first_bad
            tel.count("field_health")
            tel.event("field_health", it=it_bad,
                      nonfinite=sum(step_bad.values()), fields=step_bad,
                      hint="re-run with --debug-checks to localize")

    def _emit_blockdt(self, fetched, its) -> None:
        """Schema-v6 block-timestep telemetry at the fetch boundary: one
        ``dt_bins`` event per checked step / clean window, built from the
        already-FETCHED per-substep bdt_* diagnostics — host arithmetic
        only, the deferred-window zero-sync contract is untouched.  The
        updates/updates_full counters double as the chip-free complexity
        proxy (docs/NEXT.md): every substep advances sim-time by dt_min
        under BOTH schemes, so the global-dt cost of the same span is
        exactly n updates per substep."""
        steps = [(it, d) for it, d in zip(its, fetched)
                 if "bdt_active" in d]
        if not steps:
            return
        ds = [d for _, d in steps]
        n = self.state.n
        updates = sum(int(d["bdt_active"]) for d in ds)
        full = n * len(ds)
        resorts = sum(int(d["bdt_resort"]) for d in ds)
        self.bdt_updates += updates
        self.bdt_updates_full += full
        self.bdt_resorts += resorts
        self.bdt_keeps += len(ds) - resorts
        self.telemetry.event(
            "dt_bins", it=steps[-1][0], steps=len(ds),
            pop=[int(v) for v in np.asarray(ds[-1]["bdt_pop"])],
            updates=updates, updates_full=full,
            saved=round(1.0 - updates / full, 6) if full else 0.0,
            resorts=resorts, keeps=len(ds) - resorts,
            drift_max=max(int(d["bdt_drift"]) for d in ds),
            work=sum(float(d["bdt_work"]) for d in ds),
        )

    def drain_snapshots(self) -> list:
        """(iteration, npz_path) pairs for snapshot frames written since
        the last drain, in iteration order — the thin interface the
        --insitu renderer consumes (host file IO only, no device
        access). Frames appear only at check/flush boundaries, so under
        deferral a whole window's due frames land at once."""
        frames, self._snap_frames = self._snap_frames, []
        return frames

    def _emit_snapshot(self, fetched, its) -> None:
        """Schema-v8 live science surface at the fetch boundary: for
        every due step (``it % snap_every == 0``) write one .npz frame
        into the ``snapshots/`` ring (grid + meta; capped at snap_keep)
        and emit one ``snapshot`` event (grid meta + extrema inline, the
        frame path as the pointer). ``fetched`` holds the
        already-FETCHED diagnostics — host numpy + file IO only, the
        deferred-window zero-sync contract is untouched (pinned by
        tests/test_telemetry.py's snapshot guard)."""
        if self._snap_spec is None:
            return
        spec = self._snap_spec
        steps = [(it, d) for it, d in zip(its, fetched)
                 if "snap_grid" in d and it % self._snap_every == 0]
        if not steps:
            return
        tel = self.telemetry
        # box extents travel with every frame so a jax-free renderer can
        # label axes; fetched once per boundary (the boundary is already
        # a sync point)
        lo = np.asarray(jax.device_get(self.box.lo), np.float64)
        lengths = np.asarray(jax.device_get(self.box.lengths), np.float64)
        for it, d in steps:
            grid = np.asarray(d["snap_grid"])
            vmin = [float(v) for v in np.asarray(d["snap_min"])]
            vmax = [float(v) for v in np.asarray(d["snap_max"])]
            path = None
            if self._snap_dir:
                os.makedirs(self._snap_dir, exist_ok=True)
                path = os.path.join(self._snap_dir,
                                    f"snap_{int(it):06d}.npz")
                payload = {
                    "grid": grid, "it": np.int64(it),
                    "fields": np.asarray(spec.fields),
                    "axis": np.int64(spec.axis),
                    "reduce": np.asarray(spec.reduce),
                    "volume": np.bool_(spec.volume),
                    "lo": lo, "lengths": lengths,
                    "vmin": np.asarray(vmin), "vmax": np.asarray(vmax),
                }
                if "snap_pts" in d:
                    payload["pts"] = np.asarray(d["snap_pts"])
                np.savez(path, **payload)
                self._snap_frames.append((int(it), path))
                self._snap_ring.append(path)
                while self._snap_keep > 0 \
                        and len(self._snap_ring) > self._snap_keep:
                    old = self._snap_ring.pop(0)
                    try:
                        os.remove(old)
                    except OSError:
                        pass
            tel.event("snapshot", it=int(it), fields=list(spec.fields),
                      grid=spec.grid, axis=spec.axis, reduce=spec.reduce,
                      volume=spec.volume, vmin=vmin, vmax=vmax,
                      path=path)

    @staticmethod
    def _lists_fresh(diagnostics) -> bool:
        """False when the step ran on EXPIRED lists (drift/growth ate
        the Verlet skin before launch): its pair sums may have missed
        neighbors, so the step must be discarded and replayed on fresh
        lists — the same discard semantics as a cap overflow, but the
        recovery is a cheap list rebuild, not a static re-size."""
        return int(diagnostics.get("list_ok", 1)) != 0

    def _reconfigure_after_overflow(self, diagnostics, grav_margin: float):
        occ = int(diagnostics["occupancy"])
        if self._mesh is not None and occ == self._cfg.nbr.cap + 1:
            # the cap+1 SENTINEL (not a real occupancy) is how escaped
            # halo runs surface under sharding; grow the window margin so
            # the rebuild converges — but never for unrelated gravity/
            # cell-cap overflows, which would inflate comm volume for the
            # rest of the run
            self._halo_margin *= 1.5
            # every sentinel trip is telemetry: the exchange events stamp
            # the cumulative count so a drift-heavy run's resize churn is
            # visible in the record, not just in wall time
            self.telemetry.count("halo_trips")
        # occ == cap+1 is the window-blowout SENTINEL, not a real
        # occupancy — feeding it back as min_cap would ratchet the cap
        # (and force a fresh compile) on every blowout; a plain
        # re-estimate resizes the window instead
        window_blown = occ == self._cfg.nbr.cap + 1
        nbr_over = occ > self._cfg.nbr.cap
        self._configure(
            min_cap=0 if window_blown or not nbr_over else occ,
            grav_margin=grav_margin, reason="overflow",
        )

    def _step_checked(self, replay: bool = False) -> Dict[str, float]:
        """Advance one step synchronously; a step whose own diagnostics
        reveal a cell-cap overflow (truncated neighbor candidates) is
        discarded and re-run under a freshly sized config — overflow must
        never corrupt state.

        ``replay``: a rolled-back window's step. Where the window's
        launches donate, the replay launches the same donated program
        over a pinned copy: the undonated twin is a second executable
        nothing has compiled until the run's first rollback, and a
        recovery is no place for a first-use compile."""
        with self.telemetry.span("sphexa:step"):
            return self._step_checked_impl(replay and self._donate_active)

    def _pin(self) -> SimState:
        """The current carry, safe from a donated launch: with donation
        active the launch CONSUMES self.state, so the particle slab is a
        real copy. Aux slots (turb/chem/_bstate) are never donated and
        ride by reference."""
        with self.telemetry.span("sphexa:pin",
                                 copied=bool(self._donate_active)):
            pin = self.state
            if self._donate_active:
                pin = jax.tree.map(jnp.copy, self.state)
            return dataclasses.replace(self.sim_state, particles=pin)

    def _step_checked_impl(self, donate: bool = False) -> Dict[str, float]:
        reconfigured = False
        grav_margin = 1.5
        grav_blown_once = False
        t0 = time.perf_counter()
        for _attempt in range(4):
            pin = self._pin() if donate else None
            out = self._launch(donate_ok=donate)
            with self.telemetry.span("sphexa:fetch"):
                diagnostics = {**out[1], **self._fetch_scalars(out[1])}
            if not self._overflowed(diagnostics):
                break
            if pin is not None:
                # the discarded launch consumed its input: the retry
                # (and the sizing before it) reads the pinned copy
                self._set_sim_state(pin)
            if not self._lists_fresh(diagnostics):
                # stale persistent lists: discard + rebuild (no re-size)
                self._rebuild_lists(
                    "expiry", slack=float(diagnostics["list_slack"]))
                continue
            if self._grav_window_blown(diagnostics):
                # escaped sparse near-field runs (the cap+1 sentinel):
                # grow the MAC-need margin so the re-size converges —
                # NOT the interaction-list caps, which would recompile a
                # bigger engine for a comm problem. A second trip within
                # one step jumps straight to the full-slab ceiling
                # (caps == S, where the sentinel provably cannot fire)
                # so convergence fits the 4-attempt budget.
                self._grav_halo_margin = (
                    1e9 if grav_blown_once else self._grav_halo_margin * 1.5
                )
                grav_blown_once = True
                self.telemetry.count("grav_halo_trips")
            elif self._gravity_overflowed(diagnostics):
                grav_margin *= 1.5
            self._reconfigure_after_overflow(diagnostics, grav_margin)
            reconfigured = True
        else:
            raise RuntimeError(
                "neighbor/gravity caps failed to converge in 4 attempts "
                f"(last: {self._cap_report(diagnostics)})"
            )
        # launch -> batched scalar fetch is the step's device span (the
        # fetch drains the dispatched program); retries charge here too,
        # exactly like a recompile charges the reference's Timer
        wall = time.perf_counter() - t0
        self._apply(out)
        self.iteration += 1
        self._observe_lists([diagnostics])
        # config check FIRST: _configure() drops self._lists, so a
        # proactive rebuild before it would be wasted work
        if not self._config_still_valid(diagnostics):
            self._configure(reason="stale-grid")
            reconfigured = True
        elif not self._lists_cover_h(diagnostics, diagnostics):
            self._configure(reason="h-relax")
            reconfigured = True
        self._plan_lists()
        result = {
            k: np.asarray(v) if getattr(v, "ndim", 0) else float(v)
            for k, v in diagnostics.items()
        }
        result["reconfigured"] = float(reconfigured)
        self.telemetry.event(
            "step", it=self.iteration, wall_s=round(wall, 6),
            dt=float(result["dt"]) if "dt" in result else None,
            reconfigured=bool(reconfigured),
            **self._list_fills([diagnostics]),
        )
        self._emit_distributed(diagnostics, steps=1)
        self._emit_science([diagnostics], [self.iteration])
        self._emit_blockdt([diagnostics], [self.iteration])
        self._emit_snapshot([diagnostics], [self.iteration])
        self._emit_memory("post-compile")
        if self.debug_checks:
            # first triggered checkify predicate of THIS step ("" = all
            # NaN/Inf/OOB checks passed); .get() syncs, which is the
            # sanitizer's contract — locate the failing step exactly
            msg = self._check_err.get() if self._check_err is not None \
                else None
            result["check_error"] = msg or ""
        self._last_diag = result
        return result

    def step(self) -> Dict[str, float]:
        """Advance one step.

        With ``check_every == 1`` (default) the step is checked
        synchronously. With ``check_every > 1`` steps are launched with NO
        device->host sync on the happy path; every ``check_every`` steps
        (sooner where the pair list is predicted to cover fewer:
        ``_plan_lists``) the accumulated diagnostics are fetched in one
        transfer and, if an overflow is found, the simulation rolls back
        to the last verified state and replays the lost steps under a
        fresh config (the same discard-and-retry semantics, checked
        late). Diagnostics returned between check boundaries are the last
        verified ones, marked ``{"deferred": 1.0}``.
        """
        if self.check_every <= 1 or not self._pending:
            # a checked step or a window opens: its spans (pin, launches,
            # flush, a replay) and those of a dump at its boundary share
            # this iteration
            self.telemetry.iteration = self.iteration
        if self.check_every <= 1:
            return self._step_checked()
        if not self._pending:
            # host stamp of the window's first launch: flush() attributes
            # the whole window's device time against it — the only
            # per-step timing the sync-free happy path can honestly give
            self._window_t0 = time.perf_counter()
            # only the WINDOW-START state is pinned for rollback (one
            # extra state, not check_every of them — 68 MB/state at 100^3).
            # With donation active the window's first launch CONSUMES
            # self.state, so the pin must be a real copy — one copy per
            # window, amortized over check_every donated steps
            self._window_prior = (self._pin(), self.iteration)
        out = self._launch(donate_ok=True)
        self._apply(out)
        self.iteration += 1
        # happy-path telemetry is launch-count only: diagnostics stay on
        # device, timestamps are host-side — zero added transfers
        self.telemetry.event("launch", it=self.iteration)
        self._pending.append(out[1])
        if len(self._pending) >= self._plan.steps:
            return self.flush()
        return {**self._last_diag, "deferred": 1.0}

    def flush(self) -> Dict[str, float]:
        """Drain the deferred-check queue: one batched fetch of every
        pending step's scalar diagnostics; if any step overflowed, roll
        back to the window-start state and replay the whole window through
        the synchronous checked path."""
        if not self._pending:
            return self._last_diag
        with self.telemetry.span("sphexa:flush"):
            pending, self._pending = self._pending, []
            prior, self._window_prior = self._window_prior, None
            t0, self._window_t0 = self._window_t0, None
            with self.telemetry.span("sphexa:fetch"):
                fetched = jax.device_get(
                    [self._scalar_view(d) for d in pending])
            # the batched fetch drains every launched program, so this
            # host span IS the window's device time; per-step attribution
            # is its mean (what "step time" means under deferral,
            # docs/OBSERVABILITY)
            window_wall = time.perf_counter() - t0 if t0 is not None else 0.0
            bad = next(
                (i for i, scal in enumerate(fetched)
                 if self._overflowed(scal)),
                None,
            )
            if bad is None:
                with self.telemetry.span("sphexa:settle"):
                    return self._settle(pending, fetched, window_wall)
            with self.telemetry.span("sphexa:rollback"):
                return self._rollback(pending, fetched, prior, bad)

    def _settle(self, pending, fetched, window_wall) -> Dict[str, float]:
        """A verified window's host work after the fetch: its events, the
        validity check of the config, the age check of the lists."""
        self.telemetry.event(
            "window", it=self.iteration, steps=len(pending),
            wall_s=round(window_wall, 6),
            per_step_s=round(window_wall / len(pending), 6),
            planned_steps=self._plan.steps,
            **self._list_fills(fetched),
        )
        # distributed telemetry rides the SAME fetch: per-shard
        # load/exchange events + HBM snapshot, at window granularity
        self._emit_distributed(fetched[-1], steps=len(pending))
        # science ledger rides it too: one physics/numerics event +
        # a constants row per step of the window (every step keeps
        # its row even under --check-every N)
        win_its = list(range(self.iteration - len(pending) + 1,
                             self.iteration + 1))
        self._emit_science(fetched, win_its)
        self._emit_blockdt(fetched, win_its)
        self._emit_snapshot(fetched, win_its)
        self._emit_memory("post-compile")
        self._emit_memory("flush")
        diagnostics = {**pending[-1], **fetched[-1]}
        result = {
            k: np.asarray(v) if getattr(v, "ndim", 0) else float(v)
            for k, v in diagnostics.items()
        }
        result["reconfigured"] = 0.0
        self._last_diag = result
        self._observe_lists(fetched)
        if not self._config_still_valid(fetched[-1]):
            self._configure(reason="stale-grid")
            self._last_diag["reconfigured"] = 1.0
        elif not self._lists_cover_h(fetched[0], fetched[-1]):
            self._configure(reason="h-relax")
            self._last_diag["reconfigured"] = 1.0
        self._plan_lists()
        return self._last_diag

    def _rollback(self, pending, fetched, prior, bad) -> Dict[str, float]:
        """Roll back to the window start and replay every window step
        through the synchronous checked path."""
        diag_bad = fetched[bad]
        expiry_only = (
            not self._lists_fresh(diag_bad)
            and int(diag_bad["occupancy"]) <= self._cfg.nbr.cap
            and not self._gravity_overflowed(diag_bad)
        )
        self.telemetry.count("rollbacks")
        self.telemetry.event(
            "rollback", it=self.iteration, to_it=prior[1],
            steps=len(pending), bad_index=bad,
            reason="list-expiry" if expiry_only else "overflow",
        )
        self._set_sim_state(prior[0])
        self.iteration = prior[1]
        if expiry_only:
            # expiry only: fresh lists on the rolled-back state suffice;
            # the old list served the window's steps before the bad one
            self._rebuild_lists(
                "rollback", slack=float(diag_bad["list_slack"]),
                served_to=prior[1] + bad)
        else:
            grav_margin = 1.5
            if self._grav_window_blown(diag_bad):
                # escaped sparse gravity runs (cap+1 sentinel): regrow
                # the MAC-need margin, not the interaction-list caps.
                # The replay below goes through _step_checked, which
                # escalates to the full-slab ceiling on a repeat trip.
                self._grav_halo_margin *= 1.5
                self.telemetry.count("grav_halo_trips")
            elif self._gravity_overflowed(diag_bad):
                grav_margin = 1.5 * 1.5
            self._reconfigure_after_overflow(diag_bad, grav_margin)
        for _ in range(len(pending)):
            result = self._step_checked(replay=True)
        self.telemetry.event("replay", it=self.iteration, steps=len(pending))
        result["reconfigured"] = 1.0
        self._last_diag = result
        return result

    def run(self, num_steps: int, log_every: int = 0, printer=print):
        # per-iteration report routes through the telemetry console sink
        # when one is attached (``printer`` stays the fallback); scalar
        # keys are propagator-dependent beyond STEP_DIAG_KEYS, so missing
        # ones render as nan instead of KeyError-ing the whole run
        emit = self.telemetry.console_printer(printer)
        nan = float("nan")
        for _ in range(num_steps):
            d = self.step()
            if log_every and self.iteration % log_every == 0:
                if d.get("deferred"):
                    emit(f"it {self.iteration:5d}  (deferred check)")
                else:
                    emit(
                        f"it {self.iteration:5d}  t={float(self.state.ttot):.6g}  "
                        f"dt={float(d.get('dt', nan)):.4g}  "
                        f"nc~{float(d.get('nc_mean', nan)):.1f}  "
                        f"rho_max={float(d.get('rho_max', nan)):.4g}"
                    )
        # the final partial window must be verified before the state is
        # handed back — overflow must never corrupt state
        self.flush()
        return self.state
