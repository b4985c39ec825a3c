"""jaxaudit entry-point registry: the package's hot jitted functions.

Each ``@entrypoint`` builder constructs a SMALL synthetic case (the
``init/`` case builders at tiny N, the same ``Simulation`` configuration
machinery production uses — so the audited config IS the shipped config)
and returns the traced callable + example args. Builders run lazily per
audit run and import jax-heavy modules inside the function body, so
importing this module stays cheap and device-free.

Conventions:

- step entries audit the plain jit for tracing/execution and the
  ``*_donated`` twin's lowering for the donation rule (``donate=(0,)`` =
  the ParticleState pytree at lowered arg position 0; static args are
  elided from ``args_info``).
- ``carry`` maps (step-1 args, step-1 out) -> step-2 args, giving the
  recompile rule real committed avals (weak types visible).
- sharded entries declare ``mesh_axes`` and size their mesh from
  ``audit_context().mesh_size`` (CLI default 2; ``preflight --mesh P``
  and ``--cpu-devices P`` retrace at campaign-shaped P) — when the
  process has fewer devices they raise ``EntrySkip`` (the tier-1 gate
  runs under the 8-virtual-device CPU mesh and asserts no skips).
- entries carrying an ``exchange_budget_bytes`` declare the analytic
  cross-shard volume (sizing-derived) the JXA203 gate checks the traced
  collective output bytes against.
"""

from __future__ import annotations

import dataclasses
import functools

from sphexa_tpu.devtools.audit.core import (
    EntryCase,
    EntrySkip,
    audit_context,
    entrypoint,
)

# tiny-but-nondegenerate case sizes: big enough for a real neighbor grid
# and a multi-level gravity tree, small enough that a full step traces
# and runs in ~seconds on a CPU host
_SIDE = 6          # 216 particles (cube cases)
_SIDE_GRAV = 6     # sphere cuts (evrard) keep ~half of side^3
# second trace point for the JXA204 tree-growth probe: large enough for
# a real N jump, small enough that the extra retrace stays cheap
_SIDE_GROW = 8

# headroom added to every analytic exchange budget before the JXA203
# volume gate: covers the small fixed-size collectives riding the stage
# (escape sentinels, the all_gathered telemetry scalars, range bounds)
_EXCHANGE_HEADROOM = 262_144


def _mesh_size_and_side():
    """Mesh size for sharded entries, from the audit context (CLI
    default 2 keeps tier-1 cheap; ``preflight --mesh P`` retraces the
    same builders at campaign-shaped P), plus a cube side whose particle
    count splits evenly across it (216 doesn't divide by 16)."""
    import jax

    P = audit_context().mesh_size
    if len(jax.devices()) < P:
        raise EntrySkip(f"needs >= {P} devices for the 'p' mesh "
                        "(sphexa-audit bootstraps one; in-process callers "
                        "use util.cpu_mesh.force_cpu_mesh)")
    side = _SIDE if (_SIDE ** 3) % P == 0 else 8
    return P, side


@functools.lru_cache(maxsize=None)
def _sim(case: str, side: int, prop: str = "std"):
    """Memoized Simulation construction: entries only READ the sim's
    state/config products, so sharing one build between entries (e.g.
    step_nbody + gravity_solve both want the configured evrard nbody
    sim, gravity caps included) halves the audit's setup cost."""
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    state, box, const = make_initializer(case)(side)
    return Simulation(state, box, const, prop=prop)


# ---------------------------------------------------------------------------
# propagator step builders (the five production steps)
# ---------------------------------------------------------------------------


def _step_std_case(side: int) -> EntryCase:
    from sphexa_tpu import propagator as prop

    sim = _sim("sedov", side, prop="std")
    cfg, state, box = sim._cfg, sim.state, sim.box
    return EntryCase(
        fn=lambda s, b: prop.step_hydro_std(s, b, cfg, None),
        args=(state, box),
        lower=lambda: prop.step_hydro_std_donated.lower(state, box, cfg,
                                                        None),
        carry=lambda a, out: (out[0], out[1]),
    )


@entrypoint("step_std", donate=(0,))
def step_std():
    case = _step_std_case(_SIDE)
    # JXA204 growth probe: the same step at _SIDE_GROW — cell grids and
    # scan accumulators must not grow superlinearly in N
    case.grow = lambda: (_step_std_case(_SIDE_GROW),
                         _SIDE_GROW ** 3 / _SIDE ** 3)
    return case


@entrypoint("step_ve", donate=(0,))
def step_ve():
    from sphexa_tpu import propagator as prop

    sim = _sim("sedov", _SIDE, prop="ve")
    cfg, state, box = sim._cfg, sim.state, sim.box
    return EntryCase(
        fn=lambda s, b: prop.step_hydro_ve(s, b, cfg, None),
        args=(state, box),
        lower=lambda: prop.step_hydro_ve_donated.lower(state, box, cfg,
                                                       None),
        carry=lambda a, out: (out[0], out[1]),
    )


@entrypoint("step_nbody", donate=(0,))
def step_nbody():
    from sphexa_tpu import propagator as prop

    sim = _sim("evrard", _SIDE_GRAV, prop="nbody")
    cfg, state, box, gtree = sim._cfg, sim.state, sim.box, sim._gtree
    return EntryCase(
        fn=lambda s, b, g: prop.step_nbody(s, b, cfg, g),
        args=(state, box, gtree),
        lower=lambda: prop.step_nbody_donated.lower(state, box, cfg, gtree),
        carry=lambda a, out: (out[0], out[1], a[2]),
    )


@entrypoint("step_turb_ve", donate=(0,))
def step_turb_ve():
    from sphexa_tpu import propagator as prop

    sim = _sim("turbulence", _SIDE, prop="turb-ve")
    cfg, state, box = sim._cfg, sim.state, sim.box
    turb_cfg, turb = sim.turb_cfg, sim.turb_state
    return EntryCase(
        fn=lambda s, b, t: prop.step_turb_ve(s, b, cfg, None, t, turb_cfg),
        args=(state, box, turb),
        lower=lambda: prop.step_turb_ve_donated.lower(
            state, box, cfg, None, turb, turb_cfg),
        carry=lambda a, out: (out[0], out[1], out[3]),
    )


@entrypoint("step_std_cooling", donate=(0,))
def step_std_cooling():
    from sphexa_tpu import propagator as prop

    sim = _sim("evrard-cooling", _SIDE_GRAV, prop="std-cooling")
    cfg, state, box, gtree = sim._cfg, sim.state, sim.box, sim._gtree
    cool_cfg, chem = sim.cooling_cfg, sim.chem
    return EntryCase(
        fn=lambda s, b, g, ch: prop.step_hydro_std_cooling(
            s, b, cfg, g, ch, cool_cfg),
        args=(state, box, gtree, chem),
        lower=lambda: prop.step_hydro_std_cooling_donated.lower(
            state, box, cfg, gtree, chem, cool_cfg),
        carry=lambda a, out: (out[0], out[1], a[2], out[3]),
    )


# ---------------------------------------------------------------------------
# gravity solve (gravity/traversal.py)
# ---------------------------------------------------------------------------


def _gravity_case(side: int):
    """(EntryCase, n) for the evrard gravity solve at one toy side."""
    import jax.numpy as jnp
    import numpy as np

    from sphexa_tpu import native
    from sphexa_tpu.gravity.traversal import compute_gravity

    sim = _sim("evrard", side, prop="nbody")
    s, box = sim.state, sim.box
    keys = native.compute_keys(
        np.asarray(s.x), np.asarray(s.y), np.asarray(s.z),
        np.asarray(box.lo), np.asarray(box.lengths), sim.curve,
    )
    order = native.argsort_keys(keys)
    skeys = jnp.asarray(keys[order])
    xs, ys, zs, ms, hs = (
        jnp.asarray(np.asarray(f)[order])
        for f in (s.x, s.y, s.z, s.m, s.h)
    )
    meta, gcfg = sim._cfg.grav_meta, sim._cfg.gravity
    return EntryCase(
        fn=lambda x, y, z, m, h, sk, b, gt: compute_gravity(
            x, y, z, m, h, sk, b, gt, meta, gcfg),
        args=(xs, ys, zs, ms, hs, skeys, box, sim._gtree),
    ), int(s.n)


@entrypoint("gravity_solve")
def gravity_solve():
    case, n = _gravity_case(_SIDE_GRAV)
    # JXA204 growth probe: the round-10 carried caution names exactly
    # this entry — a superlinear TREE build hiding in the traced-size
    # exemption. Two-point probe at _SIDE_GROW closes it.
    def grow():
        grown, n2 = _gravity_case(_SIDE_GROW)
        return grown, n2 / n

    case.grow = grow
    return case


# ---------------------------------------------------------------------------
# sparse halo exchange (parallel/exchange.py) — sharded on the CPU mesh
# ---------------------------------------------------------------------------


@entrypoint("halo_exchange_sparse", mesh_axes=("p",))
def halo_exchange_sparse():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec

    from sphexa_tpu import native
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel import exchange as ex
    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.propagator import shard_map
    from sphexa_tpu.simulation import make_propagator_config

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    cfg = make_propagator_config(state, box, const)
    # globally SFC-sorted arrays, as the sharded step provides them
    keys = native.compute_keys(
        np.asarray(state.x), np.asarray(state.y), np.asarray(state.z),
        np.asarray(box.lo), np.asarray(box.lengths), cfg.curve,
    )
    order = native.argsort_keys(keys)
    skeys = jnp.asarray(keys[order])
    x, y, z, h, m = (
        jnp.asarray(np.asarray(f)[order])
        for f in (state.x, state.y, state.z, state.h, state.m)
    )
    mesh = make_mesh(P)
    S_shard = state.n // P
    nbr = cfg.nbr
    if nbr.run_cap > S_shard:  # same clamp as the sharded force stages
        nbr = dataclasses.replace(nbr, run_cap=S_shard)
    hmax = (S_shard,) * (P - 1)  # full per-distance coverage at tiny N

    def stage(b, keys, x, y, z, h, m):
        # the 5-tuple contract: the per-shard telemetry dict rides the
        # audited trace too, so JXA104/JXA106 cover the schema-v2 metric
        # plumbing (all_gathered exchange scalars) alongside the exchange
        from sphexa_tpu.propagator import _shard_metrics

        ranges, serve, jbuf, escaped, hmetrics = ex.shard_halo_stage_sparse(
            x, y, z, h, keys, b, nbr, P, hmax, "p"
        )
        halo = serve((x, y, z, m))
        jx, jy, jz, jm = jbuf((x, y, z, m), halo)
        # chain the tail reductions after the exchange and each other —
        # escaped/hmetrics are computed PRE-serve, so without the pins
        # these collectives race the ppermutes (the JXA201 class)
        esc = jax.lax.pmax(
            ex.chain_after(jnp.asarray(escaped, jnp.int32), jx), "p"
        )
        smetrics = _shard_metrics(ranges, escaped, hmetrics, "p", token=esc)
        return jx, jy, jz, jm, esc, smetrics

    Pp, Pr = PartitionSpec("p"), PartitionSpec()
    from sphexa_tpu.propagator import SHARD_DIAG_KEYS

    fn = jax.jit(shard_map(
        stage, mesh=mesh,
        in_specs=(Pr, Pp, Pp, Pp, Pp, Pp, Pp),
        out_specs=(Pp, Pp, Pp, Pp, Pr, {k: Pr for k in SHARD_DIAG_KEYS}),
        check_vma=False,
    ))
    return EntryCase(
        fn=fn, args=(box, skeys, x, y, z, h, m),
        # analytic serve volume: hmax rows per peer distance x 4 fields
        exchange_budget_bytes=sum(hmax) * 4 * 4 + _EXCHANGE_HEADROOM,
    )


@entrypoint("halo_exchange_windowed", mesh_axes=("p",))
def halo_exchange_windowed():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec

    from sphexa_tpu import native
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel import exchange as ex
    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.propagator import shard_map
    from sphexa_tpu.simulation import make_propagator_config

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    cfg = make_propagator_config(state, box, const)
    keys = native.compute_keys(
        np.asarray(state.x), np.asarray(state.y), np.asarray(state.z),
        np.asarray(box.lo), np.asarray(box.lengths), cfg.curve,
    )
    order = native.argsort_keys(keys)
    skeys = jnp.asarray(keys[order])
    x, y, z, h, m = (
        jnp.asarray(np.asarray(f)[order])
        for f in (state.x, state.y, state.z, state.h, state.m)
    )
    mesh = make_mesh(P)
    S_shard = state.n // P
    Wmax = S_shard  # full-slab windows, as the gravity near field uses
    nbr = cfg.nbr
    if nbr.run_cap > S_shard:
        nbr = dataclasses.replace(nbr, run_cap=S_shard)

    def stage(b, keys, x, y, z, h, m):
        from sphexa_tpu.propagator import _shard_metrics

        ranges, serve, jbuf, escaped, hmetrics = ex.shard_halo_stage(
            x, y, z, h, keys, b, nbr, P, Wmax, "p"
        )
        halo = serve((x, y, z, m))
        jx, jy, jz, jm = jbuf((x, y, z, m), halo)
        esc = jax.lax.pmax(
            ex.chain_after(jnp.asarray(escaped, jnp.int32), jx), "p"
        )
        smetrics = _shard_metrics(ranges, escaped, hmetrics, "p", token=esc)
        return jx, jy, jz, jm, esc, smetrics

    Pp, Pr = PartitionSpec("p"), PartitionSpec()
    from sphexa_tpu.propagator import SHARD_DIAG_KEYS

    fn = jax.jit(shard_map(
        stage, mesh=mesh,
        in_specs=(Pr, Pp, Pp, Pp, Pp, Pp, Pp),
        out_specs=(Pp, Pp, Pp, Pp, Pr, {k: Pr for k in SHARD_DIAG_KEYS}),
        check_vma=False,
    ))
    return EntryCase(
        fn=fn, args=(box, skeys, x, y, z, h, m),
        # analytic serve volume: one all_to_all of P windows x 4 fields
        exchange_budget_bytes=P * Wmax * 4 * 4 + _EXCHANGE_HEADROOM,
    )


# ---------------------------------------------------------------------------
# sharded gravity: psum multipole upsweep + LET traversal + windowed
# near-field exchange (propagator._gravity_sharded_stage) — the campaign
# gravity program, traced whole so the JXA2xx rules see the full
# collective schedule
# ---------------------------------------------------------------------------


@entrypoint("gravity_sharded", mesh_axes=("p",))
def gravity_sharded():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sphexa_tpu import native
    from sphexa_tpu import propagator as prop
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.simulation import Simulation

    P, _ = _mesh_size_and_side()
    state, box, const = make_initializer("evrard")(_SIDE_GRAV)
    # evrard's sphere cut leaves an arbitrary n; trim to a multiple of
    # 16 so one state shards on any audited mesh size
    n16 = (state.n // 16) * 16
    state = jax.tree.map(
        lambda a: a[:n16] if getattr(a, "ndim", 0) == 1 else a, state)
    sim = Simulation(state, box, const, prop="nbody")
    s = sim.state
    keys = native.compute_keys(
        np.asarray(s.x), np.asarray(s.y), np.asarray(s.z),
        np.asarray(sim.box.lo), np.asarray(sim.box.lengths), sim.curve,
    )
    order = native.argsort_keys(keys)
    skeys = jnp.asarray(keys[order])
    xs, ys, zs, ms, hs = (
        jnp.asarray(np.asarray(f)[order])
        for f in (s.x, s.y, s.z, s.m, s.h)
    )
    sstate = dataclasses.replace(s, x=xs, y=ys, z=zs, m=ms, h=hs)
    cfg_sh = dataclasses.replace(sim._cfg, mesh=make_mesh(P),
                                 shard_axis="p")
    # gtree rides as a TRACED arg (O(tree) replicated coarse structure,
    # too big for a baked-in jaxpr constant)
    return EntryCase(
        fn=lambda st, bb, k, gt: prop._gravity_sharded_stage(
            st.x, st.y, st.z, st.m, st.h, k, bb, cfg_sh, gt),
        args=(sstate, sim.box, skeys, sim._gtree),
    )


@entrypoint("gravity_sharded_windowed", mesh_axes=("p",))
def gravity_sharded_windowed():
    """The MAC-sized sparse gravity near field: gravity_sharded's
    program with per-distance row caps from sizing.device_gravity_halo
    bound into the serve (exchange.serve_sparse riding the stage). Sized
    at a node count and opening angle where the MAC genuinely prunes
    (evrard side 20, theta 0.8 — at ``--mesh 4`` the sized volume sits
    strictly below the full-slab baseline; docs/NEXT.md round 13), so
    JXA203 records the gravity comm diet next to the full-slab entry's
    number and JXA201 proves the longer chained collective schedule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sphexa_tpu import native
    from sphexa_tpu import propagator as prop
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel import make_mesh
    from sphexa_tpu.parallel.sizing import device_gravity_halo
    from sphexa_tpu.simulation import Simulation

    P, _ = _mesh_size_and_side()
    state, box, const = make_initializer("evrard")(20)
    n16 = (state.n // 16) * 16
    state = jax.tree.map(
        lambda a: a[:n16] if getattr(a, "ndim", 0) == 1 else a, state)
    sim = Simulation(state, box, const, prop="nbody", theta=0.8)
    s = sim.state
    keys = native.compute_keys(
        np.asarray(s.x), np.asarray(s.y), np.asarray(s.z),
        np.asarray(sim.box.lo), np.asarray(sim.box.lengths), sim.curve,
    )
    order = native.argsort_keys(keys)
    skeys = jnp.asarray(keys[order])
    xs, ys, zs, ms, hs = (
        jnp.asarray(np.asarray(f)[order])
        for f in (s.x, s.y, s.z, s.m, s.h)
    )
    sstate = dataclasses.replace(s, x=xs, y=ys, z=zs, m=ms, h=hs)
    cells = device_gravity_halo(
        xs, ys, zs, ms, skeys, sim.box, sim._gtree, sim._cfg.grav_meta,
        theta=sim.theta, P=P,
    )
    cfg_sh = dataclasses.replace(sim._cfg, mesh=make_mesh(P),
                                 shard_axis="p", grav_cells=cells)
    # 5 served fields (x/y/z/m/h) x f32; the replicated multipole psum
    # and the all_gathered telemetry scalars ride the headroom
    return EntryCase(
        fn=lambda st, bb, k, gt: prop._gravity_sharded_stage(
            st.x, st.y, st.z, st.m, st.h, k, bb, cfg_sh, gt),
        args=(sstate, sim.box, skeys, sim._gtree),
        exchange_budget_bytes=sum(cells) * 5 * 4 + _EXCHANGE_HEADROOM,
    )


# ---------------------------------------------------------------------------
# sharded hydro step: the exact campaign entry — make_sharded_step's
# propagator config (windowed/sparse halo sizing included) traced over
# the audit mesh, with the analytic _halo_info exchange budget as the
# JXA203 volume gate
# ---------------------------------------------------------------------------


@entrypoint("step_std_sharded", mesh_axes=("p",))
def step_std_sharded():
    from sphexa_tpu import propagator as prop
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    sim = Simulation(state, box, const, prop="std", backend="pallas",
                     num_devices=P)
    hi = sim._halo_info
    # mirror make_sharded_step's config replace so the audited trace IS
    # the stepper's program (tracing the stepper itself would audit its
    # device_put re-sharding prologue, a false JXA104 host boundary)
    cfg_sh = dataclasses.replace(
        sim._cfg, mesh=sim._mesh, shard_axis="p",
        halo_window=(hi["wmax"] if hi["mode"] == "windowed" else 0),
        halo_cells=tuple(hi.get("caps", ())),
        halo_runs=hi.get("run_slots", 0),
    )
    return EntryCase(
        fn=lambda s, b: prop.step_hydro_std(s, b, cfg_sh, None),
        args=(sim.state, sim.box),
        exchange_budget_bytes=hi["bytes_per_step"] + _EXCHANGE_HEADROOM,
    )


# ---------------------------------------------------------------------------
# hierarchical block-timestep step (sph/blockdt.py): the std builder with
# per-particle Δt bins — audited at dt_bins=4 so the fold-key sort, the
# drift-aware resort cond, the due-mask compaction and the masked
# integrate all appear in the traced program (JXA301 covers the new
# sphexa/dt-bins taxonomy phase; the sharded twin holds the JXA201
# collective-order rule over the unchanged force-stage exchange)
# ---------------------------------------------------------------------------


@entrypoint("step_std_blockdt", donate=(0,))
def step_std_blockdt():
    from sphexa_tpu import propagator as prop
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    state, box, const = make_initializer("sedov")(_SIDE)
    sim = Simulation(state, box, const, prop="std", dt_bins=4,
                     bin_resort_drift=0.01)
    cfg, bst = sim._cfg, sim._bstate
    state, box = sim.state, sim.box
    return EntryCase(
        fn=lambda s, b, bd: prop.step_hydro_std_blockdt(
            s, b, cfg, None, bd),
        args=(state, box, bst),
        lower=lambda: prop.step_hydro_std_blockdt_donated.lower(
            state, box, cfg, None, bst),
        carry=lambda a, out: (out[0], out[1], out[3]),
    )


@entrypoint("step_std_blockdt_sharded", mesh_axes=("p",))
def step_std_blockdt_sharded():
    from sphexa_tpu import propagator as prop
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.simulation import Simulation

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    sim = Simulation(state, box, const, prop="std", backend="pallas",
                     num_devices=P, dt_bins=4)
    hi = sim._halo_info
    # same config mirror as step_std_sharded: the audited trace IS the
    # stepper's program, without its device_put re-sharding prologue
    cfg_sh = dataclasses.replace(
        sim._cfg, mesh=sim._mesh, shard_axis="p",
        halo_window=(hi["wmax"] if hi["mode"] == "windowed" else 0),
        halo_cells=tuple(hi.get("caps", ())),
        halo_runs=hi.get("run_slots", 0),
    )
    return EntryCase(
        fn=lambda s, b, bd: prop.step_hydro_std_blockdt(
            s, b, cfg_sh, None, bd),
        args=(sim.state, sim.box, sim._bstate),
        exchange_budget_bytes=hi["bytes_per_step"] + _EXCHANGE_HEADROOM,
    )


# ---------------------------------------------------------------------------
# in-graph observable ledger (observables/ledger.py) — the science
# reductions every step tail runs; audited standalone so JXA101 (dtype)
# and JXA104 (host boundary) hold the ledger itself, single-device and
# over a 2-device mesh (where each sum lowers to a chained collective)
# ---------------------------------------------------------------------------


@entrypoint("observable_ledger")
def observable_ledger():
    import jax.numpy as jnp

    from sphexa_tpu.observables.ledger import (
        ObservableSpec,
        ledger_diagnostics,
    )

    sim = _sim("sedov", _SIDE, prop="std")
    s, box, const = sim.state, sim.box, sim.const
    ngmax = sim._cfg.nbr.ngmax
    spec = ObservableSpec(extra="mach")  # exercises the case-extra path
    rho = jnp.ones_like(s.m)
    c = jnp.ones_like(s.m)
    nc = jnp.full((s.n,), const.ng0 - 1, jnp.int32)

    def fn(state, b, rho, nc, c):
        return ledger_diagnostics(state, rho, nc, const, ngmax, spec=spec,
                                  egrav=0.0, box=b, c=c)

    return EntryCase(fn=fn, args=(s, box, rho, nc, c))


@entrypoint("observable_ledger_sharded", mesh_axes=("p",))
def observable_ledger_sharded():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables.ledger import ledger_diagnostics
    from sphexa_tpu.parallel import make_mesh, shard_state
    from sphexa_tpu.simulation import make_propagator_config

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    cfg = make_propagator_config(state, box, const)
    mesh = make_mesh(P)
    sstate = shard_state(state, mesh)
    pspec = NamedSharding(mesh, PartitionSpec("p"))
    rho = jax.device_put(jnp.ones((state.n,)), pspec)
    nc = jax.device_put(jnp.full((state.n,), const.ng0 - 1, jnp.int32),
                        pspec)

    def fn(st, rho, nc):
        return ledger_diagnostics(st, rho, nc, const, cfg.nbr.ngmax)

    return EntryCase(fn=jax.jit(fn), args=(sstate, rho, nc))


# ---------------------------------------------------------------------------
# in-graph field snapshot (observables/snapshot.py) — the fixed-shape
# scatter-add deposit the live-science surface rides; audited standalone
# (like the ledger) single-device and over a 2-device mesh, where the
# replicated grid output makes GSPMD insert exactly one psum for the
# whole stacked (F, G*G) deposit
# ---------------------------------------------------------------------------


# jaxaudit: disable=JXA401 -- the deposit is a colliding histogram
# scatter BY DESIGN (many particles per cell); the grid is a viz/
# monitoring surface whose contract is the cell sum up to rounding,
# not bitwise replay — the science ledger (observable_ledger) keeps
# the deterministic pinned-order path
@entrypoint("observable_snapshot")
def observable_snapshot():
    import jax.numpy as jnp

    from sphexa_tpu.observables.snapshot import (
        SnapshotSpec,
        snapshot_diagnostics,
    )

    sim = _sim("sedov", _SIDE, prop="std")
    s, box = sim.state, sim.box
    # exercises the multi-field stack AND the particle-subsample tap
    spec = SnapshotSpec(fields=("rho", "temp"), grid=8, stride=7)
    rho = jnp.ones_like(s.m)

    def fn(state, b, rho):
        return snapshot_diagnostics(state, rho, b, spec)

    return EntryCase(fn=fn, args=(s, box, rho))


# jaxaudit: disable=JXA401 -- same deliberate histogram scatter as the
# single-device entry above
@entrypoint("observable_snapshot_sharded", mesh_axes=("p",))
def observable_snapshot_sharded():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.observables.snapshot import (
        SnapshotSpec,
        snapshot_diagnostics,
    )
    from sphexa_tpu.parallel import make_mesh, shard_state

    P, side = _mesh_size_and_side()
    state, box, const = make_initializer("sedov")(side)
    mesh = make_mesh(P)
    sstate = shard_state(state, mesh)
    pspec = NamedSharding(mesh, PartitionSpec("p"))
    rho = jax.device_put(jnp.ones((state.n,)), pspec)
    spec = SnapshotSpec(fields=("rho",), grid=8)

    def fn(st, rho, b):
        return snapshot_diagnostics(st, rho, b, spec)

    return EntryCase(fn=jax.jit(fn), args=(sstate, rho, box))


# ---------------------------------------------------------------------------
# tree build / sizing (parallel/sizing.py)
# ---------------------------------------------------------------------------


# phase_coverage_min=0: reconfigure-time program — none of its work runs
# inside a step-phase scope, so JXA301's taxonomy gate does not apply.
@entrypoint("tree_build_sizing", phase_coverage_min=0.0)
def tree_build_sizing():
    from sphexa_tpu.init import make_initializer
    from sphexa_tpu.parallel import sizing
    from sphexa_tpu.sfc.keys import compute_sfc_keys

    state, box, const = make_initializer("sedov")(_SIDE)
    level, group = 2, 64
    keys = compute_sfc_keys(state.x, state.y, state.z, box)

    def fn(x, y, z, b, keys):
        occ, ext = sizing.sizing_stats(x, y, z, b, level, group)
        hist = sizing.key_histogram(keys, level)
        return occ, ext, hist

    return EntryCase(fn=fn, args=(state.x, state.y, state.z, box, keys))


@entrypoint("knob_inertness", phase_coverage_min=0.0)
def knob_inertness():
    """JXA402 carrier: the traced fn is a stub (the rule's real work is
    the off-vs-unset probe pairs built by production_knob_probes, which
    fingerprint probe Simulations for every off-sentinel KnobSpec in
    tuning/knobs.py). A dedicated entry keeps the probes out of every
    step entry's rule loop while still running in every package audit.
    """
    import jax.numpy as jnp

    from sphexa_tpu.devtools.audit.lowerdiff import production_knob_probes

    return EntryCase(
        fn=lambda x: x * 1.0,
        args=(jnp.ones((8,), jnp.float32),),
        knob_probes=production_knob_probes,
    )
