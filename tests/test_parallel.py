"""Multi-device tests on the virtual 8-device CPU mesh: the sharded step
must (a) run with real cross-device shardings and (b) agree with the
single-device step bit-for-bit-ish. The analog of the reference's
oversubscribed-mpiexec integration tests (domain/test/integration_mpi/).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sphexa_tpu.init import init_sedov
from sphexa_tpu.parallel import make_mesh, make_sharded_step, shard_state
from sphexa_tpu.propagator import step_hydro_std
from sphexa_tpu.simulation import make_propagator_config


def make_cfg(state, box, const, block=512):
    return make_propagator_config(state, box, const, block=block)


class TestShardedStep:
    def test_eight_device_step_matches_single(self):
        assert jax.device_count() >= 8, "conftest should provide 8 CPU devices"
        state, box, const = init_sedov(16)  # 4096 particles / 8 devices
        cfg = make_cfg(state, box, const)

        # single-device reference
        ref_state, ref_box, ref_diag = step_hydro_std(state, box, cfg)

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg)
        out_state, out_box, out_diag = step(sstate, box)

        # the sharded result is the same physics
        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x), rtol=1e-5, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp), rtol=1e-4
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )

    def test_sharded_arrays_stay_sharded(self):
        state, box, const = init_sedov(16)
        cfg = make_cfg(state, box, const)
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg)
        out_state, _, _ = step(sstate, box)
        # a replicated array also spans 8 devices — assert the per-device
        # shard really is 1/8th of the rows
        shard_rows = out_state.x.addressable_shards[0].data.shape[0]
        assert shard_rows == out_state.x.shape[0] // 8, "output lost its 8-way sharding"

    def test_multiple_steps_stable(self):
        state, box, const = init_sedov(16)
        cfg = make_cfg(state, box, const)
        mesh = make_mesh(8)
        s = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg)
        for _ in range(3):
            s, box, d = step(s, box)
        assert np.all(np.isfinite(np.asarray(s.x)))
        assert float(d["dt"]) > 0

    def test_indivisible_count_rejected(self):
        state, box, const = init_sedov(15)  # 3375 not divisible by 8
        mesh = make_mesh(8)
        with pytest.raises(ValueError, match="not divisible"):
            shard_state(state, mesh)


@pytest.mark.slow
class TestShardedPallas:
    """The multi-chip FAST path: Mosaic engine per shard under shard_map
    (interpret mode on the CPU mesh), vs the single-device pallas step."""

    def test_sharded_pallas_matches_single(self):
        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, ref_diag = step_hydro_std(state, box, cfg)

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg)
        out_state, _, out_diag = step(sstate, box)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")

        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp), rtol=1e-4
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )
        assert int(out_diag["nc_max"]) == int(ref_diag["nc_max"])

    def test_sharded_pallas_multiple_steps(self):
        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg)
        sbox = box
        for _ in range(3):
            sstate, sbox, diag = step(sstate, sbox)
        assert np.isfinite(np.asarray(sstate.x)).all()
        assert float(diag["dt"]) > 0.0


@pytest.mark.slow
class TestShardedGravity:
    """Self-gravity under the sharded step (GSPMD partitioning; the
    replicated coarse tree matches the reference's replicated global
    octree, assignment.hpp:51-53)."""

    def test_sharded_gravity_matches_single(self):
        import dataclasses
        import jax.numpy as jnp

        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.propagator import step_hydro_ve
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(16)
        # trim the sphere cut to a mesh multiple (test-only)
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )

        sim = Simulation(state, box, const, prop="ve", block=512)
        ref_state, _, ref_diag = sim._launch()[:3]

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, sim._cfg, step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box, sim._gtree)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=5e-4, atol=5e-7,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-5
        )


@pytest.mark.slow
class TestHaloExchange:
    """The windowed all_to_all halo exchange (parallel/exchange.py):
    per-peer row windows instead of full-array replication — the
    exchange_halos.hpp analog, with comm volume asserted."""

    def test_measured_window_matches_full_slab_result(self):
        import numpy as np

        from sphexa_tpu.parallel import exchange as ex
        from sphexa_tpu.propagator import _sort_by_keys, step_hydro_std
        from sphexa_tpu.sfc.box import make_global_box

        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, _ = step_hydro_std(state, box, cfg)

        gbox = make_global_box(state.x, state.y, state.z, box)
        sstate0, keys, _ = _sort_by_keys(state, gbox, cfg.curve)
        wmax = ex.estimate_halo_window(
            sstate0.x, sstate0.y, sstate0.z, sstate0.h, keys, gbox,
            cfg.nbr, P=8,
        )
        S = state.n // 8
        assert 0 < wmax <= S

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, halo_window=wmax)
        out_state, _, out_diag = step(sstate, box)
        # exchanged rows per shard = (P-1) * wmax, never more than the
        # all_gather-equivalent; physics identical to the single-device step
        assert int(out_diag["occupancy"]) <= cfg.nbr.cap
        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x),
            rtol=1e-5, atol=1e-7,
        )

    def test_too_small_window_trips_sentinel(self):
        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        # a 64-row window cannot cover the candidate runs at this size:
        # the escape guard must flip the occupancy sentinel rather than
        # silently truncate
        step = make_sharded_step(mesh, cfg, halo_window=64)
        _, _, diag = step(sstate, box)
        assert int(diag["occupancy"]) > cfg.nbr.cap

    def test_window_scaling_shrinks_with_cell_depth(self):
        """The discovery produces windows that shrink relative to the
        slab as the grid refines (the O(surface) scaling property of the
        reference's halo lists, halos/halos.hpp)."""
        import dataclasses

        import numpy as np

        from sphexa_tpu.parallel import exchange as ex
        from sphexa_tpu.propagator import _sort_by_keys
        from sphexa_tpu.sfc.box import make_global_box

        state, box, const = init_sedov(24)
        cfg = make_propagator_config(state, box, const, block=512)
        gbox = make_global_box(state.x, state.y, state.z, box)
        sstate0, keys, _ = _sort_by_keys(state, gbox, cfg.curve)

        widths = []
        for level in (2, 3):
            nbr = dataclasses.replace(
                cfg.nbr, level=level, cap=4096, window=4, run_cap=0, gap=0,
            )
            widths.append(ex.estimate_halo_window(
                sstate0.x, sstate0.y, sstate0.z, sstate0.h, keys, gbox,
                nbr, P=8, margin=1.0, quantum=1,
            ))
        assert widths[1] <= widths[0]


@pytest.mark.slow
class TestShardedVE:
    """The flagship VE pipeline on the multi-chip fast path (VERDICT r2 #3):
    per-shard Mosaic kernels with windowed halos for the whole
    xmass->gradh->IAD->divv->AV->momentum sequence."""

    def test_sharded_ve_pallas_matches_single(self):
        import numpy as np

        from sphexa_tpu.propagator import step_hydro_ve

        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, ref_diag = step_hydro_ve(state, box, cfg)

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            np.asarray(out_state.alpha), np.asarray(ref_state.alpha),
            rtol=1e-4, atol=1e-6,
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )

    def test_sharded_ve_avclean_matches_single(self):
        import numpy as np

        from sphexa_tpu.propagator import step_hydro_ve

        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas", av_clean=True)
        ref_state, _, _ = step_hydro_ve(state, box, cfg)
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, step_fn=step_hydro_ve)
        out_state, _, _ = step(sstate, box)
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-4, atol=1e-6,
        )

    def test_sharded_turb_ve_matches_single(self):
        """turb-ve through the sharded stepper (VERDICT r3 #5): the VE
        force stage runs per-shard Mosaic kernels, the OU stirring is
        GSPMD-partitioned XLA, and the advanced TurbulenceState pytree is
        threaded through (turb_ve.hpp:53 runs under the full domain)."""
        from sphexa_tpu.propagator import step_turb_ve
        from sphexa_tpu.sph.hydro_turb import create_stirring_modes

        state, box, const = init_sedov(16)
        tcfg, turb = create_stirring_modes(lbox=1.0, st_max_modes=200)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, ref_diag, ref_turb = step_turb_ve(
            state, box, cfg, None, turb, tcfg
        )

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, step_fn=step_turb_ve,
                                 aux_cfg=tcfg)
        out_state, _, out_diag, out_turb = step(sstate, box, None, turb)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-4, atol=1e-6,
        )
        # the OU phase advance must agree exactly (same dt, same RNG path)
        np.testing.assert_allclose(
            np.asarray(out_turb.phases), np.asarray(ref_turb.phases),
            rtol=1e-6, atol=1e-9,
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )

    def test_sharded_std_cooling_matches_single(self):
        """std-cooling through the sharded stepper (VERDICT r3 #5): the
        per-particle ChemistryData rides the slab sharding and the
        in-step SFC sort (std_hydro_grackle.hpp:56)."""
        from sphexa_tpu.physics.cooling import ChemistryData, CoolingConfig
        from sphexa_tpu.propagator import step_hydro_std_cooling

        state, box, const = init_sedov(16)
        ccfg = CoolingConfig(gamma=const.gamma)
        chem = ChemistryData.ionized(state.n)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, ref_diag, ref_chem = step_hydro_std_cooling(
            state, box, cfg, None, chem, ccfg
        )

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        schem = shard_state(chem, mesh)
        step = make_sharded_step(mesh, cfg, step_fn=step_hydro_std_cooling,
                                 aux_cfg=ccfg)
        out_state, _, out_diag, out_chem = step(sstate, box, None, schem)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp),
            rtol=1e-4, atol=1e-7,
        )
        # chemistry stays aligned with the sorted state and slab-sharded
        assert out_chem.hi.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_chem.hi), np.asarray(ref_chem.hi),
            rtol=1e-5, atol=1e-8,
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )


@pytest.mark.slow
class TestShardedNbody:
    """Gravity-only N-body under the sharded step (the sharded-nbody
    coverage flagged in VERDICT r2 'What's weak' #9)."""

    def test_sharded_nbody_matches_single(self):
        import numpy as np

        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.propagator import step_nbody
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(16, overrides={"G": 1.0})
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )
        sim = Simulation(state, box, const, prop="nbody", block=512)
        ref_state, _, ref_diag = sim._launch()[:3]

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, sim._cfg, step_fn=step_nbody)
        out_state, _, out_diag = step(sstate, box, sim._gtree)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=5e-4, atol=5e-7,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-5
        )


@pytest.mark.slow
class TestShardedGravityFastPath:
    """Distributed gravity on the Pallas fast path: psum multipole
    upsweep (global_multipole.hpp analog) + near field through the
    windowed halo exchange — no particle-array replication."""

    def test_sharded_ve_gravity_pallas_matches_single(self):
        import numpy as np

        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.propagator import step_hydro_ve
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(16)
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )
        sim = Simulation(state, box, const, prop="ve", block=512,
                         backend="pallas")
        ref_state, _, ref_diag = sim._launch()[:3]

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, sim._cfg, step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box, sim._gtree)
        assert out_state.x.sharding.spec == jax.sharding.PartitionSpec("p")
        # the distributed upsweep sums leaf payloads in a different f32
        # order than the single-device pass; MAC-marginal nodes can flip
        # between M2P and descend, shifting a few particles' forces by
        # up to the theta-truncation error (~0.5% relative; measured
        # max |dvx| 2.6e-4 here). Energies and list sizes agree tightly.
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-2, atol=5e-4,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-4
        )
        # per-shard slabs end in PARTIAL tail blocks (mostly-duplicated
        # rows -> point-like bboxes) that legitimately accept more nodes
        # than any full single-device block — assert cap-boundedness (the
        # production overflow contract), not closeness
        assert int(out_diag["m2p_max"]) <= sim._cfg.gravity.m2p_cap
        assert int(out_diag["p2p_max"]) <= sim._cfg.gravity.p2p_cap

    def test_sharded_gravity_let_matches_single(self):
        """LET analog (VERDICT r4 #5): sharded solve classifying against
        the per-shard slab-bbox essential set (GravityConfig.let_cap)
        must match the full-tree sharded solve AND genuinely prune."""
        import dataclasses as dc

        import numpy as np

        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.propagator import step_hydro_ve
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(16)
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )
        sim = Simulation(state, box, const, prop="ve", block=512,
                         backend="pallas")
        ref_state, _, ref_diag = sim._launch()[:3]

        num_nodes = sim._cfg.grav_meta.num_nodes
        cfg_let = dc.replace(
            sim._cfg,
            gravity=dc.replace(sim._cfg.gravity, let_cap=num_nodes),
        )
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg_let, step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box, sim._gtree)
        # the essential set is ACTIVE (at this tiny tree the slab bbox
        # opens everything, so it equals the full tree; the at-scale
        # pruning is measured by scripts/measure_let.py: 2-3.4x at 1-4M)
        assert 0 < int(out_diag["let_max"]) <= num_nodes
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-2, atol=5e-4,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-4
        )

    def test_sharded_gravity_let_bitmask_matches_single(self):
        """ISSUE-1 sharded coverage: the let_cap path feeding the
        hierarchical bitmask-rank compaction (superblock pre-pass
        classifying against the slab essential list, per-block lists
        from gravity/pallas_compact.py) must match the single-device
        dense-sort solve within the same MAC-marginal tolerance as the
        sort-based sharded paths."""
        import dataclasses as dc

        import numpy as np

        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.propagator import step_hydro_ve
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(16)
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )
        sim = Simulation(state, box, const, prop="ve", block=512,
                         backend="pallas")
        ref_state, _, ref_diag = sim._launch()[:3]

        num_nodes = sim._cfg.grav_meta.num_nodes
        cfg_bm = dc.replace(
            sim._cfg,
            gravity=dc.replace(sim._cfg.gravity, let_cap=num_nodes,
                               compaction="bitmask", super_factor=2,
                               super_cap=num_nodes),
        )
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg_bm, step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box, sim._gtree)
        assert 0 < int(out_diag["let_max"]) <= num_nodes
        assert 0 < int(out_diag["c_max"]) <= num_nodes
        assert int(out_diag["compact_width"]) == num_nodes
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-2, atol=5e-4,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-4
        )


@pytest.mark.slow
class TestShardedEwaldSpherical:
    """VERDICT r3 #7: periodic (Ewald) gravity and spherical order-P
    multipoles on the sharded fast path — psum upsweep + windowed
    near-field halos (full-slab windows), equivalent to the
    single-device solves."""

    def _sharded_gravity(self, xs, ys, zs, ms, hs, skeys, box, gtree,
                         meta, cfg, ecfg=None, order=0):
        import dataclasses as dc
        import functools

        from jax.sharding import PartitionSpec as P

        from sphexa_tpu.gravity.ewald import compute_gravity_ewald
        from sphexa_tpu.gravity.traversal import (
            compute_gravity,
            compute_multipoles_sharded,
        )
        from sphexa_tpu.propagator import shard_map  # version-compat shim

        mesh = make_mesh(8)
        Pn = 8
        S = xs.shape[0] // Pn
        gcfg = dc.replace(cfg, use_pallas=True, multipole_order=order)

        def stage(x, y, z, m, h, keys):
            if ecfg is not None:
                gx, gy, gz, egrav, diag = compute_gravity_ewald(
                    x, y, z, m, h, keys, box, gtree, meta, gcfg, ecfg,
                    shard=("p", Pn, S),
                )
            else:
                mpc = compute_multipoles_sharded(
                    x, y, z, m, keys, gtree, meta, "p", order=order
                )
                gx, gy, gz, egrav, diag = compute_gravity(
                    x, y, z, m, h, keys, box, gtree, meta, gcfg,
                    mp_cache=mpc, shard=("p", Pn, S),
                )
            egrav = jax.lax.psum(egrav, "p")
            diag = {k: jax.lax.pmax(v, "p") for k, v in diag.items()}
            return gx, gy, gz, egrav, diag

        diag_keys = (
            ["m2p_max", "p2p_max", "leaf_occ", "c_max", "let_max",
             "compact_width"]
            if ecfg is not None
            else ["m2p_max", "p2p_max", "leaf_occ", "c_max", "let_max",
                  "compact_width", "mac_work_ratio"]
        )
        Pp, Pr = P("p"), P()
        fn = shard_map(
            stage, mesh=mesh,
            in_specs=(Pp, Pp, Pp, Pp, Pp, Pp),
            out_specs=(Pp, Pp, Pp, Pr, {k: Pr for k in diag_keys}),
            check_vma=False,
        )
        # under an outer jit like the production stepper: shard_map's
        # EAGER impl trips on a stale nested-jit cache entry when a
        # previous test traced compute_gravity inside another jit (JAX
        # "non-shard_map tracers" quirk; jitted programs are unaffected)
        return jax.jit(fn)(xs, ys, zs, ms, hs, skeys)

    def _random_setup(self, periodic, n=512, seed=7):
        import dataclasses as dc

        from sphexa_tpu.gravity.traversal import (
            GravityConfig,
            estimate_gravity_caps,
        )
        from sphexa_tpu.gravity.tree import build_gravity_tree
        from sphexa_tpu.sfc.box import BoundaryType, Box
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        rng = np.random.default_rng(seed)
        x, y, z = rng.uniform(-0.5, 0.5, (3, n)).astype(np.float32)
        m = rng.uniform(0.5, 1.5, n).astype(np.float32)
        bt = BoundaryType.periodic if periodic else BoundaryType.open
        box = Box.create(-0.5, 0.5, boundary=bt)
        keys = np.asarray(compute_sfc_keys(x, y, z, box))
        order = np.argsort(keys)
        xs, ys, zs, ms = (
            jnp.asarray(np.asarray(a)[order]) for a in (x, y, z, m)
        )
        skeys = jnp.asarray(keys[order])
        gtree, meta = build_gravity_tree(keys[order], bucket_size=32)
        cfg = estimate_gravity_caps(
            xs, ys, zs, ms, skeys, box, gtree, meta,
            GravityConfig(theta=0.6, bucket_size=32, G=1.0), margin=2.0,
        )
        hs = jnp.full_like(xs, 1e-3)
        return xs, ys, zs, ms, hs, skeys, box, gtree, meta, cfg

    def test_sharded_ewald_matches_single(self):
        import dataclasses as dc

        from sphexa_tpu.gravity.ewald import (
            EwaldConfig,
            compute_gravity_ewald,
        )

        (xs, ys, zs, ms, hs, skeys, box, gtree, meta,
         cfg) = self._random_setup(periodic=True)
        ecfg = EwaldConfig()
        # single-device reference on the same engine path (interpret)
        rcfg = dc.replace(cfg, use_pallas=True)
        rax, ray, raz, regrav, _ = compute_gravity_ewald(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, rcfg, ecfg
        )
        ax, ay, az, egrav, diag = self._sharded_gravity(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, cfg, ecfg=ecfg
        )
        # psum upsweep reorders f32 leaf sums: MAC-marginal flips bound
        # the tolerance (same argument as TestShardedGravityFastPath)
        np.testing.assert_allclose(
            np.asarray(ax), np.asarray(rax), rtol=1e-2, atol=2e-3 * float(
                jnp.max(jnp.abs(rax)))
        )
        np.testing.assert_allclose(
            float(egrav), float(regrav), rtol=1e-4
        )
        assert int(diag["p2p_max"]) <= cfg.p2p_cap

    def test_sharded_spherical_matches_single(self):
        import dataclasses as dc

        from sphexa_tpu.gravity.traversal import compute_gravity

        (xs, ys, zs, ms, hs, skeys, box, gtree, meta,
         cfg) = self._random_setup(periodic=False)
        order = 4
        rcfg = dc.replace(cfg, use_pallas=True, multipole_order=order)
        rax, ray, raz, regrav, _ = compute_gravity(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, rcfg
        )
        ax, ay, az, egrav, diag = self._sharded_gravity(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, cfg, order=order
        )
        np.testing.assert_allclose(
            np.asarray(ax), np.asarray(rax), rtol=1e-2, atol=2e-3 * float(
                jnp.max(jnp.abs(rax)))
        )
        np.testing.assert_allclose(
            float(egrav), float(regrav), rtol=1e-4
        )
        assert int(diag["m2p_max"]) <= cfg.m2p_cap


@pytest.mark.slow
class TestGravityMacWindows:
    """r13 gravity comm diet: the MAC-need-sized sparse near-field serve
    (sizing.device_gravity_halo feeding compute_gravity's cell-granular
    exchange through cfg.grav_cells) pinned equal to the single-device
    solve — std and ve open-boundary runs at P=2/P=4, plus the periodic
    Ewald path — with the same MAC-marginal f32 tolerance as the
    round-3 LET tests. grav_cells=() (the grav_window=0 fallback) must
    stay byte-identical to the pre-sizing full-slab lowering."""

    @staticmethod
    def _evrard_sim(prop, theta=0.8):
        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(20)
        n16 = (state.n // 16) * 16
        state = jax.tree.map(
            lambda a: a[:n16] if getattr(a, "ndim", 0) == 1 else a, state
        )
        # theta=0.8: the first MAC where the per-distance needs are
        # genuinely partial at this size (caps (1048, 768, 1048) vs the
        # full-slab 3*1048 at P=4 — docs/NEXT.md round 13); tighter
        # thetas open every remote leaf and the test would silently
        # degenerate to full slabs
        sim = Simulation(state, box, const, prop=prop, block=512,
                         backend="pallas", theta=theta)
        return state, sim

    @staticmethod
    def _mac_cells(state, sim, P, shifts=None):
        from sphexa_tpu.parallel.sizing import device_gravity_halo
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        keys = compute_sfc_keys(state.x, state.y, state.z, sim.box,
                                curve=sim.curve)
        order = jnp.argsort(keys)
        xs, ys, zs, ms = (
            a[order] for a in (state.x, state.y, state.z, state.m)
        )
        return device_gravity_halo(
            xs, ys, zs, ms, keys[order], sim.box, sim._gtree,
            sim._cfg.grav_meta, theta=sim.theta, P=P, shifts=shifts,
        )

    @pytest.mark.parametrize("P", [2, 4])
    @pytest.mark.parametrize("prop", ["std", "ve"])
    def test_sparse_near_field_matches_single(self, P, prop):
        from sphexa_tpu.propagator import step_hydro_std, step_hydro_ve

        step_fn = step_hydro_ve if prop == "ve" else step_hydro_std
        state, sim = self._evrard_sim(prop)
        ref_state, _, ref_diag = sim._launch()[:3]

        cells = self._mac_cells(state, sim, P)
        S = state.n // P
        assert len(cells) == P - 1
        if P == 4:
            # regime check: the serve must ship strictly less than the
            # retired full-slab exchange, or the test proves nothing
            assert sum(cells) < (P - 1) * S, (cells, S)
        mesh = make_mesh(P)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, sim._cfg, step_fn=step_fn,
                                 grav_cells=cells)
        out_state, _, out_diag = step(sstate, sim.box, sim._gtree)
        # cap-bounded, NOT cap+1: the MAC-sized caps were sufficient and
        # the escape sentinel stayed quiet (the monotone-MAC guarantee)
        assert int(out_diag["p2p_max"]) <= sim._cfg.gravity.p2p_cap
        np.testing.assert_allclose(
            np.asarray(out_state.vx), np.asarray(ref_state.vx),
            rtol=1e-2, atol=5e-4,
        )
        np.testing.assert_allclose(
            float(out_diag["egrav"]), float(ref_diag["egrav"]), rtol=1e-4
        )

    @pytest.mark.parametrize("P", [2, 4])
    def test_sparse_ewald_matches_single(self, P):
        """Periodic path: the sized caps must union the opened set over
        the Ewald replica shells (a shifted target slab reaches
        wrap-around leaves the base pass never opens), so the sparse
        serve under compute_gravity_ewald stays equal to the
        single-device Ewald solve."""
        import dataclasses as dc
        from itertools import product

        from sphexa_tpu.gravity.ewald import (
            EwaldConfig,
            compute_gravity_ewald,
        )
        from sphexa_tpu.parallel.sizing import device_gravity_halo
        from sphexa_tpu.propagator import shard_map

        from jax.sharding import PartitionSpec as PSpec

        helper = TestShardedEwaldSpherical()
        (xs, ys, zs, ms, hs, skeys, box, gtree, meta,
         cfg) = helper._random_setup(periodic=True)
        ecfg = EwaldConfig()
        r = ecfg.num_replica_shells
        shells = np.array(
            [sh for sh in product(range(-r, r + 1), repeat=3)], np.float32
        )
        shifts = jnp.asarray(shells) * box.lengths[0]
        cells = device_gravity_halo(
            xs, ys, zs, ms, skeys, box, gtree, meta,
            theta=cfg.theta, P=P, shifts=shifts,
        )
        S = xs.shape[0] // P
        assert len(cells) == P - 1 and max(cells) <= S

        rcfg = dc.replace(cfg, use_pallas=True)
        rax, _, _, regrav, _ = compute_gravity_ewald(
            xs, ys, zs, ms, hs, skeys, box, gtree, meta, rcfg, ecfg
        )

        mesh = make_mesh(P)

        def stage(x, y, z, m, hh, keys):
            gx, gy, gz, egrav, diag = compute_gravity_ewald(
                x, y, z, m, hh, keys, box, gtree, meta, rcfg, ecfg,
                shard=("p", P, tuple(cells)),
            )
            # per-shard serve telemetry is the driver's concern, not this
            # equality pin
            diag.pop("halo_rows", None)
            diag.pop("halo_occ", None)
            diag.pop("halo_runs", None)
            egrav = jax.lax.psum(egrav, "p")
            diag = {k: jax.lax.pmax(v, "p") for k, v in diag.items()}
            return gx, gy, gz, egrav, diag

        diag_keys = ["m2p_max", "p2p_max", "leaf_occ", "c_max",
                     "let_max", "compact_width"]
        Pp, Pr = PSpec("p"), PSpec()
        fn = shard_map(
            stage, mesh=mesh,
            in_specs=(Pp, Pp, Pp, Pp, Pp, Pp),
            out_specs=(Pp, Pp, Pp, Pr, {k: Pr for k in diag_keys}),
            check_vma=False,
        )
        ax, ay, az, egrav, diag = jax.jit(fn)(xs, ys, zs, ms, hs, skeys)
        assert int(diag["p2p_max"]) <= cfg.p2p_cap
        np.testing.assert_allclose(
            np.asarray(ax), np.asarray(rax), rtol=1e-2,
            atol=2e-3 * float(jnp.max(jnp.abs(rax))),
        )
        np.testing.assert_allclose(float(egrav), float(regrav), rtol=1e-4)

    def test_full_slab_lowering_byte_identical(self):
        """The grav_window=0 contract: an empty grav_cells lowers the
        sharded step to byte-identical StableHLO as a config that never
        saw the sizing pass (win stays the int S full-slab window), while
        a sparse cap tuple genuinely changes the program.

        The raw ``as_text()`` comparison here is THE canonicalizer
        guard: every other lowering-identity pin in the repo (this
        class included, below) goes through the jaxdiff fingerprint,
        and this one byte-level assert is what proves the fingerprint
        is not hashing away a real difference.
        """
        from sphexa_tpu.devtools.audit.lowerdiff import fingerprint_callable
        from sphexa_tpu.propagator import step_hydro_ve

        state, sim = self._evrard_sim("ve")
        mesh = make_mesh(4)
        sstate = shard_state(state, mesh)
        base = make_sharded_step(mesh, sim._cfg, step_fn=step_hydro_ve)
        zero = make_sharded_step(mesh, sim._cfg, step_fn=step_hydro_ve,
                                 grav_cells=())
        lower = lambda st: st._jitted.lower(
            sstate, sim.box, sim._gtree, None).as_text()
        text_base = lower(base)
        text_zero = lower(zero)
        assert text_base == text_zero
        # the fingerprint helper must agree with the byte-level verdict
        # in both directions: identical programs collide, a genuinely
        # different program (sparse caps) does not
        fprint = lambda st: fingerprint_callable(
            st._jitted, sstate, sim.box, sim._gtree, None)
        fp_base = fprint(base)
        assert fprint(zero).digest == fp_base.digest
        cells = self._mac_cells(state, sim, 4)
        sparse = make_sharded_step(mesh, sim._cfg, step_fn=step_hydro_ve,
                                   grav_cells=cells)
        assert lower(sparse) != text_base
        assert fprint(sparse).digest != fp_base.digest


@pytest.mark.slow
class TestSimulationMesh:
    """Multi-chip through the Simulation driver (num_devices): the same
    loop, reconfiguration and overflow recovery as single-chip, with the
    halo window sized and escalated like the neighbor caps."""

    def test_simulation_num_devices_matches_single(self):
        """Runs in a SUBPROCESS: after many sharded programs have been
        compiled in one process, the oversubscribed XLA:CPU mesh can
        cross-route collective executables (buffer-count mismatch) — a
        test-harness artifact; a fresh process shows the real behavior
        (jax.clear_caches() does not clear the collective registry)."""
        from conftest import run_mesh_subprocess

        code = """
            import numpy as np

            from sphexa_tpu.init import init_sedov
            from sphexa_tpu.simulation import Simulation

            state, box, const = init_sedov(16)
            ref = Simulation(state, box, const, prop="std", block=512,
                             backend="pallas")
            for _ in range(3):
                ref.step()

            sim = Simulation(state, box, const, prop="std", block=512,
                             backend="pallas", num_devices=8)
            assert sim._mesh is not None and sim._mesh.size == 8
            for _ in range(3):
                d = sim.step()
            assert d["reconfigured"] == 0.0
            np.testing.assert_allclose(
                np.asarray(sim.state.x), np.asarray(ref.state.x),
                rtol=1e-5, atol=1e-7,
            )
            rows = sim.state.x.addressable_shards[0].data.shape[0]
            assert rows == state.n // 8
            print("SIM-MESH-OK")
        """
        out = run_mesh_subprocess(code, timeout=600)
        assert "SIM-MESH-OK" in out.stdout, out.stderr[-2000:]

    def test_undersized_grav_window_sentinel_retries_to_full(self):
        """Seeded under-sized gravity window: the sparse serve's escape
        sentinel (p2p_cap + 1, the shared overflow contract) must fire,
        the driver must regrow the MAC-need margin and replay the step,
        and the retry must converge to the full-slab ceiling — a wrong
        window surfaces as a reconfigure, never as wrong physics."""
        from conftest import run_mesh_subprocess

        code = """
            import numpy as np
            import jax

            from sphexa_tpu.init import init_evrard
            from sphexa_tpu.simulation import Simulation

            state, box, const = init_evrard(12)
            n8 = (state.n // 8) * 8
            state = jax.tree.map(
                lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a,
                state)
            sim = Simulation(state, box, const, prop="ve", block=512,
                             backend="pallas", num_devices=2,
                             grav_window=64)
            # undersize the MAC-need margin far below 1 and reconfigure:
            # the serve must escape, not silently drop remote rows
            sim._grav_halo_margin = 0.05
            sim._configure(reason="test-undersize")
            S = state.n // 2
            assert max(sim._grav_cells) < S, sim._grav_cells
            d = sim.step()
            trips = sim.telemetry.counters.get("grav_halo_trips", 0)
            assert trips >= 1, trips
            assert d["reconfigured"] == 1.0
            assert max(sim._grav_cells) == S, (sim._grav_cells, S)
            ref = Simulation(state, box, const, prop="ve", block=512,
                             backend="pallas")
            ref.step()
            np.testing.assert_allclose(
                np.asarray(sim.state.vx), np.asarray(ref.state.vx),
                rtol=1e-2, atol=5e-4)
            print("GRAV-SENTINEL-OK")
        """
        out = run_mesh_subprocess(code, timeout=900)
        assert "GRAV-SENTINEL-OK" in out.stdout, out.stderr[-2000:]

    def test_simulation_num_devices_indivisible_rejected(self):
        import pytest

        from sphexa_tpu.simulation import Simulation

        state, box, const = init_sedov(15)  # 3375 % 8 != 0
        with pytest.raises(ValueError, match="not divisible"):
            Simulation(state, box, const, num_devices=8)


class TestDeviceSizing:
    """O(N/P) reconfiguration (VERDICT r3 #3): multi-device sizing runs as
    jitted device reductions; only scalars, O(#cells) histograms and
    O(tree) arrays reach the host. The reference's counterpart is the
    allreduce-incremental tree count (update_mpi.hpp:26-106) + rank-local
    assignment (assignment.hpp:84-122)."""

    def test_pyramid_tree_matches_host_build(self):
        from sphexa_tpu.parallel.sizing import leaf_array_from_device_keys
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        from sphexa_tpu.tree.csarray import compute_octree

        state, box, const = init_sedov(16)
        keys = compute_sfc_keys(state.x, state.y, state.z, box)
        ref, _ = compute_octree(
            np.sort(np.asarray(keys, np.uint64)), bucket_size=64
        )
        # unsorted device keys: the histogram build never needs the sort
        got = leaf_array_from_device_keys(keys, bucket_size=64)
        np.testing.assert_array_equal(got, ref)

    def test_pyramid_tree_matches_host_build_clustered(self):
        # deep drill-down coverage: a tight cluster forces refinement well
        # past the base histogram level
        from sphexa_tpu.parallel.sizing import leaf_array_from_device_keys
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        from sphexa_tpu.tree.csarray import compute_octree
        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        n = 20000
        # half uniform, half in a 1e-3-wide cluster
        pts = np.concatenate([
            rng.uniform(0, 1, (n // 2, 3)),
            0.5 + 1e-3 * rng.uniform(0, 1, (n // 2, 3)),
        ])
        state, box, const = init_sedov(8)
        keys = compute_sfc_keys(
            jnp.asarray(pts[:, 0], jnp.float32),
            jnp.asarray(pts[:, 1], jnp.float32),
            jnp.asarray(pts[:, 2], jnp.float32), box)
        ref, _ = compute_octree(
            np.sort(np.asarray(keys, np.uint64)), bucket_size=64
        )
        got = leaf_array_from_device_keys(keys, bucket_size=64)
        np.testing.assert_array_equal(got, ref)

    def test_pyramid_tree_matches_host_build_evrard_wrap_outlier(self):
        """Evrard-shaped centrally-condensed keys PLUS particles pinned
        to both box corners: the far corner's key is the curve maximum —
        the Hilbert wrap case where the last drill-down bucket's upper
        edge is the end of key space. Device build must equal the host
        oracle exactly: leaf array AND the full linkage/geometry the
        driver's (now device-only) _configure_gravity consumes."""
        from sphexa_tpu.gravity.tree import (
            build_gravity_tree,
            linkage_from_leaves,
        )
        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.parallel.sizing import leaf_array_from_device_keys
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        import jax.numpy as jnp

        state, box, const = init_evrard(12)
        x = np.asarray(state.x).copy()
        y = np.asarray(state.y).copy()
        z = np.asarray(state.z).copy()
        lo = np.asarray(box.lo)
        hi = lo + np.asarray(box.lengths)
        x[0], y[0], z[0] = lo
        x[1], y[1], z[1] = hi
        keys = compute_sfc_keys(
            jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
            jnp.asarray(z, jnp.float32), box)
        ref_tree, ref_meta = build_gravity_tree(
            np.sort(np.asarray(keys, np.uint64)), bucket_size=64
        )
        leaf = leaf_array_from_device_keys(keys, bucket_size=64)
        got_tree, got_meta = linkage_from_leaves(leaf)
        assert got_meta == ref_meta
        for f in ("leaf_keys", "parent", "is_leaf", "leaf_of_node",
                  "node_of_leaf", "center_frac", "halfsize_frac"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got_tree, f)),
                np.asarray(getattr(ref_tree, f)), err_msg=f)

    def test_simulation_tree_build_matches_host_oracle(self):
        """The driver's ONLY gravity-tree build is the device pyramid
        (r13, single- and multi-device alike): its configured tree must
        equal the host-numpy build_gravity_tree oracle on the same keys."""
        from sphexa_tpu.gravity.tree import build_gravity_tree
        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(12, overrides={"G": 1.0})
        sim = Simulation(state, box, const, prop="nbody", backend="xla")
        keys = compute_sfc_keys(state.x, state.y, state.z, sim.box,
                                curve=sim.curve)
        ref_tree, ref_meta = build_gravity_tree(
            np.sort(np.asarray(keys, np.uint64)),
            bucket_size=sim.grav_bucket, curve=sim.curve)
        assert sim._cfg.grav_meta == ref_meta
        np.testing.assert_array_equal(
            np.asarray(sim._gtree.leaf_keys),
            np.asarray(ref_tree.leaf_keys))
        np.testing.assert_array_equal(
            np.asarray(sim._gtree.parent), np.asarray(ref_tree.parent))

    def test_single_device_ignores_grav_window(self):
        """The grav_window knob only gates the multi-device sizing pass:
        a single-device run must size no gravity halo caps and launch
        the identical executable whatever its value."""
        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(12, overrides={"G": 1.0})
        a = Simulation(state, box, const, prop="nbody", backend="xla",
                       grav_window=0)
        b = Simulation(state, box, const, prop="nbody", backend="xla",
                       grav_window=512)
        assert a._grav_cells == () and b._grav_cells == ()
        assert a._launch_signature(False) == b._launch_signature(False)

    def test_sizing_stats_matches_host(self):
        from sphexa_tpu.parallel import sizing
        from sphexa_tpu import native
        from sphexa_tpu.neighbors.cell_list import pad_cap

        state, box, const = init_sedov(12)
        level, group = 3, 64
        occ, ext = jax.device_get(sizing.sizing_stats(
            state.x, state.y, state.z, box, level, group
        ))
        xa, ya, za = (np.asarray(a) for a in (state.x, state.y, state.z))
        keys = native.compute_keys(
            xa, ya, za, np.asarray(box.lo), np.asarray(box.lengths),
            "hilbert")
        order = native.argsort_keys(keys)
        assert int(occ) == native.max_cell_occupancy(keys[order], level)
        ref_ext = native.group_extents(xa, ya, za, order, group)
        np.testing.assert_allclose(np.asarray(ext), ref_ext, rtol=1e-6)

    def test_device_halo_window_matches_host(self):
        from sphexa_tpu.parallel.exchange import estimate_halo_window
        from sphexa_tpu.parallel.sizing import device_halo_window
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        from sphexa_tpu.simulation import make_propagator_config

        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512)
        keys = compute_sfc_keys(state.x, state.y, state.z, box)
        order = np.argsort(np.asarray(keys))
        xs = jnp.asarray(np.asarray(state.x)[order])
        ys = jnp.asarray(np.asarray(state.y)[order])
        zs = jnp.asarray(np.asarray(state.z)[order])
        hs = jnp.asarray(np.asarray(state.h)[order])
        sk = jnp.asarray(np.asarray(keys)[order])
        ref = estimate_halo_window(xs, ys, zs, hs, sk, box, cfg.nbr, P=8)
        got = device_halo_window(state.x, state.y, state.z, state.h,
                                 keys, box, cfg.nbr, P=8)
        assert got == ref

    def test_mesh_configure_transfers_o_n_over_p(self):
        """The VERDICT 'Done' gate: a num_devices=8 gravity run's
        (re)configure moves O(N/P) bytes to the host — asserted with the
        sizing transfer counter, under a device-to-host transfer guard so
        any stray implicit full-array gather fails the test."""
        from sphexa_tpu.init import init_evrard
        from sphexa_tpu.parallel import sizing
        from sphexa_tpu.simulation import Simulation

        state, box, const = init_evrard(12, overrides={"G": 1.0})
        n8 = (state.n // 8) * 8
        state = jax.tree.map(
            lambda a: a[:n8] if getattr(a, "ndim", 0) == 1 else a, state
        )
        sizing.reset_transfer_bytes()
        # tripwire: on the CPU mesh the jax transfer guard is inert
        # (host arrays are zero-copy), so catch unmetered full-array
        # gathers by intercepting numpy coercion of large jax arrays —
        # every legitimate fetch in the device-sizing path goes through
        # sizing.fetch (which yields numpy before np.asarray sees it)
        import unittest.mock as mock

        real_asarray = np.asarray
        limit = state.x.nbytes // 4  # anything >= N/4 rows is a gather

        def guarded(a, *args, **kw):
            if isinstance(a, jax.Array) and a.nbytes >= limit:
                raise AssertionError(
                    f"unmetered device->host gather of {a.nbytes} bytes"
                )
            return real_asarray(a, *args, **kw)

        with mock.patch("numpy.asarray", side_effect=guarded), \
                jax.transfer_guard_device_to_host("disallow"):
            sim = Simulation(state, box, const, prop="nbody",
                             num_devices=8, backend="xla")
        state_bytes = sum(
            a.nbytes for a in jax.tree.leaves(sim.state)
            if hasattr(a, "nbytes")
        )
        # O(N/P) + O(#cells + tree): generous constant, but far below the
        # full-state gather the host path would need
        budget = state_bytes // 8 + 2_000_000
        assert sizing.TRANSFER_BYTES < budget, (
            sizing.TRANSFER_BYTES, budget
        )


class TestSparseHaloExchange:
    """Sparse cell-granular halo exchange (shard_halo_stage_sparse): comm
    volume tracks the halo SURFACE via per-distance ppermute buffers — the
    exchangeHalos analog (exchange_halos.hpp:43-119) replacing the
    contiguous windows that measured degenerate (Wmax = S at every size,
    docs/NEXT.md round-4). These tests run at 40^3 where the per-distance
    needs are genuinely partial (VERDICT r4 weak #5): max cap < S and the
    total is ~5.6 slabs vs the windowed path's degenerate 7."""

    @staticmethod
    def _sparse_caps(state, box, nbr, P=8):
        from sphexa_tpu.parallel.sizing import device_sparse_halo
        from sphexa_tpu.sfc.box import make_global_box
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        gbox = make_global_box(state.x, state.y, state.z, box)
        keys = compute_sfc_keys(state.x, state.y, state.z, gbox)
        return device_sparse_halo(
            state.x, state.y, state.z, state.h, keys, gbox, nbr, P=P
        )[0]

    def test_sizing_volume_tracks_surface(self):
        """The sized per-distance caps ship strictly less than the
        all_gather-equivalent volume, with at least one genuinely
        partial distance — the regime the windowed path never reached."""
        state, box, const = init_sedov(40)  # 64000 / 8
        cfg = make_cfg(state, box, const)
        hc = self._sparse_caps(state, box, cfg.nbr)
        S = -(-state.n // 8)
        assert len(hc) == 7
        assert sum(hc) < 0.85 * 7 * S, (hc, S)
        assert min(hc) < 0.6 * S, (hc, S)

    def test_sparse_std_matches_single_partial_windows(self):
        """One std step, 8 shards, sparse exchange in the partial-cap
        regime vs the single-device step.

        Also the regression pin for the XLA:CPU collective-rendezvous
        race (this container's jax 0.4.x): the sparse stage issues ~P^2
        mutually independent collectives (P-1 ppermutes per serve x 3
        serves + gathers/psums), and unchained they could rendezvous in
        different orders across the oversubscribed virtual devices —
        every shard's coverage/need then collapsed to shard 0's values,
        tripping the escape sentinel with ZERO drift (occupancy ==
        cap+1, the historical failure of this test) and NaN-ing the
        positions. exchange.chain_after now pins one total order; the
        per-shard telemetry assertions below would fail first under any
        recurrence (the race's signature: all shards reporting shard
        0's need row)."""
        import dataclasses

        from sphexa_tpu.parallel import sizing
        from sphexa_tpu.propagator import step_hydro_std
        from sphexa_tpu.sfc.box import make_global_box
        from sphexa_tpu.sfc.keys import compute_sfc_keys

        state, box, const = init_sedov(40)
        cfg = make_propagator_config(state, box, const, backend="pallas")
        ref_state, _, ref_diag = step_hydro_std(state, box, cfg)

        hc = self._sparse_caps(state, box, cfg.nbr)
        S = -(-state.n // 8)
        assert max(hc) < S, "regime check: caps must be partial"
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, halo_cells=hc)
        out_state, _, out_diag = step(sstate, box)
        assert int(out_diag["occupancy"]) <= cfg.nbr.cap
        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp),
            rtol=1e-4,
        )
        np.testing.assert_allclose(
            float(out_diag["dt"]), float(ref_diag["dt"]), rtol=1e-5
        )
        # per-shard exchange telemetry (SHARD_DIAG_KEYS) vs the sizing
        # pass's independently computed need matrix — the schema-v2
        # exchange-event acceptance check AND the rendezvous-race canary
        gbox = make_global_box(state.x, state.y, state.z, box)
        keys = compute_sfc_keys(state.x, state.y, state.z, gbox)
        nbr = cfg.nbr
        if nbr.run_cap > S:
            nbr = dataclasses.replace(nbr, run_cap=S)
        need = np.asarray(jax.device_get(sizing.sparse_need_matrix(
            state.x, state.y, state.z, state.h, keys, gbox, nbr, 8)))
        expected_rows = [int(need[k].sum() - need[k, k]) for k in range(8)]
        rows = np.asarray(out_diag["shard_rows"])
        assert rows.tolist() == expected_rows
        assert len(set(rows.tolist())) > 1  # genuinely per-shard
        occ = np.asarray(out_diag["shard_occ"])
        assert occ.shape == (8,) and float(occ.max()) <= 1.0 + 1e-6
        work = np.asarray(out_diag["shard_work"])
        assert work.shape == (8,) and (work > 0).all()
        assert np.asarray(out_diag["shard_trips"]).sum() == 0

    def test_sparse_escape_sentinel_trips(self):
        """Undersized per-distance caps must surface as the occupancy
        cap+1 sentinel (the shared overflow contract), not wrong physics."""
        from sphexa_tpu.propagator import step_hydro_std

        state, box, const = init_sedov(16)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, halo_cells=(64,) * 7)
        _, _, diag = step(sstate, box)
        assert int(diag["occupancy"]) == cfg.nbr.cap + 1

    @pytest.mark.slow
    def test_sparse_ve_matches_single_512k(self):
        """VERDICT r4 next #2 'Done' gate: equivalence AND exchanged-row
        volume in a genuinely-partial regime at 512k/8 (the size where
        the sparse need measured 1.27 slabs and shrinking)."""
        from sphexa_tpu.propagator import step_hydro_ve

        state, box, const = init_sedov(80)  # 512000 / 8
        cfg = make_propagator_config(state, box, const, backend="pallas")
        hc = self._sparse_caps(state, box, cfg.nbr)
        S = -(-state.n // 8)
        # volume: the padded total must stay well under all_gather volume
        # (measured 2.50 slabs vs 7 at this size)
        assert sum(hc) < 0.45 * 7 * S, (hc, S)
        ref_state, _, _ = step_hydro_ve(state, box, cfg)
        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        step = make_sharded_step(mesh, cfg, halo_cells=hc,
                                 step_fn=step_hydro_ve)
        out_state, _, out_diag = step(sstate, box)
        assert int(out_diag["occupancy"]) <= cfg.nbr.cap
        np.testing.assert_allclose(
            np.asarray(out_state.x), np.asarray(ref_state.x),
            rtol=1e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp),
            rtol=1e-3, atol=1e-6,
        )


class TestShardedEvolvedChemistry:
    """VERDICT r4 #6 'Done' gate: the 6-species network evolves INSIDE
    the sharded std-cooling step (cooler.cpp solve_chemistry under the
    full domain) and matches the single-device run."""

    def test_sharded_evolved_species_match_single(self):
        from sphexa_tpu.physics.cooling import ChemistryData, CoolingConfig
        from sphexa_tpu.propagator import step_hydro_std_cooling

        state, box, const = init_sedov(16)
        ccfg = CoolingConfig(gamma=const.gamma, evolve_species=True)
        chem = ChemistryData.ionized(state.n)
        cfg = make_propagator_config(state, box, const, block=512,
                                     backend="pallas")
        ref_state, _, _, ref_chem = step_hydro_std_cooling(
            state, box, cfg, None, chem, ccfg
        )
        # the network actually moved the fractions off the ionized IC
        assert float(jnp.max(jnp.abs(ref_chem.hi - chem.hi))) > 0.0

        mesh = make_mesh(8)
        sstate = shard_state(state, mesh)
        schem = shard_state(chem, mesh)
        step = make_sharded_step(mesh, cfg, step_fn=step_hydro_std_cooling,
                                 aux_cfg=ccfg)
        out_state, _, _, out_chem = step(sstate, box, None, schem)
        assert out_chem.hi.sharding.spec == jax.sharding.PartitionSpec("p")
        np.testing.assert_allclose(
            np.asarray(out_chem.hi), np.asarray(ref_chem.hi),
            rtol=1e-5, atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(out_chem.e), np.asarray(ref_chem.e),
            rtol=1e-5, atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(out_state.temp), np.asarray(ref_state.temp),
            rtol=1e-4, atol=1e-7,
        )
