"""A run of the persistent pair lists is a tile (PR 40), on ONE case: the
fixtures and the tests that tests/test_pair_list_tiles.py (``noh``) and
tests/test_pair_list_tiles_sedov.py (``sedov``) each run under their own
``CASE`` (not collected itself; one file held both until PR 41).

The build cuts the pruned runs to ``LIST_RUN_ROWS`` chunks
(pair_lists._prune_empty_chunks) and the list kernel (the walk; every
list op since PR 43) fetches exactly that many rows a run through a ring
of ``LIST_RING`` tiles. The parent's shape is the same code
at 13 rows (the un-cut runs' width at these sizes) and a ring of two:
everything here is held BITWISE to it, INTERPRET mode. (The prune against
plain loops, at several tiles:
pair_list_cases.py::test_prune_matches_a_plain_loop.) Its own files, so
that it runs beside the test_pair_lists files and not behind them."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from sphexa_tpu.sph import pallas_pairs as pp

import pair_list_cases
from pair_list_cases import CASE_FIXTURE, case  # noqa: F401  (the fixture)

PARENT_SHAPE = {"LIST_RUN_ROWS": 13, "LIST_RING": 2}


def _build(case, **shape):
    """The case's lists under patched tile constants (``shape``)."""
    with pytest.MonkeyPatch.context() as mp:
        for k, v in shape.items():
            mp.setattr(pp, k, v)
        return pair_list_cases._build(case)[0]


@pytest.fixture(scope="module")
def built(case):
    return _build(case)


@pytest.fixture(scope="module")
def built_uncut(case):
    return _build(case, **PARENT_SHAPE)


def _runs(lists):
    """Per group: [(start, len, chunks)] of its live runs."""
    s, ln, nc = (np.asarray(a) for a in (
        lists.ranges.starts, lists.ranges.lens, lists.ranges.ncells))
    return [[(int(s[g, w]), int(ln[g, w]),
              (int(s[g, w]) % 128 + int(ln[g, w]) + 127) // 128)
             for w in range(nc[g])] for g in range(s.shape[0])]


def _glued(runs):
    """[start, end) of the runs with those that abut at a row boundary
    joined: what the runs admit, however a stretch was cut."""
    out = []
    for s, ln, _ in runs:
        if out and out[-1][1] == s and s % 128 == 0:
            out[-1][1] = s + ln
        else:
            out.append([s, s + ln])
    return out


def test_recut_runs_are_tiles_of_the_uncut_ones(case, built, built_uncut):
    """(i) every run streams at most LIST_RUN_ROWS chunks; the particles
    the runs admit and the sequence of kept rows are the un-cut prune's;
    everything indexed by the chunk sequence is equal to the row."""
    ss, keys, box, const, nbr = case
    lists = built
    rr = pp.list_run_rows(nbr)
    assert rr == pp.LIST_RUN_ROWS < pp._dma_rows(nbr.dma_cap) == 13
    for name in ("cnt", "fill", "emit", "tail", "gidx", "seg",
                 "slots_live", "chunks_live", "slot_need", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(lists, name)),
                                      np.asarray(getattr(built_uncut, name)))
    cut, uncut = _runs(lists), _runs(built_uncut)
    assert int(lists.runs_live) == sum(map(len, cut))
    assert int(lists.chunks_live) == int((np.asarray(lists.cnt) > 0).sum())
    longest = 0
    for g, (a, b) in enumerate(zip(cut, uncut)):
        assert all(0 < c <= rr for _, _, c in a)
        rows = lambda runs: [r for s, _, c in runs
                             for r in range(s // 128, s // 128 + c)]
        assert rows(a) == rows(b), g
        # the union of [start, start + len): a stretch's pieces abut (an
        # un-cut run may itself start where the one before ends: two
        # original runs are never merged, so both sides are glued alike)
        assert _glued(a) == _glued(b), g
        longest = max([longest] + [c for _, _, c in b])
    # the cases hold what the cut has to get right: a stretch longer than
    # two tiles, and a last run whose tile ends in the j-table's pad
    assert longest > 2 * rr
    n_rows = -(-ss.x.shape[0] // 128)
    assert max(s // 128 + rr for runs in cut for s, _, _ in runs) > n_rows


@pytest.fixture(scope="module")
def inputs(case):
    """What the later ops read, from the streamed engine: each list op is
    compared on equal inputs."""
    ss, keys, box, const, nbr = case
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    from sphexa_tpu.sph.hydro_std import compute_eos_std
    from sphexa_tpu.sph.hydro_ve import compute_eos_ve

    rho, nc, _ = pp.pallas_density(x, y, z, h, m, keys, box, const, nbr,
                                   interpret=True)
    p, c = compute_eos_std(ss.temp, rho, const)
    cs, _ = pp.pallas_iad(x, y, z, h, m / rho, keys, box, const, nbr,
                          interpret=True)
    xm = m / rho
    (kx, gradh), _ = pp.pallas_ve_def_gradh(x, y, z, h, m, xm, keys, box,
                                            const, nbr, interpret=True)
    prho, cve, _, _ = compute_eos_ve(ss.temp, m, kx, xm, gradh, const)
    return rho, nc, p, c, cs, xm, kx, prho, cve


def _list_ops(case, inputs, lists):
    """Every list op on ``lists`` by name, as a thunk (interpret mode)."""
    ss, keys, box, const, nbr = case
    x, y, z, h, m = ss.x, ss.y, ss.z, ss.h, ss.m
    rho, nc, p, c, cs, xm, kx, prho, cve = inputs
    kw = dict(interpret=True, lists=lists)
    return {
        "density": lambda: pp.pallas_density(
            x, y, z, h, m, None, box, const, nbr, **kw)[:2],
        "iad": lambda: pp.pallas_iad(
            x, y, z, h, m / rho, None, box, const, nbr, **kw)[0],
        "momentum-energy-std": lambda: pp.pallas_momentum_energy_std(
            x, y, z, ss.vx, ss.vy, ss.vz, h, m, rho, p, c, *cs, None, box,
            const, nbr, **kw)[:5],
        "momentum-energy-ve": lambda: pp.pallas_momentum_energy_ve(
            x, y, z, ss.vx, ss.vy, ss.vz, h, m, prho, cve, kx, xm,
            ss.alpha, *cs, None, box, const, nbr, nc=nc, **kw)[:5],
    }


OPS = ["density", "iad", "momentum-energy-std", "momentum-energy-ve"]


def _flat(out):
    return [np.asarray(a) for a in jax.tree.leaves(out)]


@pytest.mark.parametrize("op", OPS)
def test_list_ops_bitwise_equal_to_the_parents_shape(case, inputs, built,
                                                     built_uncut, op,
                                                     monkeypatch):
    """(ii) the tiles move no lane and no sum: each list op on the cut
    lists, through the ring of LIST_RING tiles, is bitwise what the same
    code gives at the parent's shape (13 rows a run, a ring of two), in
    the periodic box (sedov: jittered lattice, image shifts) and the open
    one (noh)."""
    got = _flat(_list_ops(case, inputs, built)[op]())
    for k, v in PARENT_SHAPE.items():
        monkeypatch.setattr(pp, k, v)
    want = _flat(_list_ops(case, inputs, built_uncut)[op]())
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _first_chunk_only(lists):
    """``lists`` cut down to each group's first kept chunk: exactly one
    run a group, whatever the tile."""
    r = lists.ranges
    s0, l0 = r.starts[:, :1], r.lens[:, :1]
    first = jnp.arange(r.starts.shape[1])[None, :] == 0
    cnt = jnp.where(jnp.arange(lists.cnt.shape[1])[None, :] == 0,
                    lists.cnt, 0)
    ranges = r._replace(
        starts=jnp.where(first, s0, 0),
        lens=jnp.where(first, jnp.minimum(l0, 128 - s0 % 128), 0),
        ncells=jnp.minimum(r.ncells, 1))
    return lists._replace(
        ranges=ranges, cnt=cnt, fill=jnp.zeros_like(lists.fill),
        emit=(cnt >= 128).astype(jnp.int32), tail=cnt[:, 0] % 128)


@pytest.mark.parametrize("runs", ["one", "fewer-than-ring"])
@pytest.mark.parametrize("op", ["density", "momentum-energy-std"])
def test_ring_deeper_than_a_groups_runs(case, inputs, built, built_uncut,
                                        op, runs, monkeypatch):
    """(iii) a ring of K tiles starts K - 1 copies before a group's first
    run is walked: a group with fewer runs than that, and with exactly
    one, starts only the copies it has and waits for each once."""
    cut, uncut = built, built_uncut
    if runs == "one":
        cut, uncut = _first_chunk_only(cut), _first_chunk_only(uncut)
        assert int(jnp.max(cut.ranges.ncells)) == 1
        ring = pp.LIST_RING
    else:
        nc = np.asarray(cut.ranges.ncells)
        ring = int(np.median(nc)) + 1
        assert ((0 < nc) & (nc < ring - 1)).any() and (nc >= ring).any()
    monkeypatch.setattr(pp, "LIST_RING", ring)
    got = _flat(_list_ops(case, inputs, cut)[op]())
    for k, v in PARENT_SHAPE.items():
        monkeypatch.setattr(pp, k, v)
    want = _flat(_list_ops(case, inputs, uncut)[op]())
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
