"""Mosaic bitmask+popcount-rank compaction for the gravity MAC lists.

The list-materialization primitive of the hierarchical MAC classification
(gravity/traversal.py, compaction="bitmask"): given each target block's
per-candidate CLASS array (0 = M2P, 1 = P2P, anything else = pruned), it
produces both fixed-cap index lists — the job the per-block packed 3-class
sort used to do at ~214 ms/1M (docs/NEXT.md round 5; sort *variants* all
measured identical, so the sort itself was the floor).

Kernel shape, patterned on sph/pallas_pairs.py's streaming engine:

- candidates stream through VMEM in 128-lane chunks (the input rides the
  grid pipeline, so chunk t is one sublane row of the block's (T, 128)
  tile — no manual DMA needed);
- per chunk and class, the lane bitmask is popcount-ranked: the exclusive
  prefix rank comes from ONE strict-lower-triangular (128,128)@(128,1)
  MXU product on the mask transposed to sublane-major (the transpose
  itself is a diag-embed + (128,128)@(128,1) product — Mosaic has no
  lane->sublane relayout primitive, the MXU is the shuffle engine);
- compaction is a one-hot (1,128)@(128,128) MXU product: column j of the
  one-hot picks the candidate whose rank equals j - fill (mod 128), so
  the running staging offset is folded into the gather — no dynamic lane
  roll anywhere;
- compacted lanes land in a 256-lane staging window; every time it fills
  past 128 lanes one ALIGNED sublane row is emitted to the output list
  (the same fill/emit scheme as the list-walk engine's staging buffer);
- the per-chunk class counts are taken outside the kernel, by one
  elementwise + lane-reduce fusion over the packed classes the caller
  just wrote (``_chunk_class_counts``), and ride in per row through SMEM:
  a chunk with no lane of either class costs two scalar reads and one
  branch, no vector load — the level-major node order clusters the
  accepted cut into a few contiguous level bands, so most chunks of a
  superblock's pre-pass are such (13 % hold a live lane at Evrard 1.1M,
  7 % on the four-slab mesh: scripts/count_compact_chunks.py).

Counts are accumulated UNCLIPPED, so a list overflowing its cap keeps
reporting the true high water and the driver's diagnostic/regrow contract
(Simulation._gravity_overflowed) keeps working; the written lists are the
first ``cap`` entries in candidate order — exactly the truncation the
3-class sort produced.

Values are carried in the low IDX_BITS of the packed int32 (class in the
bits above), and ride the MXU in f32 — exact for indices < 2^24, which
bounds the tree size this kernel accepts (~16.7M nodes; a 400^3 run's
~1.4M-node tree fits with room). Exact ONLY at fp32 contract precision,
which the value gather states: Mosaic's default runs an f32 dot in bf16
passes and rounds the indices to 8 significant bits (first chip run,
v5e / jax 0.9.0: 7091 came back as 7104, the solve lost 25% rms against
the direct sum). The mask and rank products carry 0/1 operands and sums
<= 128, exact at any precision, and stay at the default.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IDX_BITS = 24
IDX_MASK = (1 << IDX_BITS) - 1
# padding slots: class 2 = pruned/dead, value 0
DEAD = 2 << IDX_BITS


def _kernel(n0_ref, n1_ref, pk_ref, out0_ref, out1_ref, cnt_ref, stage_ref,
            chunks=None):
    """``n0_ref`` / ``n1_ref``: the row's per-chunk class counts in SMEM.
    ``chunks``: how many of the row's T chunks to walk (None = all)."""
    T = pk_ref.shape[1] if chunks is None else chunks
    out_rows = (out0_ref.shape[1], out1_ref.shape[1])

    out0_ref[0] = jnp.zeros((out_rows[0], 128), jnp.int32)
    out1_ref[0] = jnp.zeros((out_rows[1], 128), jnp.int32)
    stage_ref[...] = jnp.zeros((2, 8, 256), jnp.float32)

    sub2 = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    lan2 = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    eye = (sub2 == lan2).astype(jnp.float32)
    # L[s, u] = 1 iff u < s: rank_excl[s] = sum_{u<s} mask[u]
    lt = (lan2 < sub2).astype(jnp.float32)
    ones_col = jnp.ones((128, 1), jnp.float32)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    out_refs = (out0_ref, out1_ref)

    def compact(k, cls, val, cnt, done):
        """A chunk's ``cnt`` > 0 lanes of class ``k`` behind the ``done``
        already staged."""
        maskf = (cls == k).astype(jnp.float32)
        fill = done % 128
        row = done // 128
        # mask to sublane-major via diag-embed + MXU column product
        dcol = jnp.dot(jnp.broadcast_to(maskf, (128, 128)) * eye,
                       ones_col, preferred_element_type=jnp.float32)
        rcol = jnp.dot(lt, dcol,
                       preferred_element_type=jnp.float32)  # (128,1)
        # one-hot gather with the staging fill folded in: column j
        # takes the candidate of rank (j - fill) mod 128
        tgt = ((lan2 - fill + 128) & 127).astype(jnp.float32)
        onehot = jnp.where(rcol == tgt, dcol, 0.0)  # (128, 128)
        comp = jnp.dot(val, onehot,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)  # (1,128)
        m0 = (lane1 >= fill) & (lane1 < fill + cnt)
        m1 = lane1 < (fill + cnt - 128)
        stage_ref[k, 0:1, :128] = jnp.where(
            m0, comp, stage_ref[k, 0:1, :128])
        stage_ref[k, 0:1, 128:] = jnp.where(
            m1, comp, stage_ref[k, 0:1, 128:])

        emit = fill + cnt >= 128

        @pl.when(emit & (row < out_rows[k]))
        def _():
            out_refs[k][0, pl.ds(row, 1), :] = (
                stage_ref[k, 0:1, :128].astype(jnp.int32))

        @pl.when(emit)
        def _():
            stage_ref[k, 0:1, :128] = stage_ref[k, 0:1, 128:]
            stage_ref[k, 0:1, 128:] = jnp.zeros((1, 128), jnp.float32)

    def body(t, done):
        cnts = (n0_ref[0, 0, t], n1_ref[0, 0, t])

        # a chunk neither class has a lane in: two SMEM reads, one branch
        @pl.when(cnts[0] + cnts[1] > 0)
        def _():
            pk = pk_ref[0, pl.ds(t, 1), :]  # (1, 128)
            cls = pk >> IDX_BITS  # packed values are nonnegative
            val = (pk & IDX_MASK).astype(jnp.float32)
            for k in (0, 1):
                pl.when(cnts[k] > 0)(functools.partial(
                    compact, k, cls, val, cnts[k], done[k]))

        return done[0] + cnts[0], done[1] + cnts[1]

    done = jax.lax.fori_loop(0, T, body, (jnp.int32(0), jnp.int32(0)))

    for k in (0, 1):
        row = done[k] // 128

        @pl.when((done[k] % 128 > 0) & (row < out_rows[k]))
        def _(k=k, row=row):
            out_refs[k][0, pl.ds(row, 1), :] = (
                stage_ref[k, 0:1, :128].astype(jnp.int32))

    cnt_ref[0] = jnp.where(
        lane1 == 0, done[0], jnp.where(lane1 == 1, done[1], 0))


def _kernel_live(live_ref, n0_ref, n1_ref, pk_ref, out0_ref, out1_ref,
                 cnt_ref, stage_ref):
    """The same walk over the row's first ``ceil(live / 128)`` chunks."""
    live = live_ref[pl.program_id(0)]
    chunks = jnp.clip((live + 127) // 128, 0, pk_ref.shape[1])
    _kernel(n0_ref, n1_ref, pk_ref, out0_ref, out1_ref, cnt_ref, stage_ref,
            chunks=chunks)


def _chunked(packed):
    """``packed`` (B, C) as (B, T, 128) chunks, the tail padded DEAD."""
    B, C = packed.shape
    T = max(1, -(-C // 128))
    if T * 128 > C:
        packed = jnp.concatenate(
            [packed, jnp.full((B, T * 128 - C), DEAD, jnp.int32)], axis=1
        )
    return packed.reshape(B, T, 128)


def _chunk_class_counts(pk):
    """(cnt0, cnt1), each (B, T) int32: the lanes of class 0 and of class
    1 in every 128-lane chunk of ``pk`` (B, T, 128)."""
    cls = pk >> IDX_BITS
    return tuple(jnp.sum(cls == k, axis=2, dtype=jnp.int32) for k in (0, 1))


def live_chunks(packed):
    """(B,) int32: the 128-lane chunks of each row of ``packed`` (as
    ``compact_class_lists`` takes it) that hold a lane of class 0 or 1,
    i.e. the chunks its walk does not skip. The counts are the kernel's
    own fusion over the same array: called beside it in one program, XLA
    makes them once."""
    cnt0, cnt1 = _chunk_class_counts(_chunked(packed))
    return jnp.sum(cnt0 + cnt1 > 0, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("cap0", "cap1", "interpret"))
def compact_class_lists(packed, cap0: int, cap1: int,
                        interpret: bool = False, live=None):
    """Compact each row's class-0 and class-1 slots into fixed-cap lists.

    ``packed``: (B, C) int32, ``(cls << IDX_BITS) | value`` with value in
    [0, 2^IDX_BITS); cls 0/1 select the two lists, anything else is
    dropped. Returns ``(list0 (B, cap0) i32, n0 (B,) i32, list1 (B, cap1)
    i32, n1 (B,) i32)`` — values in candidate order, UNCLIPPED true counts
    (entries beyond a cap are truncated; slots beyond a count are 0 and
    must be masked by the caller).

    ``live``: optional (B,) int32, the caller's promise that row b holds
    nothing but dropped slots from slot ``live[b]`` on. The chunk walk of
    row b then ends at ``ceil(live[b] / 128)`` (a scalar-prefetch operand
    bounds the kernel's loop) and never reads the slots past that chunk,
    so they need not even be written; lists and counts are those of the
    full walk, bit for bit. Without it every chunk is walked.
    """
    pk = _chunked(packed)
    B, T, _ = pk.shape
    # counted here, inside the jit, so that no caller can hand the kernel
    # counts that are not its chunks'; under ``live`` the walk never reads
    # the counts past its last chunk either
    cnts = tuple(c[:, None, :] for c in _chunk_class_counts(pk))
    r0 = max(1, -(-cap0 // 128))
    r1 = max(1, -(-cap1 // 128))
    # index maps take the prefetched scalars after the grid indices
    row = lambda b, *_: (b, 0, 0)
    spec = dict(
        grid=(B,),
        in_specs=[
            # a few KB a row: T int32 per class
            pl.BlockSpec((1, 1, T), row, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, T), row, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, T, 128), row),
        ],
        out_specs=[
            pl.BlockSpec((1, r0, 128), row),
            pl.BlockSpec((1, r1, 128), row),
            pl.BlockSpec((1, 1, 128), row),
        ],
        scratch_shapes=[pltpu.VMEM((2, 8, 256), jnp.float32)],
    )
    out_shape = [
        jax.ShapeDtypeStruct((B, r0, 128), jnp.int32),
        jax.ShapeDtypeStruct((B, r1, 128), jnp.int32),
        jax.ShapeDtypeStruct((B, 1, 128), jnp.int32),
    ]
    if live is None:
        outs = pl.pallas_call(_kernel, out_shape=out_shape,
                              interpret=interpret, **spec)(*cnts, pk)
    else:
        outs = pl.pallas_call(
            _kernel_live, out_shape=out_shape, interpret=interpret,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, **spec),
        )(live.astype(jnp.int32), *cnts, pk)
    list0 = outs[0].reshape(B, r0 * 128)[:, :cap0]
    list1 = outs[1].reshape(B, r1 * 128)[:, :cap1]
    return list0, outs[2][:, 0, 0], list1, outs[2][:, 0, 1]
