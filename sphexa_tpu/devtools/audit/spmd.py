"""Shared SPMD jaxpr analysis for the shardcheck (JXA2xx) rule family.

One walk over an entry's closed jaxpr produces everything the three
rules and the ``sphexa-audit preflight`` table read:

- **Collective order graph** (JXA201): every named-axis collective
  (``primitives.COLLECTIVE_PRIMS`` at any nesting depth, shard_map
  bodies included) with its set of collective *ancestors*
  through the data-dependency graph. ``optimization_barrier`` — the
  ``exchange.chain_after`` primitive — is an ordinary eqn here, so a
  chained collective inherits its predecessor as an ancestor for free.
  Two collectives neither of which is an ancestor of the other carry no
  program order, and XLA may rendezvous them in different interleavings
  on different devices (the PR-5 deadlock/garbage class on CPU meshes,
  and an ICI stall hazard on real chips).
- **Donation-aware peak-HBM liveness** (JXA202): a live-interval sweep
  over per-device buffer bytes. Top-level avals whose leading dim is
  divisible by the traced mesh size count as one shard's slice;
  shard_map-interior avals are already per-shard. Donated entry args
  (the property JXA103 verifies actually lowers to input-output
  aliasing) credit their matched output buffer as zero bytes. Nested
  jaxprs (jit/scan/cond bodies) contribute their own internal excess
  over their operand/result footprint at the call site. The same sweep
  carries a *campaign rescale*: every buffer holding a whole number of
  per-device slabs ("extensive" — particle fields, (S,3) vectors, halo
  annexes of k*S rows) is multiplied by
  ``(campaign_n / campaign_devices) / toy_slab_rows``; fixed-size work
  buffers (scan chunk accumulators, pallas tiles, O(tree) coarse
  arrays) stay at traced size. Full-slab halo windows rescale as full
  campaign slabs, so the bound is deliberately above the real Wmax.
- **Sharding-propagation facts** (JXA203): particle-shaped operands
  entering a shard_map fully replicated (no sharded dim — the
  partitioner will materialize N rows per device), and the summed
  output bytes of all collectives (the measured cross-shard volume the
  rule gates against the analytic ``sizing``-derived budget a registry
  builder declares).

The report is cached on the EntryTrace so the three rules and the
preflight table pay for one analysis per entry.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, FrozenSet, List, Optional, Set, Tuple

from sphexa_tpu.devtools.primitives import (
    COLLECTIVE_PRIMS,
    collective_axes,
    shard_map_operand_axes,
    sub_jaxprs,
)

__all__ = [
    "Collective",
    "ReplicatedOperand",
    "SpmdReport",
    "spmd_report",
    "format_bytes",
]

_EMPTY: FrozenSet[int] = frozenset()


@dataclasses.dataclass(frozen=True)
class Collective:
    cid: int
    prim: str
    axes: Tuple[str, ...]
    out_bytes: int       # per-shard result bytes (shard_map-interior aval)
    where: str           # nesting path, e.g. "jit/shard_map"


@dataclasses.dataclass(frozen=True)
class ReplicatedOperand:
    where: str
    pos: int             # shard_map operand position
    shape: Tuple[int, ...]
    dtype: str
    toy_bytes: int
    campaign_bytes: int


@dataclasses.dataclass
class SpmdReport:
    mesh_size: int                       # largest shard_map mesh traced (1 = none)
    collectives: List[Collective]
    # ancestor sets parallel to ``collectives``: anc[j] holds the cids
    # that are data-ordered BEFORE collective j
    ancestors: List[FrozenSet[int]]
    unordered_pairs: List[Tuple[int, int]]
    toy_peak_bytes: int                  # per-device, at the traced toy N
    campaign_peak_bytes: Optional[int]   # rescaled; None for unsharded entries
    toy_slab_rows: int                   # per-device rows the rescale anchors on
    campaign_ratio: Optional[float]
    replicated: List[ReplicatedOperand]
    collective_out_bytes: int            # summed per-shard collective results
    n_global: int                        # largest leading dim over entry invars


def format_bytes(b: Optional[int]) -> str:
    if b is None:
        return "-"
    if b >= 1 << 30:
        return f"{b / (1 << 30):.2f}GiB"
    if b >= 1 << 20:
        return f"{b / (1 << 20):.2f}MiB"
    if b >= 1 << 10:
        return f"{b / (1 << 10):.1f}KiB"
    return f"{b}B"


def _is_var(v) -> bool:
    # Literals carry .val; Vars (and DropVars) don't
    return not hasattr(v, "val")


def slab_rows(jaxpr) -> int:
    """Largest leading dim over entry invars: the N that the JXA2xx slab
    arithmetic, JXA204 and the schema's axis polynomials anchor on."""
    return max((int(v.aval.shape[0]) for v in jaxpr.invars
                if getattr(v.aval, "shape", ())), default=0)


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


# ---------------------------------------------------------------------------
# collective-order graph
# ---------------------------------------------------------------------------


def _collective_order(jaxpr) -> Tuple[List[Collective], List[FrozenSet[int]],
                                      List[Tuple[int, int]]]:
    """Extract collectives + transitive collective-ancestor sets.

    Dataflow abstract interpretation: each var maps to the set of
    collective ids on some path to it. Sub-jaxpr invars/outvars are
    mapped positionally to the call eqn's when the arities line up
    (jit, scan, shard_map, cond modulo the predicate); otherwise the
    call is treated as a unit (all inner collectives become ancestors of
    all eqn outputs) — optimistic only across a call boundary, which is
    where XLA schedules calls as units anyway."""
    infos: List[Collective] = []
    anc: List[FrozenSet[int]] = []

    def walk(jx, in_anc: Dict[Any, Set[int]], where: str
             ) -> Tuple[Set[int], List[Set[int]]]:
        env: Dict[Any, Set[int]] = dict(in_anc)
        ids_here: Set[int] = set()
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            in_a: Set[int] = set()
            for v in eqn.invars:
                if _is_var(v):
                    in_a |= env.get(v, _EMPTY)
            subs = sub_jaxprs(eqn)
            if subs:
                inner_all: Set[int] = set()
                out_accum: Optional[List[Set[int]]] = None
                positional = True
                for sj in subs:
                    sub_env: Dict[Any, Set[int]] = {}
                    ivs, evs = list(sj.invars), list(eqn.invars)
                    if len(ivs) == len(evs):
                        pairs = list(zip(ivs, evs))
                    elif len(ivs) == len(evs) - 1:   # cond: evs[0] = index
                        pairs = list(zip(ivs, evs[1:]))
                    else:
                        pairs = None
                    if pairs is None:
                        for iv in ivs:
                            sub_env[iv] = set(in_a)
                    else:
                        for iv, ev in pairs:
                            sub_env[iv] = (set(env.get(ev, _EMPTY))
                                           if _is_var(ev) else set())
                    sub_ids, sub_out = walk(
                        sj, sub_env, f"{where}/{prim}" if where else prim)
                    inner_all |= sub_ids
                    if len(sub_out) == len(eqn.outvars):
                        if out_accum is None:
                            out_accum = [set(s) for s in sub_out]
                        else:
                            for k in range(len(out_accum)):
                                out_accum[k] |= sub_out[k]
                    else:
                        positional = False
                ids_here |= inner_all
                if positional and out_accum is not None:
                    for k, ov in enumerate(eqn.outvars):
                        env[ov] = in_a | out_accum[k]
                else:
                    for ov in eqn.outvars:
                        env[ov] = in_a | inner_all
            elif prim in COLLECTIVE_PRIMS and collective_axes(eqn):
                cid = len(infos)
                infos.append(Collective(
                    cid=cid, prim=prim, axes=collective_axes(eqn),
                    out_bytes=sum(aval_bytes(ov.aval) for ov in eqn.outvars),
                    where=where or "jit",
                ))
                anc.append(frozenset(in_a))
                out_a = in_a | {cid}
                ids_here.add(cid)
                for ov in eqn.outvars:
                    env[ov] = out_a
            else:
                for ov in eqn.outvars:
                    env[ov] = in_a
        out_anc = [set(env.get(v, _EMPTY)) if _is_var(v) else set()
                   for v in jx.outvars]
        return ids_here, out_anc

    walk(jaxpr, {}, "")
    # close ancestor sets transitively (an ancestor's ancestors order too)
    closed: List[Set[int]] = [set(a) for a in anc]
    for j in range(len(closed)):
        stack = list(closed[j])
        while stack:
            i = stack.pop()
            for k in closed[i]:
                if k not in closed[j]:
                    closed[j].add(k)
                    stack.append(k)
    anc = [frozenset(a) for a in closed]
    unordered = [
        (i, j)
        for j in range(len(infos))
        for i in range(j)
        if i not in anc[j] and j not in anc[i]
    ]
    return infos, anc, unordered


# ---------------------------------------------------------------------------
# donation-aware peak liveness
# ---------------------------------------------------------------------------


def _per_device_bytes(aval, P: int, scaled: bool) -> int:
    b = aval_bytes(aval)
    if scaled and P > 1:
        shape = getattr(aval, "shape", ())
        if shape and int(shape[0]) >= P and int(shape[0]) % P == 0:
            b //= P
    return b


def _campaign_bytes(bt: int, aval, s_toy: int, ratio: float) -> int:
    if ratio <= 1.0 or not s_toy:
        return bt
    itemsize = getattr(getattr(aval, "dtype", None), "itemsize", 0)
    if not itemsize:
        return bt
    elems = bt // itemsize
    # extensive (scales with the slab) iff a whole number of per-device
    # slabs: particle-derived buffers are always k*S elements (fields,
    # (S,3) vectors, concat halo annexes = P*S windows), while the
    # fixed-size work buffers that must NOT rescale (scan chunk
    # accumulators, cell-grid tiles, O(tree) coarse arrays) are sized by
    # config constants unrelated to S
    if elems >= s_toy and elems % s_toy == 0:
        return int(bt * ratio)
    return bt


def _peak_liveness(jaxpr, P: int, s_toy: int, ratio: float,
                   donated_positions: Set[int]) -> Tuple[int, int]:
    """(toy_peak, campaign_peak) per-device bytes over the program.

    Buffers live from definition to last use (entry args, consts and
    results live the whole program). A donated entry arg's matched
    result (same shape+dtype, greedy) is credited zero — XLA aliases it
    onto the input buffer. A nested jaxpr adds only its internal excess
    over the call's operand/result footprint."""
    zero_vars: Set[Any] = set()
    invar_set = set(jaxpr.invars)
    matched: Set[int] = set()
    for pos in sorted(donated_positions):
        if pos >= len(jaxpr.invars):
            continue
        iv = jaxpr.invars[pos]
        ish = getattr(iv.aval, "shape", None)
        idt = getattr(iv.aval, "dtype", None)
        for k, ov in enumerate(jaxpr.outvars):
            if k in matched or not _is_var(ov) or ov in invar_set:
                continue
            if (getattr(ov.aval, "shape", None) == ish
                    and getattr(ov.aval, "dtype", None) == idt):
                matched.add(k)
                zero_vars.add(ov)
                break

    def sweep(jx, scaled: bool, top: bool) -> Tuple[int, int]:
        n = len(jx.eqns)
        end = n
        first: Dict[Any, int] = {}
        last: Dict[Any, int] = {}
        for v in (*jx.invars, *jx.constvars):
            first[v] = 0
            last[v] = end
        for i, eqn in enumerate(jx.eqns):
            for ov in eqn.outvars:
                first.setdefault(ov, i)
                last.setdefault(ov, i)
            for iv in eqn.invars:
                if _is_var(iv):
                    first.setdefault(iv, 0)
                    last[iv] = max(last.get(iv, 0), i)
        for ov in jx.outvars:
            if _is_var(ov):
                first.setdefault(ov, 0)
                last[ov] = end
        delta_t = [0] * (end + 2)
        delta_c = [0] * (end + 2)
        for v, f0 in first.items():
            if top and v in zero_vars:
                continue
            bt = _per_device_bytes(v.aval, P, scaled)
            bc = _campaign_bytes(bt, v.aval, s_toy, ratio)
            l0 = last.get(v, f0)
            delta_t[f0] += bt
            delta_t[l0 + 1] -= bt
            delta_c[f0] += bc
            delta_c[l0 + 1] -= bc
        extra_t = [0] * (end + 1)
        extra_c = [0] * (end + 1)
        for i, eqn in enumerate(jx.eqns):
            if eqn.primitive.name == "pallas_call":
                # kernel-body avals are VMEM block/tile views, not HBM
                # buffers — the call's HBM footprint is its operands and
                # results, already counted at this level
                continue
            subs = sub_jaxprs(eqn)
            if not subs:
                continue
            sub_scaled = scaled and eqn.primitive.name != "shard_map"
            io_t = io_c = 0
            for v in (*eqn.invars, *eqn.outvars):
                if not _is_var(v):
                    continue
                bt = _per_device_bytes(v.aval, P, scaled)
                io_t += bt
                io_c += _campaign_bytes(bt, v.aval, s_toy, ratio)
            for sj in subs:
                pt, pc = sweep(sj, sub_scaled, top=False)
                extra_t[i] = max(extra_t[i], max(0, pt - io_t))
                extra_c[i] = max(extra_c[i], max(0, pc - io_c))
        peak_t = peak_c = run_t = run_c = 0
        for p in range(end + 1):
            run_t += delta_t[p]
            run_c += delta_c[p]
            peak_t = max(peak_t, run_t + extra_t[p])
            peak_c = max(peak_c, run_c + extra_c[p])
        return peak_t, peak_c

    return sweep(jaxpr, scaled=True, top=True)


# ---------------------------------------------------------------------------
# sharding propagation
# ---------------------------------------------------------------------------


def _replicated_operands(jaxpr, n_global: int, campaign_n: int
                         ) -> List[ReplicatedOperand]:
    out: List[ReplicatedOperand] = []

    def walk(jx, where: str):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                for pos, axes in enumerate(shard_map_operand_axes(eqn)):
                    if axes or pos >= len(eqn.invars):
                        continue       # some dim is sharded, or arity drift
                    v = eqn.invars[pos]
                    aval = getattr(v, "aval", None)
                    shape = tuple(getattr(aval, "shape", ()) or ())
                    if not shape or n_global <= 1 or int(shape[0]) != n_global:
                        continue       # not particle-shaped: replication is
                        #                the design (coarse tree, tables)
                    tb = aval_bytes(aval)
                    cb = int(tb * (campaign_n / n_global)) if campaign_n else tb
                    out.append(ReplicatedOperand(
                        where=where or "jit", pos=pos, shape=shape,
                        dtype=str(getattr(aval, "dtype", "?")),
                        toy_bytes=tb, campaign_bytes=cb,
                    ))
            for sj in sub_jaxprs(eqn):
                walk(sj, f"{where}/{prim}" if where else prim)

    walk(jaxpr, "")
    return out


def _mesh_size(jaxpr) -> int:
    best = 1

    def walk(jx):
        nonlocal best
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                mesh = eqn.params.get("mesh")
                size = getattr(mesh, "size", None)
                if size is None and hasattr(mesh, "shape"):
                    size = 1
                    for d in dict(mesh.shape).values():
                        size *= int(d)
                if size:
                    best = max(best, int(size))
            for sj in sub_jaxprs(eqn):
                walk(sj)

    walk(jaxpr)
    return best


# ---------------------------------------------------------------------------
# the one-call report
# ---------------------------------------------------------------------------


def spmd_report(trace, ctx) -> SpmdReport:
    """Analyze an EntryTrace under an AuditContext; cached on the trace."""
    cached = getattr(trace, "_spmd_report", None)
    if cached is not None:
        return cached
    closed = trace.closed_jaxpr
    jx = closed.jaxpr
    P = _mesh_size(jx)
    infos, ancestors, unordered = _collective_order(jx)

    donated: Set[int] = set()
    if trace.entry.donate:
        from jax import tree_util

        spans = [len(tree_util.tree_leaves(a)) for a in trace.case.args]
        offsets = [sum(spans[:i]) for i in range(len(spans))]
        for p in trace.entry.donate:
            if p < len(spans):
                donated |= set(range(offsets[p], offsets[p] + spans[p]))

    n_global = slab_rows(jx)
    s_toy = 0
    for v in jx.invars:
        shape = getattr(v.aval, "shape", ())
        if shape:
            d0 = int(shape[0])
            rows = d0 // P if (P > 1 and d0 >= P and d0 % P == 0) else d0
            s_toy = max(s_toy, rows)

    sharded = bool(trace.entry.mesh_axes)
    ratio: Optional[float] = None
    if sharded and s_toy:
        ratio = (ctx.campaign_n / max(ctx.campaign_devices, 1)) / s_toy
    toy_peak, campaign_peak = _peak_liveness(
        jx, P, s_toy, ratio or 0.0, donated)

    replicated = (_replicated_operands(jx, n_global, ctx.campaign_n)
                  if sharded else [])

    report = SpmdReport(
        mesh_size=P,
        collectives=infos,
        ancestors=ancestors,
        unordered_pairs=unordered,
        toy_peak_bytes=toy_peak,
        campaign_peak_bytes=(campaign_peak if (sharded and ratio) else None),
        toy_slab_rows=s_toy,
        campaign_ratio=ratio,
        replicated=replicated,
        collective_out_bytes=sum(c.out_bytes for c in infos),
        n_global=n_global,
    )
    trace._spmd_report = report
    return report
