"""JXA203: sharding-propagation audit — silent replication and exchange
volume beyond the analytic expectation.

Two ways sharding propagation goes wrong land here:

- a **particle-shaped operand enters a shard_map fully replicated**
  (no dim sharded): the partitioner materializes all N rows on
  every device — the implicit all-gather the Warren-Salmon LET program
  exists to avoid. Flagged when the operand's campaign-rescaled bytes
  clear the AuditContext threshold; small replicated tables and the
  O(tree) coarse gravity arrays (leading dim != N) are the design and
  stay clean.
- the entry's **summed collective output bytes exceed the analytic
  budget** its registry builder declared (``exchange_budget_bytes``,
  derived from sizing.sparse_need_matrix / _halo_info shipped_rows)
  by more than ``exchange_slack``: a partitioner-inserted collective is
  shipping particle fields the explicit exchange didn't account for.
  Entries without a declared budget skip the volume gate.
"""

from __future__ import annotations

from typing import List

from sphexa_tpu.devtools.audit.core import EntryTrace, audit_context, register
from sphexa_tpu.devtools.audit.spmd import format_bytes, spmd_report
from sphexa_tpu.devtools.common import Finding


@register(
    "JXA203", "sharding-propagation",
    "particle-shaped operand replicated into a shard_map, or cross-shard "
    "collective volume beyond the sizing-derived expectation",
)
def check(trace: EntryTrace) -> List[Finding]:
    ctx = audit_context()
    rep = spmd_report(trace, ctx)
    out: List[Finding] = []

    big = [r for r in rep.replicated
           if r.campaign_bytes >= ctx.repl_threshold_bytes]
    if big:
        desc = "; ".join(
            f"operand#{r.pos}[{r.where}] {r.shape} {r.dtype} "
            f"({format_bytes(r.toy_bytes)} traced, "
            f"{format_bytes(r.campaign_bytes)} at campaign N)"
            for r in big[:4])
        more = len(big) - min(len(big), 4)
        out.append(trace.finding(
            "JXA203",
            f"{len(big)} particle-shaped operand(s) enter a shard_map "
            f"fully replicated — every device materializes all N rows "
            f"(an implicit all-gather of particle fields): {desc}"
            + (f"; +{more} more" if more > 0 else "")
            + ". Shard them with PartitionSpec('p') or slice per shard.",
        ))

    case = trace.case
    budget = getattr(case, "exchange_budget_bytes", None)
    if budget:
        slack = getattr(case, "exchange_slack", 2.0) or 1.0
        allowed = int(budget * slack)
        measured = rep.collective_out_bytes
        if measured > allowed:
            out.append(trace.finding(
                "JXA203",
                f"cross-shard collective volume {format_bytes(measured)} "
                f"exceeds the analytic expectation "
                f"{format_bytes(budget)} x slack {slack:g} = "
                f"{format_bytes(allowed)} — a partitioner-inserted "
                f"collective is shipping rows the explicit exchange "
                f"didn't account for (check with_sharding_constraint "
                f"placement and the sizing-derived halo caps).",
            ))
    return out
