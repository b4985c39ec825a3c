"""jaxcost: static per-phase roofline cost model over traced jaxprs.

The fourth static layer (after jaxlint/AST, jaxaudit/trace, shardcheck/
SPMD): predict the per-phase device-time table ``sphexa-telemetry
trace`` measures from a chip capture, without a chip. The walk reuses
``spmd.py``'s unwrap conventions (nested ClosedJaxprs, shard_map/scan
bodies, ``pallas_call`` treated as a call-site leaf) and attributes
every eqn to the ``util/phases.py`` taxonomy through the
``sphexa/<phase>`` named scopes PR 7 stamped into
``eqn.source_info.name_stack`` — the same scopes traceview reads back
out of an xplane, so the static and measured tables join phase-by-phase.

Per eqn the model accumulates:

- **FLOPs** from per-primitive cost rules (``FLOP_RULES`` /
  ``ELEMENTWISE_WEIGHTS``): dot/conv from dimension numbers, elementwise
  and reductions from operand sizes (transcendentals weighted), scan
  bodies multiplied by the static trip count, ``while`` bodies counted
  once (trip count is dynamic — a documented lower bound), ``cond``
  charged at its most expensive branch, ``pallas_call`` kernels at body
  FLOPs x grid when the grid is readable.
- **HBM bytes** from operand+result avals, twice: an upper bound (every
  eqn reads/writes HBM — no fusion) and a lower bound with a same-phase
  fusion discount (each value is charged once per phase — perfect
  intra-phase fusion, the XLA-on-TPU asymptote).
- **ICI bytes** for collective primitives (``primitives.COLLECTIVE_PRIMS``),
  per-shard result volume — the same accounting JXA203 gates.

``predict`` divides the tallies by a ``devices.py`` model into a
per-phase ms table + arithmetic intensity and classifies each phase
against the ridge point. Eqns with no sphexa scope roll up into an
``unattributed`` bucket and a FLOP-coverage fraction, mirroring
traceview's coverage gate.

Calibration (``sphexa-telemetry trace <dir> --predict``) joins a
measured capture against the prediction for the program that produced
it and gates the per-phase measured/predicted ratios inside a committed
band — the model can never silently drift from what chips do.

Jax-free at import (the ``spmd.py`` contract): everything here walks
already-traced jaxprs; jax only loads lazily when a calibration target
has to be traced.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple

from sphexa_tpu.devtools.audit.devices import DeviceModel, get_device
from sphexa_tpu.devtools.audit.spmd import _is_var, aval_bytes
from sphexa_tpu.devtools.primitives import COLLECTIVE_PRIMS, sub_jaxprs
from sphexa_tpu.telemetry.traceview import PHASE_RE

__all__ = [
    "PhaseCost",
    "CostReport",
    "PhasePrediction",
    "Prediction",
    "analyze_jaxpr",
    "cost_report",
    "predict",
    "load_budget",
    "validate_budget",
    "load_calibration",
    "calibration_join",
    "predict_for_target",
]

UNATTRIBUTED = "unattributed"

# ---------------------------------------------------------------------------
# per-primitive FLOP cost rules
# ---------------------------------------------------------------------------

#: FLOPs charged per OUTPUT element for elementwise-shaped primitives.
#: Primitives absent from every table below default to weight 1 (one
#: vector op per element); pure data movement is weight 0. These are the
#: "per-primitive cost rules" the calibration fixture pins — corrupting
#: one moves a phase's predicted ms outside the committed band.
ELEMENTWISE_WEIGHTS: Dict[str, float] = {
    # transcendentals: multi-pass polynomial/Newton implementations
    "exp": 8.0, "exp2": 8.0, "log": 8.0, "log1p": 8.0, "expm1": 8.0,
    "sin": 8.0, "cos": 8.0, "tan": 8.0, "tanh": 8.0, "logistic": 8.0,
    "erf": 8.0, "erfc": 8.0, "erf_inv": 8.0, "atan2": 8.0,
    "asin": 8.0, "acos": 8.0, "atan": 8.0, "sinh": 8.0, "cosh": 8.0,
    "asinh": 8.0, "acosh": 8.0, "atanh": 8.0, "pow": 8.0,
    # divide/rsqrt-class: iterative refinement
    "div": 4.0, "sqrt": 4.0, "rsqrt": 4.0, "cbrt": 4.0, "rem": 4.0,
    "integer_pow": 2.0,
    # data movement: bytes are charged, arithmetic is not
    "broadcast_in_dim": 0.0, "reshape": 0.0, "transpose": 0.0,
    "squeeze": 0.0, "expand_dims": 0.0, "slice": 0.0, "rev": 0.0,
    "concatenate": 0.0, "pad": 0.0, "gather": 0.0, "dynamic_slice": 0.0,
    "dynamic_update_slice": 0.0, "copy": 0.0, "convert_element_type": 0.0,
    "bitcast_convert_type": 0.0, "iota": 0.0, "stop_gradient": 0.0,
    "device_put": 0.0, "split": 0.0, "optimization_barrier": 0.0,
    "axis_index": 0.0,
}

#: primitives whose FLOPs scale with the INPUT (reduction-shaped):
#: one op per input element
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "reduce_precision",
    "argmax", "argmin", "reduce",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "scatter", "scatter-add", "scatter_add", "scatter_mul",
    "scatter_min", "scatter_max",
})


def _elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if not shape:
        return 1
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _dtype_name(aval) -> str:
    dt = getattr(aval, "dtype", None)
    return getattr(dt, "name", "float32") if dt is not None else "float32"


def _out_elems(eqn) -> int:
    return sum(_elems(v.aval) for v in eqn.outvars if hasattr(v, "aval"))


def _in_elems(eqn) -> int:
    return sum(_elems(v.aval) for v in eqn.invars if hasattr(v, "aval"))


def _dot_general_flops(eqn) -> float:
    """2 * batch * M * N * K from the dimension numbers + lhs/rhs avals."""
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    batch = 1
    for d in lb:
        batch *= int(lhs.shape[d])
    contract = 1
    for d in lc:
        contract *= int(lhs.shape[d])
    lhs_free = _elems(lhs) // max(batch * contract, 1)
    rc_set = set(rc)
    rb_set = set(_rb)
    rhs_free = 1
    for i, d in enumerate(rhs.shape):
        if i not in rc_set and i not in rb_set:
            rhs_free *= int(d)
    return 2.0 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn) -> float:
    """2 * output elements * kernel taps per output feature."""
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params.get("dimension_numbers")
    out_feature = int(rhs.shape[dn.rhs_spec[0]]) if dn is not None \
        else int(rhs.shape[-1])
    taps = _elems(rhs) / max(out_feature, 1)
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    return 2.0 * _elems(out) * taps / max(groups, 1)


def _sort_flops(eqn) -> float:
    n = _in_elems(eqn)
    return float(n) * max(math.log2(max(n, 2)), 1.0)


def _reduce_window_flops(eqn) -> float:
    window = eqn.params.get("window_dimensions") or ()
    taps = 1
    for d in window:
        taps *= int(d)
    return float(_out_elems(eqn)) * max(taps, 1)


#: primitive name -> flops(eqn); consulted before the elementwise tables
FLOP_RULES: Dict[str, Any] = {
    "dot_general": _dot_general_flops,
    "conv_general_dilated": _conv_flops,
    "sort": _sort_flops,
    "reduce_window_sum": _reduce_window_flops,
    "reduce_window_max": _reduce_window_flops,
    "reduce_window_min": _reduce_window_flops,
    "reduce_window": _reduce_window_flops,
    "select_and_scatter_add": _reduce_window_flops,
}


def eqn_flops(eqn) -> float:
    """Per-primitive FLOP estimate for one leaf eqn."""
    prim = eqn.primitive.name
    rule = FLOP_RULES.get(prim)
    if rule is not None:
        return float(rule(eqn))
    if prim in _REDUCE_PRIMS:
        return float(_in_elems(eqn))
    return float(_out_elems(eqn)) * ELEMENTWISE_WEIGHTS.get(prim, 1.0)


# ---------------------------------------------------------------------------
# the per-phase accumulator + jaxpr walk
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PhaseCost:
    """Accumulated static cost of one phase bucket."""

    phase: str
    flops: float = 0.0
    flops_by_dtype: Dict[str, float] = dataclasses.field(default_factory=dict)
    hbm_lower: float = 0.0      # same-phase fusion discount applied
    hbm_upper: float = 0.0      # every eqn round-trips HBM
    ici_bytes: float = 0.0
    eqns: int = 0

    def dominant_dtype(self) -> str:
        if not self.flops_by_dtype:
            return "float32"
        return max(self.flops_by_dtype.items(), key=lambda kv: kv[1])[0]


@dataclasses.dataclass
class CostReport:
    """Per-phase static cost of one traced entry."""

    phases: Dict[str, PhaseCost]      # taxonomy phases + any unknown scopes
    unattributed: PhaseCost           # eqns with no sphexa/ scope at all
    unknown_scopes: Tuple[str, ...]   # sphexa/<x> with x outside PHASES
    total_flops: float
    coverage: float                   # on-taxonomy FLOP share (1.0 if 0 FLOPs)


class _Acc:
    """Mutable walk state: phase buckets + per-phase fusion seen-sets."""

    def __init__(self) -> None:
        self.buckets: Dict[str, PhaseCost] = {}
        self._seen: Dict[str, set] = {}

    def bucket(self, phase: str) -> PhaseCost:
        b = self.buckets.get(phase)
        if b is None:
            b = self.buckets[phase] = PhaseCost(phase=phase)
            self._seen[phase] = set()
        return b

    def add_eqn(self, phase: str, flops: float, dtype: str,
                io_vars, mult: float, ici: float = 0.0) -> None:
        b = self.bucket(phase)
        b.eqns += 1
        b.flops += flops * mult
        if flops:
            b.flops_by_dtype[dtype] = \
                b.flops_by_dtype.get(dtype, 0.0) + flops * mult
        b.ici_bytes += ici * mult
        seen = self._seen[phase]
        for v in io_vars:
            nb = aval_bytes(getattr(v, "aval", None))
            b.hbm_upper += nb * mult
            if id(v) not in seen:
                seen.add(id(v))
                b.hbm_lower += nb * mult

    def merge(self, other: "_Acc") -> None:
        for phase, ob in other.buckets.items():
            b = self.bucket(phase)
            b.eqns += ob.eqns
            b.flops += ob.flops
            for d, f in ob.flops_by_dtype.items():
                b.flops_by_dtype[d] = b.flops_by_dtype.get(d, 0.0) + f
            b.hbm_lower += ob.hbm_lower
            b.hbm_upper += ob.hbm_upper
            b.ici_bytes += ob.ici_bytes

    def total_flops(self) -> float:
        return sum(b.flops for b in self.buckets.values())


def _phase_of(eqn, inherited: str) -> str:
    info = getattr(eqn, "source_info", None)
    stack = getattr(info, "name_stack", None) if info is not None else None
    if stack is None:
        return inherited
    found = PHASE_RE.findall(str(stack))
    return found[-1] if found else inherited


def _pallas_leaf(eqn, phase: str, mult: float, acc: _Acc) -> None:
    """pallas_call is a liveness LEAF (the JXA202 convention): HBM at the
    call-site operands/results; FLOPs best-effort from the kernel body x
    grid steps (0 when the grid is unreadable on this jax version)."""
    flops = 0.0
    dtype = "float32"
    try:
        gm = eqn.params.get("grid_mapping")
        grid = tuple(int(g) for g in (getattr(gm, "grid", None) or ()))
        steps = 1
        for g in grid:
            steps *= max(g, 1)
        body = eqn.params.get("jaxpr")
        inner = getattr(body, "jaxpr", body)
        if inner is not None and hasattr(inner, "eqns"):
            flops = sum(eqn_flops(e) for e in inner.eqns
                        if not sub_jaxprs(e)) * steps
        out0 = next((v for v in eqn.outvars if hasattr(v, "aval")), None)
        if out0 is not None:
            dtype = _dtype_name(out0.aval)
    except Exception:  # noqa: BLE001 - a cost estimate must not crash audits
        flops = 0.0
    io = [v for v in eqn.invars if _is_var(v)] + list(eqn.outvars)
    acc.add_eqn(phase, flops, dtype, io, mult)


def _walk(jaxpr, inherited: str, mult: float, acc: _Acc) -> None:
    for eqn in jaxpr.eqns:
        phase = _phase_of(eqn, inherited)
        prim = eqn.primitive.name

        if prim == "pallas_call":
            _pallas_leaf(eqn, phase, mult, acc)
            continue

        if prim == "cond":
            # charge the most expensive branch, not the sum of all
            branch_accs = []
            for br in eqn.params.get("branches", ()):
                sub = getattr(br, "jaxpr", br)
                a = _Acc()
                _walk(sub, phase, mult, a)
                branch_accs.append(a)
            if branch_accs:
                acc.merge(max(branch_accs, key=lambda a: a.total_flops()))
                continue

        subs = sub_jaxprs(eqn)
        if subs:
            submult = mult
            if prim == "scan":
                submult = mult * max(int(eqn.params.get("length", 1) or 1), 1)
            # while bodies are charged once: the trip count is dynamic,
            # so the model is a documented lower bound there
            for sub in subs:
                _walk(sub, phase, submult, acc)
            continue

        out0 = next((v for v in eqn.outvars if hasattr(v, "aval")), None)
        dtype = _dtype_name(out0.aval) if out0 is not None else "float32"
        ici = 0.0
        if prim in COLLECTIVE_PRIMS:
            ici = float(sum(aval_bytes(v.aval) for v in eqn.outvars
                            if hasattr(v, "aval")))
        io = [v for v in eqn.invars if _is_var(v)] + list(eqn.outvars)
        acc.add_eqn(phase, eqn_flops(eqn), dtype, io, mult, ici=ici)


def analyze_jaxpr(jaxpr) -> CostReport:
    """Walk one (raw) jaxpr into a per-phase ``CostReport``. Accepts a
    ClosedJaxpr too (``.jaxpr`` is unwrapped)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    acc = _Acc()
    _walk(jaxpr, "", 1.0, acc)

    from sphexa_tpu.util.phases import PHASES  # lazy: phases.py imports jax

    taxonomy = set(PHASES)
    unattributed = acc.buckets.pop("", None) or PhaseCost(phase=UNATTRIBUTED)
    unattributed.phase = UNATTRIBUTED
    unknown = tuple(sorted(p for p in acc.buckets if p not in taxonomy))
    total = sum(b.flops for b in acc.buckets.values()) + unattributed.flops
    on_tax = sum(b.flops for p, b in acc.buckets.items() if p in taxonomy)
    return CostReport(
        phases=dict(sorted(acc.buckets.items())),
        unattributed=unattributed,
        unknown_scopes=unknown,
        total_flops=total,
        coverage=(on_tax / total) if total > 0 else 1.0,
    )


def cost_report(trace, ctx=None) -> CostReport:
    """Cached per-entry report (the ``spmd_report`` contract: one walk
    per ``EntryTrace``, shared by every JXA3xx rule and the cost CLI)."""
    cached = getattr(trace, "_cost_report", None)
    if cached is not None:
        return cached
    report = analyze_jaxpr(trace.closed_jaxpr.jaxpr)
    trace._cost_report = report
    return report


# ---------------------------------------------------------------------------
# roofline prediction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PhasePrediction:
    phase: str
    flops: float
    hbm_lower: float
    hbm_upper: float
    ici_bytes: float
    ai: float              # FLOPs / fused (lower-bound) HBM bytes
    compute_ms: float
    hbm_ms: float          # fused bytes / HBM BW
    hbm_ms_upper: float    # unfused bytes / HBM BW
    ici_ms: float
    ms: float              # roofline headline: max(compute, hbm, ici)
    ms_upper: float
    bound: str             # "compute" | "memory" | "ici"
    dtype: str

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Prediction:
    device: str
    rows: Tuple[PhasePrediction, ...]   # phases sorted by headline ms desc
    unattributed: PhasePrediction
    total_ms: float                     # all buckets, headline bound
    total_ms_upper: float
    coverage: float
    unknown_scopes: Tuple[str, ...]

    def row(self, phase: str) -> Optional[PhasePrediction]:
        if phase == UNATTRIBUTED:
            return self.unattributed
        return next((r for r in self.rows if r.phase == phase), None)


def _predict_bucket(b: PhaseCost, dev: DeviceModel) -> PhasePrediction:
    compute_s = sum(f / dev.peak_for(d) for d, f in b.flops_by_dtype.items())
    hbm_s = b.hbm_lower / dev.hbm_bytes_per_s
    hbm_up_s = b.hbm_upper / dev.hbm_bytes_per_s
    ici_s = b.ici_bytes / dev.ici_bytes_per_s
    ms = max(compute_s, hbm_s, ici_s) * 1e3
    ms_upper = max(compute_s, hbm_up_s, ici_s) * 1e3
    if ici_s >= max(compute_s, hbm_s):
        bound = "ici"
    elif compute_s >= hbm_s:
        bound = "compute"
    else:
        bound = "memory"
    return PhasePrediction(
        phase=b.phase, flops=b.flops, hbm_lower=b.hbm_lower,
        hbm_upper=b.hbm_upper, ici_bytes=b.ici_bytes,
        ai=b.flops / b.hbm_lower if b.hbm_lower > 0 else float("inf"),
        compute_ms=compute_s * 1e3, hbm_ms=hbm_s * 1e3,
        hbm_ms_upper=hbm_up_s * 1e3, ici_ms=ici_s * 1e3,
        ms=ms, ms_upper=ms_upper, bound=bound, dtype=b.dominant_dtype(),
    )


def predict(report: CostReport, device) -> Prediction:
    """Classify a ``CostReport`` against a device model (name or
    ``DeviceModel``) into the predicted per-phase ms table."""
    dev = device if isinstance(device, DeviceModel) else get_device(device)
    rows = tuple(sorted(
        (_predict_bucket(b, dev) for b in report.phases.values()),
        key=lambda r: -r.ms))
    un = _predict_bucket(report.unattributed, dev)
    return Prediction(
        device=dev.name, rows=rows, unattributed=un,
        total_ms=sum(r.ms for r in rows) + un.ms,
        total_ms_upper=sum(r.ms_upper for r in rows) + un.ms_upper,
        coverage=report.coverage, unknown_scopes=report.unknown_scopes,
    )


def memory_bound_phases(pred: Prediction, dev: Optional[DeviceModel] = None,
                        ) -> List[PhasePrediction]:
    """Phases whose arithmetic intensity sits below the device ridge
    point, heaviest first — the static ranking of ROADMAP item-2's
    fusion/cadence candidates."""
    dev = dev or get_device(pred.device)
    return [r for r in pred.rows if r.ai < dev.ridge(r.dtype)]


# ---------------------------------------------------------------------------
# committed per-phase budget file (the static analog of TELEMETRY_LOCK)
# ---------------------------------------------------------------------------

BUDGET_SCHEMA = 1


def validate_budget(doc: Any) -> List[str]:
    """Schema errors for a COST_BUDGET.json document; [] when valid."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ["budget document is not a JSON object"]
    if doc.get("schema") != BUDGET_SCHEMA:
        errs.append(f"schema must be {BUDGET_SCHEMA}, got {doc.get('schema')!r}")
    try:
        get_device(str(doc.get("device")))
    except ValueError as e:
        errs.append(str(e))
    entries = doc.get("entries")
    if not isinstance(entries, dict) or not entries:
        errs.append("entries must be a non-empty object keyed by entry name")
        return errs
    for name, spec in entries.items():
        if not isinstance(spec, dict):
            errs.append(f"{name}: entry spec is not an object")
            continue
        phases = spec.get("phases")
        if not isinstance(phases, dict) or not phases:
            errs.append(f"{name}: phases must be a non-empty object")
            continue
        for ph, ms in phases.items():
            if not isinstance(ms, (int, float)) or ms <= 0:
                errs.append(f"{name}: phase {ph!r} budget must be a "
                            f"positive number, got {ms!r}")
        total = spec.get("total_ms")
        if total is not None and (not isinstance(total, (int, float))
                                  or total <= 0):
            errs.append(f"{name}: total_ms must be a positive number")
    return errs


def load_budget(path: str) -> Dict[str, Any]:
    """Load + validate a budget file; raises ``ValueError`` with every
    schema problem (a broken gate must not pass silently)."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    errs = validate_budget(doc)
    if errs:
        raise ValueError(f"{path}: " + "; ".join(errs))
    return doc


# ---------------------------------------------------------------------------
# calibration against a measured capture (trace --predict)
# ---------------------------------------------------------------------------

CALIBRATION_FILE = "calibration.json"


def load_calibration(trace_dir: str) -> Optional[Dict[str, Any]]:
    """The capture's committed calibration declaration, or None. Format::

        {"schema": 1,
         "target": "scripts/make_trace_fixture.py::trace_fixture",
         "device": "cpu-smoke", "tolerance": 1.8,
         "phases": {"density": {"ratio": 123.4}, ...}}

    ``ratio`` is the recorded measured_us / predicted_us for the phase;
    the gate holds while fresh ratios stay within ``tolerance`` x of it.
    """
    path = os.path.join(trace_dir, CALIBRATION_FILE)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    errs: List[str] = []
    if not isinstance(doc.get("target"), str) or "::" not in doc["target"]:
        errs.append("target must be '<module-or-file>::<entry-name>'")
    try:
        get_device(str(doc.get("device")))
    except ValueError as e:
        errs.append(str(e))
    phases = doc.get("phases")
    if not isinstance(phases, dict) or not phases:
        errs.append("phases must be a non-empty object")
    else:
        for ph, spec in phases.items():
            r = spec.get("ratio") if isinstance(spec, dict) else None
            if not isinstance(r, (int, float)) or r <= 0:
                errs.append(f"phase {ph!r}: ratio must be a positive number")
    tol = doc.get("tolerance", 2.0)
    if not isinstance(tol, (int, float)) or tol <= 1.0:
        errs.append("tolerance must be a number > 1")
    if errs:
        raise ValueError(f"{path}: " + "; ".join(errs))
    return doc


def predict_for_target(target: str, device: str) -> Prediction:
    """Trace a registry target (``<module-or-file>::<entry>``) and
    predict it — the only jax-loading path in this module."""
    mod_name, _, entry_name = target.partition("::")
    from sphexa_tpu.devtools.audit.cli import _load_target
    from sphexa_tpu.devtools.audit.core import (
        EntryTrace,
        entries_from_namespace,
    )

    mod = _load_target(mod_name)
    entries = {e.name: e for e in entries_from_namespace(vars(mod))}
    if entry_name not in entries:
        raise ValueError(f"{mod_name}: no @entrypoint named {entry_name!r} "
                         f"(has: {sorted(entries)})")
    entry = entries[entry_name]
    trace = EntryTrace(entry, entry.build())
    return predict(cost_report(trace), device)


def calibration_join(summary: Dict[str, Any], calib: Dict[str, Any],
                     ) -> Dict[str, Any]:
    """Join a traceview summary against the static prediction of the
    calibration target; returns rows + band violations.

    A calibrated phase missing from either side is a violation: the
    capture and the program drifting apart is exactly the failure this
    gate exists to catch.
    """
    pred = predict_for_target(calib["target"], calib["device"])
    tol = float(calib.get("tolerance", 2.0))
    measured = {p["phase"]: float(p["us"]) for p in summary.get("phases", ())}
    rows: List[Dict[str, Any]] = []
    violations: List[str] = []
    for phase, spec in sorted(calib["phases"].items()):
        ref = float(spec["ratio"])
        lo, hi = ref / tol, ref * tol
        row: Dict[str, Any] = {"phase": phase, "ref_ratio": ref,
                               "band": [lo, hi]}
        prow = pred.row(phase)
        mus = measured.get(phase)
        if prow is None or prow.ms <= 0:
            row["status"] = "no-prediction"
            violations.append(f"{phase}: no static prediction for the "
                              f"calibration target")
        elif mus is None:
            row["status"] = "no-measurement"
            violations.append(f"{phase}: absent from the measured capture")
        else:
            row["measured_us"] = mus
            row["predicted_us"] = prow.ms * 1e3
            ratio = mus / (prow.ms * 1e3)
            row["ratio"] = ratio
            row["status"] = "ok" if lo <= ratio <= hi else "out-of-band"
            if row["status"] != "ok":
                violations.append(
                    f"{phase}: measured/predicted ratio {ratio:.3g} outside "
                    f"[{lo:.3g}, {hi:.3g}] (recorded {ref:.3g} x tolerance "
                    f"{tol:g}) — the cost rules drifted from the capture")
        rows.append(row)
    return {
        "target": calib["target"],
        "device": calib["device"],
        "tolerance": tol,
        "rows": rows,
        "violations": violations,
        "ok": not violations,
    }
