"""jaxaudit core: entry-point model, trace cache, rule registry, runner.

Where jaxlint reads SOURCE (ast, never imports the code), jaxaudit reads
what the TRACER produces: it imports the package, traces each registered
entry point on small synthetic example args, and checks invariants on the
resulting jaxpr / lowered module. The two layers are complementary — an
AST pass structurally cannot see a silent f64 promotion inside a jitted
step, a missed buffer donation, a constant baked into the jaxpr, or a
step-2 retrace; the tracer sees exactly those.

Model
-----
- An ``EntryPoint`` is a *declaration*: a name, audit metadata (declared
  donation, declared mesh axes, const-size budget), and a lazy ``build``
  callable returning an ``EntryCase`` with the traced function + example
  args. Building is lazy so importing a registry module stays cheap and
  device-free (the same hygiene jaxlint enforces on the package).
- ``EntryTrace`` caches everything expensive per entry — the closed
  jaxpr, the lowering, the executed output for the recompile carry — so
  each rule pays only for what it reads and nothing is traced twice.
- Rules are ``check(trace) -> [Finding]`` callables registered under JXA
  ids, mirroring the lint rule registry. Findings anchor at the entry's
  *registration site* (the decorated builder in the registry module), so
  the shared inline-suppression grammar applies:
  ``# jaxaudit: disable=JXA103 -- reason`` on or directly above the
  ``@entrypoint`` line.

``JXA000`` is reserved for entries whose build or trace raises — a broken
registry entry can never silently shrink coverage.
"""

from __future__ import annotations

import dataclasses
import traceback
from pathlib import Path
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
)

from sphexa_tpu.devtools.common import (
    Finding,
    SuppressionTable,
    make_disable_re,
    parse_suppressions,
)

__all__ = [
    "AuditContext",
    "audit_context",
    "set_audit_context",
    "EntryCase",
    "EntryPoint",
    "EntryTrace",
    "EntrySkip",
    "entrypoint",
    "entries_from_namespace",
    "Rule",
    "register",
    "all_rules",
    "Auditor",
]

_DISABLE_RE = make_disable_re("jaxaudit")


@dataclasses.dataclass(frozen=True)
class AuditContext:
    """Process-wide knobs the SPMD (JXA2xx) rules and the registry read.

    ``mesh_size`` is the virtual CPU mesh the sharded registry entries
    trace on (the CLI's --cpu-devices / preflight's --mesh); the
    campaign fields parameterize JXA202's symbolic rescale (per-device
    slab = campaign_n / campaign_devices) and the per-device HBM gate.
    """

    mesh_size: int = 2
    campaign_n: int = 64_000_000
    campaign_devices: int = 16
    hbm_budget_bytes: int = 16 << 30          # v5e: 16 GiB HBM per chip
    repl_threshold_bytes: int = 1 << 20       # campaign-scale replication gate
    # --- jaxcost (JXA3xx / JXA204) knobs ---------------------------------
    # device model the cost rules predict against (devices.py)
    cost_device: str = "v5e"
    # JXA301 default: minimum attributed-FLOP share per entry (per-entry
    # phase_coverage_min overrides; the step builders sit near 1.0)
    phase_coverage_min: float = 0.7
    # JXA302 default budget file (repo-root committed); an entry may pin
    # its own via EntryPoint.cost_budget_file. A missing DEFAULT file
    # skips the gate (out-of-repo use); a missing DECLARED file fails.
    cost_budget_path: str = "COST_BUDGET.json"
    # JXA204: growth-probe slack over linear-in-N for the exempt
    # (non-slab) buffer class
    tree_growth_slack: float = 1.25
    # --- statecheck (JXA5xx) knobs ---------------------------------------
    # JXA501 default schema lock (repo-root committed, like the cost
    # budget); a missing DEFAULT file skips the gate (out-of-repo use)
    state_schema_path: str = "STATE_SCHEMA.json"
    # JXA502 member-axis width for the vmap-batchability probe; 0
    # disables the probe (the package audit/tier-1 default — the vmap
    # report is the `sphexa-audit schema --vmap` gate's job)
    vmap_members: int = 0


_CONTEXT = AuditContext()


def audit_context() -> AuditContext:
    return _CONTEXT


def set_audit_context(ctx: AuditContext) -> AuditContext:
    """Install a new context; returns the previous one (for restore)."""
    global _CONTEXT
    prev = _CONTEXT
    _CONTEXT = ctx
    return prev


class EntrySkip(Exception):
    """Raised by a builder when its environment prerequisites are absent
    (e.g. a sharded entry on a single-device host). Skips are REPORTED,
    not errors — but the tier-1 gate asserts none occur under the test
    mesh, so coverage can't rot silently."""


@dataclasses.dataclass
class EntryCase:
    """The concrete traced case an entry's builder produces.

    ``fn`` takes ONLY traced arguments (close over static configs in the
    builder) so ``jax.make_jaxpr(fn)(*args)`` works directly. ``lower``
    is the AOT lowering thunk for the donation audit — for jitted
    functions return ``jitted.lower(*full_args)`` of the variant the hot
    path actually uses (the donated twin where one exists). ``carry``
    rebuilds step-2 args from (step-1 args, step-1 outputs) for the
    recompile audit; it must only REARRANGE pytree leaves.
    """

    fn: Callable
    args: Tuple[Any, ...]
    lower: Optional[Callable[[], Any]] = None
    carry: Optional[Callable[[Tuple[Any, ...], Any], Tuple[Any, ...]]] = None
    # optional weak-type probe: a variant of ``args`` with host-fed
    # scalars (Python floats where the public API tolerates either);
    # the traced OUTPUT signature must match the canonical one
    perturb: Optional[Callable[[Tuple[Any, ...]], Tuple[Any, ...]]] = None
    # JXA203 volume gate: the analytic cross-shard bytes/step this case
    # is expected to ship (sizing.sparse_need_matrix / shipped_rows
    # derived); None = no volume check for this entry
    exchange_budget_bytes: Optional[int] = None
    # slack factor on the volume gate (negotiation/metrics overhead)
    exchange_slack: float = 2.0
    # JXA204 growth probe: rebuild the SAME entry at a larger toy N
    # (returns (grown EntryCase, n_ratio)); None = no growth probe
    grow: Optional[Callable[[], Tuple["EntryCase", float]]] = None
    # JXA402 knob-inertness probes: a thunk returning the list of
    # lowerdiff.KnobProbe off-vs-unset comparisons this entry vouches
    # for (the registry's knob_inertness entry wires
    # production_knob_probes here); None = rule does not apply
    knob_probes: Optional[Callable[[], Any]] = None


@dataclasses.dataclass
class EntryPoint:
    """A registered auditable entry: declaration + lazy case builder."""

    name: str
    build: Callable[[], EntryCase]
    # positions in the lowered ``args_info`` tuple whose WHOLE pytree
    # must be donated (static args are elided from args_info; count only
    # traced positionals)
    donate: Tuple[int, ...] = ()
    # collective axis names the entry's declared sharding provides;
    # () = unsharded (any named-axis collective is then a finding)
    mesh_axes: Tuple[str, ...] = ()
    # jaxpr-constant size budget (bytes) for the const-bloat audit
    const_bytes_limit: int = 1 << 20
    # trace under jax.enable_x64 (fixture use: the f64
    # rule can't fire with x64 off — jax silently demotes)
    x64: bool = False
    # per-entry override of the JXA202 per-device HBM budget (bytes);
    # None = the AuditContext default (16 GiB)
    hbm_budget: Optional[int] = None
    # JXA301 override: minimum attributed-FLOP share (None = the
    # AuditContext default; 0.0 exempts reconfigure-time programs that
    # legitimately run outside the step-phase taxonomy)
    phase_coverage_min: Optional[float] = None
    # JXA302 override: per-entry budget file instead of the context
    # default COST_BUDGET.json (fixtures pin doctored budgets this way)
    cost_budget_file: Optional[str] = None
    # JXA303: phases this entry DECLARES compute-bound; one of them
    # sitting below the device ridge point is a finding (an interaction
    # kernel that degraded into a bandwidth-bound gather loop)
    expect_compute_bound: Tuple[str, ...] = ()
    path: str = "?"
    line: int = 0


def _display_path(filename: str) -> str:
    """cwd-relative posix path when possible: findings (and therefore the
    committed baseline's (rule, path, hash) keys) must not embed the
    machine-specific absolute checkout path, or a baseline written on one
    machine never matches on another."""
    p = Path(filename)
    try:
        return p.relative_to(Path.cwd()).as_posix()
    except ValueError:
        return p.as_posix()


def entrypoint(name: str, *, donate: Tuple[int, ...] = (),
               mesh_axes: Tuple[str, ...] = (),
               const_bytes_limit: int = 1 << 20,
               x64: bool = False,
               hbm_budget: Optional[int] = None,
               phase_coverage_min: Optional[float] = None,
               cost_budget_file: Optional[str] = None,
               expect_compute_bound: Tuple[str, ...] = ()) -> Callable:
    """Decorator: declare a builder function as an audit entry point.

    The decorated function runs lazily (per audit run) and returns an
    ``EntryCase``. The binding in the module namespace becomes the
    registry entry; findings anchor at the builder's definition line.
    """

    def deco(build: Callable[[], EntryCase]) -> EntryPoint:
        code = getattr(build, "__code__", None)
        return EntryPoint(
            name=name, build=build, donate=tuple(donate),
            mesh_axes=tuple(mesh_axes),
            const_bytes_limit=const_bytes_limit, x64=x64,
            hbm_budget=hbm_budget,
            phase_coverage_min=phase_coverage_min,
            cost_budget_file=cost_budget_file,
            expect_compute_bound=tuple(expect_compute_bound),
            path=_display_path(code.co_filename) if code else "?",
            line=code.co_firstlineno if code else 0,
        )

    return deco


def entries_from_namespace(ns: Dict[str, Any]) -> List[EntryPoint]:
    """Collect EntryPoint bindings from a module namespace, in source
    order (the module-level registry contract: decorate builders with
    ``@entrypoint`` and this picks them up — no global mutable state)."""
    entries = [v for v in ns.values() if isinstance(v, EntryPoint)]
    names = [e.name for e in entries]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise ValueError(f"duplicate audit entry name(s): {sorted(dupes)}")
    return sorted(entries, key=lambda e: (e.path, e.line))


# ---------------------------------------------------------------------------
# per-entry trace cache
# ---------------------------------------------------------------------------


class EntryTrace:
    """Lazily computed, cached trace artifacts for one entry.

    Rules pull ``closed_jaxpr`` (tracing only — no compile), ``lowered``
    (AOT lowering — no compile), or ``out`` (one real execution, only the
    recompile rule needs it: weak_type does not survive into
    ShapeDtypeStructs, so carried avals must come from concrete outputs).
    """

    def __init__(self, entry: EntryPoint, case: EntryCase):
        self.entry = entry
        self.case = case
        self._closed = None
        self._out_shape = None
        self._lowered = None
        self._out = dataclasses.MISSING

    def _x64_scope(self):
        import contextlib

        if not self.entry.x64:
            return contextlib.nullcontext()
        import jax

        return jax.enable_x64()

    @property
    def closed_jaxpr(self):
        if self._closed is None:
            import jax

            with self._x64_scope():
                # return_shape=True: the SAME trace also yields the
                # output pytree of ShapeDtypeStructs, so statecheck's
                # schema inference costs no extra trace
                self._closed, self._out_shape = jax.make_jaxpr(
                    self.case.fn, return_shape=True)(*self.case.args)
        return self._closed

    @property
    def out_shape(self):
        """Output pytree of ShapeDtypeStructs (same trace as the jaxpr);
        ``closed_jaxpr.out_avals`` carries the matching flat-order
        weak_type bits."""
        if self._out_shape is None:
            self.closed_jaxpr  # noqa: B018 - fills the cache
        return self._out_shape

    @property
    def lowered(self):
        if self._lowered is None and self.case.lower is not None:
            with self._x64_scope():
                self._lowered = self.case.lower()
        return self._lowered

    @property
    def out(self):
        if self._out is dataclasses.MISSING:
            with self._x64_scope():
                self._out = self.case.fn(*self.case.args)
        return self._out

    def finding(self, rule: str, message: str) -> Finding:
        e = self.entry
        return Finding(rule=rule, path=e.path, line=e.line, col=0,
                       message=f"[{e.name}] {message}",
                       snippet=f"entry:{e.name}")


# ---------------------------------------------------------------------------
# rule registry (mirrors devtools/lint/core.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    description: str
    check: Callable[[EntryTrace], List[Finding]]


_REGISTRY: Dict[str, Rule] = {}


def register(id: str, name: str, description: str):
    """Decorator: register ``check(trace) -> [Finding]`` under a rule id."""

    def deco(fn: Callable[[EntryTrace], List[Finding]]):
        if id in _REGISTRY:
            raise ValueError(f"duplicate rule id {id}")
        _REGISTRY[id] = Rule(id=id, name=name, description=description,
                             check=fn)
        return fn

    return deco


def all_rules() -> Dict[str, Rule]:
    # importing the rules package populates the registry
    import sphexa_tpu.devtools.audit.rules  # noqa: F401

    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


class Auditor:
    def __init__(self, select: Optional[Sequence[str]] = None):
        rules = all_rules()
        if select:
            unknown = set(select) - set(rules)
            if unknown:
                raise ValueError(f"unknown rule id(s): {sorted(unknown)}")
            rules = {k: v for k, v in rules.items() if k in select}
        self.rules = rules
        self._suppressions: Dict[str, SuppressionTable] = {}

    def _suppression_table(self, path: str) -> SuppressionTable:
        if path not in self._suppressions:
            try:
                source = Path(path).read_text()
            except OSError:
                source = ""
            self._suppressions[path] = parse_suppressions(source, _DISABLE_RE)
        return self._suppressions[path]

    def run_entries(self, entries: Sequence[EntryPoint]
                    ) -> Tuple[List[Finding], List[Finding], List[Finding],
                               List[str]]:
        """(active, suppressed, errors, skipped_names) over the entries.

        A builder/trace failure becomes a ``JXA000`` pseudo-finding (not
        suppressible away by accident: it carries the exception). An
        ``EntrySkip`` lands in ``skipped_names`` for the caller to gate.
        """
        active: List[Finding] = []
        suppressed: List[Finding] = []
        errors: List[Finding] = []
        skipped: List[str] = []
        for entry in entries:
            try:
                case = entry.build()
            except EntrySkip as e:
                skipped.append(f"{entry.name}: {e}")
                continue
            except Exception as e:  # noqa: BLE001 - reported as JXA000
                errors.append(Finding(
                    rule="JXA000", path=entry.path, line=entry.line, col=0,
                    message=f"[{entry.name}] entry build failed: "
                            f"{e.__class__.__name__}: {e}",
                ))
                continue
            trace = EntryTrace(entry, case)
            table = self._suppression_table(entry.path)
            for rule in self.rules.values():
                try:
                    found = rule.check(trace)
                except Exception as e:  # noqa: BLE001 - reported as JXA000
                    tb = traceback.format_exc(limit=3)
                    errors.append(Finding(
                        rule="JXA000", path=entry.path, line=entry.line,
                        col=0,
                        message=f"[{entry.name}] {rule.id} crashed: "
                                f"{e.__class__.__name__}: {e}\n{tb}",
                    ))
                    continue
                for f in found:
                    if table.is_suppressed(f.rule, f.line):
                        suppressed.append(f)
                    else:
                        active.append(f)
        key = lambda f: (f.path, f.line, f.rule, f.message)
        return (sorted(active, key=key), sorted(suppressed, key=key),
                sorted(errors, key=key), skipped)
