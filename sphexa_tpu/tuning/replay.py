"""Workload replay: rebuild a run's Simulation and time one candidate.

The harness closes the measurement half of the observe→decide loop: a
``ReplaySpec`` reconstructs a workload either from a telemetry run
manifest (``spec_from_manifest`` — the run that was slow IS the
workload you tune) or from a named init case, and ``measure_candidate``
scores one knob dict on it using the machinery the production driver
already trusts:

* the candidate knobs are applied through the SAME ``tuned=`` path a
  table entry takes (Simulation's direct-dict source), so the sweep
  measures exactly what committing the entry would run;
* timing is the existing sync-free deferred-window clock — the
  candidate runs as one (or more) ``check_every`` windows and the
  objective is the ``window`` event's ``per_step_s``, not a fresh
  ad-hoc ``time.time()`` loop;
* optionally the objective is one PHASE of the per-phase device-time
  table (``objective="phase:gravity-mac"``): the measured window runs
  under a jax.profiler trace and traceview's ``summarize_trace``
  attributes it — tune the phase you are losing, not end-to-end.

Exceptions deliberately propagate: the search driver (search.run_sweep)
is the crash boundary that turns a dead candidate into a ``failed``
sweep event instead of a dead sweep.
"""

import dataclasses
import math
from typing import Dict, Optional

from sphexa_tpu.telemetry import MemorySink, Telemetry, read_manifest

#: knob whose value doubles as the measurement window length
_CADENCE = "check_every"


@dataclasses.dataclass(frozen=True)
class ReplaySpec:
    """One reconstructable workload: a named init case at a given scale
    on a given backend/mesh. Snapshot-file workloads are out of scope
    (replay must be buildable on a machine that only has the manifest)."""

    case: str
    side: int
    prop: str = "std"
    backend: str = "auto"
    theta: float = 0.5
    devices: Optional[int] = None

    @property
    def n(self) -> int:
        return self.side ** 3


def spec_from_manifest(run_dir: str) -> ReplaySpec:
    """Rebuild the workload of a telemetry run from its manifest (the
    app stamps ``config`` = CLI args plus top-level ``case``/``prop``
    keys — ``write_manifest`` splats its ``extra`` dict into the
    manifest root). Raises ``FileNotFoundError`` (no manifest) or
    ``ValueError`` (one that does not describe a replayable case run)."""
    m = read_manifest(run_dir)
    if m is None:
        raise FileNotFoundError(f"{run_dir}: no manifest.json "
                                f"(not a telemetry run dir)")
    cfg = m.get("config") or {}
    case = m.get("case") or cfg.get("init")
    side = cfg.get("side")
    if not case or not side:
        raise ValueError(f"{run_dir}: manifest lacks case/side — "
                         f"cannot reconstruct the workload")
    from sphexa_tpu.init import CASES, split_case_spec

    base, _ = split_case_spec(str(case))
    if base not in CASES:
        raise ValueError(f"{run_dir}: case {case!r} is not a named init "
                         f"case (snapshot replays are unsupported)")
    return ReplaySpec(
        case=str(case), side=int(side),
        prop=str(m.get("prop") or cfg.get("prop") or "std"),
        backend=str(cfg.get("backend") or "auto"),
        theta=float(cfg.get("theta") or 0.5),
        devices=cfg.get("devices"),
    )


def build_case(spec: ReplaySpec):
    """(state, box, const) for the spec — one initializer call, shared
    by every candidate (measure_candidate re-invokes it so a candidate
    that corrupts state cannot poison the next one)."""
    from sphexa_tpu.init import make_initializer

    return make_initializer(spec.case)(spec.side)


def measure_candidate(spec: ReplaySpec, knobs: Dict, steps: int = 6,
                      warmup: int = 1,
                      objective: str = "per_step_s",
                      trace_dir: Optional[str] = None) -> Dict:
    """Score one knob dict on the spec's workload; returns
    ``{status, objective, value, per_step_s, steps, windows, rollbacks,
    reconfigures}``. ``status`` is ``ok``, or ``overflow`` when the run
    needed a rollback/replay (the timing then includes recovery — a
    cap-busting candidate is legal but scored at its true cost and
    flagged). Lower value is better for every objective."""
    from sphexa_tpu.simulation import Simulation

    state, box, const = build_case(spec)
    mem = MemorySink()
    inner = Telemetry(sinks=[mem])
    # the candidate's knobs ride the production tuned= path (direct-dict
    # source); check_every is special — it IS the measurement window, so
    # when the candidate does not sweep it we pin the window to the
    # measured step count (one batched fetch per measurement)
    cadence = int(knobs.get(_CADENCE, steps))
    measured = max(cadence, math.ceil(steps / cadence) * cadence)
    sim = Simulation(
        state, box, const, prop=spec.prop, theta=spec.theta,
        backend=spec.backend, num_devices=spec.devices,
        check_every=None if _CADENCE in knobs else measured,
        tuned=dict(knobs) if knobs else None, workload=spec.case,
        telemetry=inner,
    )
    # warmup windows: compile + first-window jitter stay out of the score
    if warmup > 0:
        sim.run(warmup * cadence)
    mem.events.clear()
    base_rollbacks = inner.counters["rollbacks"]
    base_reconfigs = inner.counters["reconfigures"]
    tracing = objective.startswith("phase:")
    if tracing:
        if not trace_dir:
            raise ValueError(f"objective {objective!r} needs trace_dir")
        import jax

        jax.profiler.start_trace(trace_dir)
    try:
        sim.run(measured)
    finally:
        if tracing:
            import jax

            jax.profiler.stop_trace()
    windows = mem.of_kind("window")
    wall = sum(w["wall_s"] for w in windows)
    done = sum(w["steps"] for w in windows)
    per_step = wall / done if done else float("nan")
    rollbacks = int(inner.counters["rollbacks"] - base_rollbacks)
    result = {
        "status": "overflow" if rollbacks else "ok",
        "objective": objective,
        "value": per_step,
        "per_step_s": per_step,
        "steps": int(done),
        "windows": len(windows),
        "rollbacks": rollbacks,
        "reconfigures": int(inner.counters["reconfigures"]
                            - base_reconfigs),
    }
    if tracing:
        from sphexa_tpu.telemetry.traceview import summarize_trace

        want = objective.split(":", 1)[1]
        summary = summarize_trace(trace_dir)
        row = next((p for p in summary.get("phases", ())
                    if p.get("phase") == want), None)
        if row is None:
            raise ValueError(
                f"phase {want!r} absent from the trace (has: "
                f"{[p.get('phase') for p in summary.get('phases', ())]})")
        # per-step device microseconds of the one phase being tuned
        result["value"] = float(row["us"]) / max(done, 1)
        result["phase_us"] = float(row["us"])
    return result


def static_cost_candidate(spec: ReplaySpec, knobs: Dict, phase: str,
                          device: str = "v5e") -> Dict:
    """Score one knob dict CHIP-FREE (``objective="static-cost:<phase>"``).

    The candidate's knobs ride the same production ``tuned=`` path as
    ``measure_candidate``, but instead of running steps the propagator
    step is TRACED to a jaxpr and the value is the static roofline
    prediction (jaxcost, devtools/audit/costmodel.py) of the target
    phase's ms on the named device model — a sweep can rank candidates
    on a machine with no accelerator at all. The ranking is only as
    good as the cost model: run ``sphexa-telemetry trace <capture>
    --predict`` against a real capture before trusting it
    (docs/STATIC_ANALYSIS.md, calibration workflow).
    """
    import jax

    from sphexa_tpu import propagator as prop
    from sphexa_tpu.devtools.audit.costmodel import analyze_jaxpr, predict
    from sphexa_tpu.simulation import Simulation

    state, box, const = build_case(spec)
    sim = Simulation(
        state, box, const, prop=spec.prop, theta=spec.theta,
        backend=spec.backend, num_devices=spec.devices,
        tuned=dict(knobs) if knobs else None, workload=spec.case,
    )
    cfg, gtree = sim._cfg, sim._gtree
    # one closure per propagator, mirroring the audit registry's step
    # builders so the traced program IS the production step
    steps = {
        "std": lambda s, b: prop.step_hydro_std(s, b, cfg, gtree),
        "ve": lambda s, b: prop.step_hydro_ve(s, b, cfg, gtree),
        "nbody": lambda s, b: prop.step_nbody(s, b, cfg, gtree),
        "turb-ve": lambda s, b: prop.step_turb_ve(
            s, b, cfg, gtree, sim.turb_state, sim.turb_cfg),
        "std-cooling": lambda s, b: prop.step_hydro_std_cooling(
            s, b, cfg, gtree, sim.chem, sim.cooling_cfg),
    }
    if spec.prop not in steps:
        raise ValueError(f"static-cost objective has no step builder for "
                         f"prop {spec.prop!r} (has: {sorted(steps)})")
    jaxpr = jax.make_jaxpr(steps[spec.prop])(sim.state, sim.box)
    pred = predict(analyze_jaxpr(jaxpr), device)
    row = pred.row(phase)
    if row is None or row.ms <= 0:
        raise ValueError(
            f"phase {phase!r} absent from the static prediction (has: "
            f"{[r.phase for r in pred.rows]})")
    return {
        "status": "ok",
        "objective": f"static-cost:{phase}",
        "value": row.ms,
        "predicted_ms": row.ms,
        "ai": row.ai,
        "bound": row.bound,
        "device": pred.device,
        "steps": 0, "windows": 0, "rollbacks": 0, "reconfigures": 0,
    }
