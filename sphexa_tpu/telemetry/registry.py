"""The metrics registry: counters, typed events, host spans, compiles.

One ``Telemetry`` instance is shared by everything that measures a run —
the Simulation driver, the app loop, the benchmark — so every surface reports
into the same place instead of disconnected ones (the pre-telemetry
state: util/timer.py wall laps and the per-step diagnostics dict).

Host-side only, by construction: nothing here touches device arrays.
Callers hand in already-host scalars (floats, ints); the zero-sync
deferred-window contract lives in the CALLERS (Simulation.step/flush)
and is pinned by tests/test_telemetry.py.
"""

import itertools
import threading
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional

import numpy as np

#: events.jsonl schema version; bump on any incompatible field change and
#: document the migration in docs/OBSERVABILITY.md. v2 added the
#: distributed kinds (exchange / shard_load / memory / imbalance), v3
#: the physics-observability kinds (physics / numerics / drift /
#: field_health), v4 the time-and-history kinds (phase_attr / crash),
#: v5 the autotuning kinds (sweep / tuning), v6 the block-timestep kind
#: (dt_bins); v7 the optional ``stage`` payload ("sph" | "gravity") on
#: the exchange / shard_load kinds — the gravity near field's MAC-sized
#: sparse serve emits its own exchange record next to the SPH one (no
#: new kinds and no new REQUIRED fields); v8 the live-science-surface
#: kind (snapshot) — in-graph field-grid frames riding the flush
#: boundary (observables/snapshot.py), rendered by ``sphexa-telemetry
#: serve``. v8 only ADDS a kind, so v8 readers accept v1-v7 files
#: strictly clean and v7 readers count ``snapshot`` under unknown_kinds;
#: v9 the host-span kind (span): ``Telemetry.span`` times the driver's
#: and the dump's host work where it happens, on ``perf_counter_ns`` and
#: in the profiler capture under the same name. v9 only ADDS a kind too;
#: v10 the optional WHY payload on ``rebuild_lists`` (``reason``,
#: ``age_steps``, ``slack``, ``slot_need``, ``slot_cap``, ``attempts``),
#: and the event is emitted once per rebuild that BUILT a list (before
#: v10: once per call, built or not). Like v7 it adds no kind and no
#: REQUIRED field, so v10 readers accept v1-v9 files strictly clean;
#: v11 the planned check window: optional ``planned_steps`` on ``window``
#: (what the driver's plan allowed, <= ``check_every``; a window may now
#: be shorter than ``check_every``) and optional ``rate`` /
#: ``cover_steps`` on ``rebuild_lists`` (the skin fraction per step the
#: plan was made with, and the life predicted for the outgoing list).
#: No kind, no REQUIRED field: v11 readers accept v1-v10 files clean;
#: v12 the flat lane table's occupancy: optional ``slots_live`` /
#: ``slots_cap`` on ``rebuild_lists`` (rows of the pair lists' flat
#: lane table in use, each group's kept chunks rounded up to the 8-row
#: tile, and the static row budget they were built into). No kind, no
#: REQUIRED field: v12 readers accept v1-v11 files clean;
#: v13 the tree solve's list occupancies: optional ``cand_fill`` /
#: ``m2p_fill`` / ``p2p_fill`` on ``window`` and ``step`` where the
#: steps solved gravity (live slots over lists x cap of the superblock
#: candidate lists and of the blocks' M2P and P2P lists, averaged over
#: the window's steps: how far the block loop's width-following stages
#: engage). No kind, no REQUIRED field: v13 readers accept v1-v12 files;
#: v14 the run axis of a sparse exchange: optional ``run_slots`` /
#: ``live_runs_max`` on ``exchange`` (the sized slots of the exchange's
#: run axis, ``PropagatorConfig.halo_runs`` / ``GravityConfig
#: .p2p_run_cap``, and the fullest group's or block's live runs on the
#: fullest shard: the exchange's per-slot index work goes with the
#: first, a trip is the second passing it). No kind, no REQUIRED field:
#: v14 readers accept v1-v13 files;
#: v15 the cooling step's limiter and source: optional ``dt_cool_min`` /
#: ``du_cool_min`` on ``numerics`` where the step carries the
#: diagnostics ``dt_cool`` / ``du_cool_min`` (std-cooling): the window's
#: smallest cooling-time limit ct_crit * min|u / du_dt| and its most
#: negative step-averaged cooling source, over finite samples like the
#: other extrema. No kind, no REQUIRED field: v15 readers accept v1-v14
#: files;
#: v16 the radiated-energy counter: optional ``e_cool`` / ``e_cool_step``
#: on ``numerics`` where the step carries ``e_cool_rate`` = sum(m du_cool)
#: (std-cooling): the energy the cooling source gave the gas (negative:
#: radiated) over every verified step so far, and per verified step of
#: the window (a list, parallel to ``physics.its``), with the
#: integrator's Adams-Bashforth weights; ``etot - e_cool`` is what such a
#: run conserves. No kind, no REQUIRED field: v16 readers accept v1-v15
#: files;
#: v17 the compaction kernel's live chunks: optional
#: ``prepass_chunk_live`` / ``compact_chunk_live`` on ``window`` and
#: ``step`` beside the v13 fills: chunks of 128 slots that hold a live
#: lane ÷ chunks the kernel's walk visits, of the superblocks' pre-pass
#: (rows x every chunk of the full tree or the LET list) and of the
#: blocks' main pass (rows x the chunks up to their superblock's count);
#: a dead chunk costs the kernel a scalar test, a live one three MXU
#: products a class. 0 where the solve runs no such pass. No kind, no
#: REQUIRED field: v17 readers accept v1-v16 files;
#: v18 the pair lists' run tiles: optional ``chunks_live`` / ``runs_live``
#: / ``run_rows`` on ``rebuild_lists`` beside the v12 rows: the chunks
#: that keep a lane (a pass visits each once; ``slots_live`` rounds them
#: up to the row tile a group), the runs they lie in, and the rows a
#: run's copy fetches (``pallas_pairs.list_run_rows``: a run is a tile of
#: at most that many chunks). ``chunks_live / (runs_live x run_rows)`` is
#: the share of the rows a pass fetches that a lane is taken from. No
#: kind, no REQUIRED field: v18 readers accept v1-v17 files;
#: v19 the mesh's sort of an aux state: ``exchange`` gains the stage
#: ``"sort"`` beside ``"sph"`` and ``"gravity"``, emitted where a step
#: carries a per-particle aux pytree (std-cooling's chemistry) through
#: the global SFC sort on a mesh. There ``rows`` is the number of rows
#: sorted (an int, not a per-shard list), ``shipped_rows`` what each
#: device receives for the gather GSPMD makes of it ((P - 1) slabs), and
#: the optional ``migrant_rows`` the rows whose sorted position lies on
#: another slab than they came from, in the window's last step:
#: ``migrant_rows / rows`` is the share of that exchange that is real
#: redistribution. No kind, no REQUIRED field: v19 readers accept v1-v18
#: files;
#: v20 persistent pair lists on a mesh: ``exchange`` of stage ``"sph"``
#: gains the optional ``layout_age_steps``, the steps the send layout the
#: newest launch shipped over had served when it shipped: a list step's
#: layout is frozen with its lists at their rebuild (``PairLists.halo``)
#: and ages with them; a streamed step negotiates its own and reads 0.
#: The ``rebuild_lists`` event is emitted on a mesh with the fields it
#: has (``slot_need`` / ``slots_live`` the fullest slab's, ``chunks_live``
#: / ``runs_live`` the slabs' sums). No kind, no REQUIRED field: v20
#: readers accept v1-v19 files;
#: v21 the compile kind (compile): what a program cost to come by, folded
#: from jax.monitoring's per-compile events into one event a program
#: (``_on_duration`` below): the outermost trace, the lowering, the
#: backend's time, and whether the persistent cache held the executable.
#: ``retrace`` says THAT a launch traced; ``compile`` says what it cost.
#: v21 only ADDS a kind: v21 readers accept v1-v20 files strictly clean
#: and a v20 reader counts ``compile`` under unknown_kinds.
SCHEMA_VERSION = 21

#: event schema versions this reader understands (older versions only
#: ever ADD kinds, so the per-kind field table below covers them all)
SUPPORTED_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15,
                      16, 17, 18, 19, 20, 21)

#: every event kind the schema admits, with its required payload fields
#: (beyond the envelope ``v``/``seq``/``t``/``kind``). The CLI's --strict
#: validation enforces exactly this table.
EVENT_KINDS: Dict[str, tuple] = {
    "launch": ("it",),            # one deferred-window step dispatched
    "step": ("it", "wall_s"),     # one synchronously checked step done
    # deferred flush; since v11 with the optional ``planned_steps``;
    # since v13 (as "step") with the optional list fills of a gravity
    # run, since v17 with its compaction kernel's live-chunk shares
    "window": ("it", "steps", "wall_s", "per_step_s"),
    "reconfigure": ("it", "reason"),
    "rollback": ("it", "steps", "reason"),
    "replay": ("it", "steps"),
    "retrace": ("it", "delta"),   # jit cache grew on a launch (recompile)
    # persistent pair lists (re)built; since v10 with the optional WHY
    # payload: reason, age_steps, slack, slot_need, slot_cap, attempts;
    # since v11 also rate, cover_steps; since v12 slots_live, slots_cap;
    # since v18 chunks_live, runs_live, run_rows
    "rebuild_lists": ("it",),
    "phases": ("it",),            # per-iteration host phase laps
    "trace": ("dir",),            # jax.profiler trace started
    "run_end": (),
    "note": (),
    # -- v2: distributed kinds (one run, P shards) ------------------------
    # per-window halo-exchange record: ``rows`` = per-shard TRUE candidate
    # need (device-measured), ``shipped_rows`` = the static sized volume
    # actually moved per serve (sum(hmax) sparse / (P-1)*Wmax windowed);
    # since v14 with the optional ``run_slots`` / ``live_runs_max``;
    # since v19 also stage "sort": ``rows`` the rows sorted (an int),
    # with the optional ``migrant_rows``; since v20 (stage ``sph``) with
    # the optional ``layout_age_steps``
    "exchange": ("it", "shipped_rows", "rows"),
    # per-window load record: per-shard particle counts + work proxies
    "shard_load": ("it", "particles"),
    # per-device HBM snapshot at a named point (manifest / post-compile /
    # flush); bytes lists are empty on backends without memory_stats()
    "memory": ("point",),
    # imbalance watchdog: max/mean of a per-shard metric crossed the
    # configured ratio (the runtime analog of the retrace watchdog)
    "imbalance": ("it", "metric", "ratio", "threshold"),
    # -- v3: physics-observability kinds (the in-graph science ledger) ----
    # per-window conservation record: parallel per-step lists (``its``,
    # ``t``, ``dt``, ``etot``/``ecin``/``eint``/``egrav``, ``linmom``,
    # ``angmom``, optional ``extra``) — every step keeps its row even
    # under deferred checking
    "physics": ("it", "etot"),
    # per-window numerics health: dt-limiter histogram, neighbor-cap
    # clip / h-saturation counts, nonfinite counts, field extrema; since
    # v15 with the optional ``dt_cool_min`` / ``du_cool_min``; since v16
    # with the optional ``e_cool`` / ``e_cool_step``
    "numerics": ("it",),
    # conservation-drift watchdog: |etot - etot0|/|etot0| crossed the
    # configured budget (Simulation(drift_budget=...) / --drift-budget)
    "drift": ("it", "drift", "budget"),
    # field-health watchdog: nonfinite rho/h/du values appeared in a
    # verified step (localize with --debug-checks)
    "field_health": ("it", "nonfinite"),
    # -- v4: time-and-history kinds (profiler attribution + crash) --------
    # per-phase device-time attribution of a --trace-dir capture
    # (telemetry/traceview.py over the jax.profiler dump): ``phases`` =
    # {"<phase>": device_us}, plus coverage/total_device_us/dir context
    "phase_attr": ("phases",),
    # crash flight recorder (telemetry/flightrec.py): appended by the
    # abnormal-exit hooks alongside blackbox.json so the event stream
    # itself records WHY it ends mid-run
    "crash": ("reason",),
    # -- v5: autotuning kinds (sphexa_tpu/tuning/) ------------------------
    # one sweep candidate measured by the replay harness: the knob dict
    # tried, its status ("ok" / "overflow" / "failed"), and on success
    # the objective name + value (per_step_s, or phase:<name> device us)
    "sweep": ("candidate", "knobs", "status"),
    # one tuning decision: where the active knobs came from ("table" /
    # "heuristic" / "explicit"), plus key/knobs/provenance context —
    # also emitted by gravity_tuning when N sits within 10% of its
    # step-function threshold (the near-cliff attribution note)
    "tuning": ("source",),
    # -- v6: block-timestep kind (sph/blockdt.py) -------------------------
    # per-window hierarchical block-dt record: ``pop`` = the (dt_bins,)
    # bin-occupancy histogram at the window's last substep, ``updates``/
    # ``updates_full`` = particle updates performed vs the global-dt cost
    # of the same substeps (the chip-free complexity proxy, docs/NEXT.md),
    # plus the drift-aware resort decision counters (resorts/keeps) and
    # the worst observed key-drift inversion count (drift_max)
    "dt_bins": ("it", "pop", "updates", "updates_full"),
    # -- v8: live-science-surface kind (observables/snapshot.py) ----------
    # one in-graph snapshot frame fetched at the check/flush boundary:
    # grid meta + per-field extrema inline (``fields``/``grid``/``axis``/
    # ``reduce``/``vmin``/``vmax``), pixels in the sidecar ``snapshots/``
    # .npz ring with ``path`` as the pointer (null when no ring dir is
    # configured) — rendered by ``sphexa-telemetry serve``
    "snapshot": ("it", "fields", "grid"),
    # -- v9: host-span kind (Telemetry.span) ------------------------------
    # one closed host span: ``name`` ("sphexa:<what>"), per-process
    # ``id``, ``parent`` (id of the innermost span open in the same
    # thread, null at top level), ``it`` (the iteration at which the
    # current check window or checked step opened: a window's and the
    # following dump's spans share it), ``t0_ns``/``dur_ns`` on
    # ``time.perf_counter_ns``, plus the span's own payload. Emitted at
    # exit, so children precede their parent in the stream
    "span": ("name", "id", "parent", "it", "t0_ns", "dur_ns"),
    # -- v21: compile kind (the jax.monitoring listeners below) -----------
    # one program compiled or loaded: ``fun`` ("jit(<name>)"), seconds of
    # the outermost trace / the lowering / the backend (``backend_s`` holds
    # the retrieval on a hit), ``cache`` ("hit" | "miss" | "off"),
    # ``retrieval_s`` / ``saved_s`` as jax reports them on a hit (else 0),
    # ``t1_ns`` (``perf_counter_ns`` when the backend returned), ``it`` and
    # ``parent`` as on a span: the innermost span open in the compiling
    # thread, so a compile belongs to the launch, the sizing pass or the
    # initialiser it happened under
    "compile": ("fun", "trace_s", "lower_s", "backend_s", "cache",
                "retrieval_s", "saved_s", "t1_ns", "it", "parent"),
}

#: first schema version each kind appeared in (an older-versioned event
#: carrying a newer kind is writer confusion, not forward compatibility)
_KINDS_ADDED = {
    2: ("exchange", "shard_load", "memory", "imbalance"),
    3: ("physics", "numerics", "drift", "field_health"),
    4: ("phase_attr", "crash"),
    5: ("sweep", "tuning"),
    6: ("dt_bins",),
    8: ("snapshot",),
    9: ("span",),
    21: ("compile",),
}
KIND_SINCE: Dict[str, int] = {
    **dict.fromkeys(EVENT_KINDS, 1),
    **{k: v for v, kinds in _KINDS_ADDED.items() for k in kinds},
}

#: kinds that already existed in schema v1 (kept for introspection)
V1_KINDS = frozenset(k for k, v in KIND_SINCE.items() if v == 1)


def _jsonable(v):
    """Coerce numpy scalars/arrays so sinks can json.dumps payloads
    directly (per-shard metrics arrive as small (P,) arrays)."""
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v


def validate_event(e: dict) -> List[str]:
    """Schema problems with one event dict ([] = valid). Any supported
    version validates (v3 readers accept v1/v2 files). An UNKNOWN kind
    is deliberately NOT a problem here — unknownness is the
    forward-compat dimension the reader reports separately (summary's
    ``unknown_kinds`` counts, strict exit code), and flagging it twice
    would render every future-schema event as schema-invalid noise. A
    newer-only kind claiming an older ``v`` IS a problem (writer
    confusion, not forward compat)."""
    problems = []
    if not isinstance(e, dict):
        return ["event is not an object"]
    if e.get("v") not in SUPPORTED_VERSIONS:
        problems.append(f"bad schema version {e.get('v')!r}")
    kind = e.get("kind")
    if kind in EVENT_KINDS:
        since = KIND_SINCE[kind]
        if e.get("v") in SUPPORTED_VERSIONS and e["v"] < since:
            problems.append(
                f"v{since}-only kind {kind!r} on a v{e['v']} event")
        else:
            for field in EVENT_KINDS[kind]:
                if field not in e:
                    problems.append(f"{kind} event missing field {field!r}")
    for field in ("seq", "t"):
        if not isinstance(e.get(field), (int, float)):
            problems.append(f"missing/non-numeric envelope field {field!r}")
    return problems


#: per-process span ids: one sequence over every Telemetry instance, so
#: an id names one span of a profiler capture whichever registry made it
_SPAN_IDS = itertools.count(1)
#: the spans open in each thread, innermost last
_OPEN = threading.local()
#: jax.profiler.TraceAnnotation, resolved on the first span (None = not
#: tried yet, False = jax unavailable: the telemetry CLI never imports jax)
_TRACE_ANNOTATION = None
#: the registry that spans opened without a handle, and the compile
#: listeners, report to: the latest Simulation's, else the one
#: ``set_current`` named
_CURRENT: Optional["Telemetry"] = None
#: what closed while no registry was current (the initialiser's span and
#: compiles, when the caller constructs its Simulation afterwards):
#: ``(kind, payload)``, the oldest dropped past PENDING_MAX, handed to the
#: first registry ``set_current`` names
PENDING_MAX = 256
_PENDING: deque = deque(maxlen=PENDING_MAX)


def _trace_annotation():
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:
            _TRACE_ANNOTATION = False
    return _TRACE_ANNOTATION


def _report(tel, kind, payload):
    """Emit on ``tel``, or keep for the next current registry."""
    if tel is None:
        _PENDING.append((kind, payload))
    else:
        tel.event(kind, **payload)


class Span:
    """One open host span (``Telemetry.span``). Item assignment adds to
    the payload of the event it emits on exit, for what is known only
    once the work is done (``sp["bytes"] = n``). Opened without a handle
    (``tel`` None) it reports to the registry current when it CLOSES, and
    with none current then, to the pending list."""

    __slots__ = ("_tel", "_name", "_payload", "_ann", "_t0", "_it",
                 "id", "parent")

    def __init__(self, tel, name, payload):
        self._tel, self._name, self._payload = tel, name, payload

    def __setitem__(self, key, value):
        self._payload[key] = value

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self.id = next(_SPAN_IDS)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._it = None if self._tel is None else self._tel.iteration
        ann = _trace_annotation()
        self._ann = ann(self._name, id=self.id) if ann else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _OPEN.stack.pop()
        tel, it = self._tel, self._it
        if tel is None:
            tel = _CURRENT
            it = 0 if tel is None else tel.iteration
        _report(tel, "span", dict(
            name=self._name, id=self.id, parent=self.parent, it=it,
            t0_ns=self._t0, dur_ns=dur, **self._payload))
        return False


# ---------------------------------------------------------------------------
# the compile listeners: jax.monitoring's per-compile events folded into
# one ``compile`` event a program. jax fires them synchronously on the
# compiling thread, and only when it traces, lowers or compiles: a launch
# of a compiled program reaches no listener.
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = "/jax/compilation_cache/"
#: None = not tried yet, True = registered, False = jax unavailable
_LISTENING = None
#: the pieces of the compile in flight in each thread
_FOLD = threading.local()


def _count_callback():
    tel = _CURRENT
    if tel is not None:
        tel.counters["compile_callbacks"] += 1


def _on_event(event, **kwargs):
    """``cache_hits`` / ``compile_requests_use_cache``, between a
    program's lowering and the end of its backend compile."""
    _count_callback()
    if event.startswith(_CACHE_EVENTS):
        _FOLD.__dict__.setdefault("cache", set()).add(
            event[len(_CACHE_EVENTS):])


def _on_duration(event, duration, **kwargs):
    """Keep a compile's pieces as they arrive; ``backend_compile_duration``
    is the last of them and emits the event. Nested jits trace inside the
    outermost and report before it, under their own names: the trace kept
    is the last one named as the lowered module is (``jit(<name>)``)."""
    _count_callback()
    pieces = _FOLD.__dict__
    if event == _TRACE_EVENT:
        pieces.setdefault("traces", {})[kwargs.get("fun_name")] = duration
    elif event == _LOWER_EVENT:
        pieces["lower"] = (kwargs.get("fun_name"), duration)
    elif event.startswith(_CACHE_EVENTS):
        pieces[event[len(_CACHE_EVENTS):]] = duration
    elif event == _BACKEND_EVENT:
        fun = str(kwargs.get("fun_name"))
        lowered, lower_s = pieces.get("lower", (None, 0.0))
        traces = pieces.get("traces", {})
        cache = pieces.get("cache", ())
        stack = getattr(_OPEN, "stack", None)
        tel = _CURRENT
        payload = dict(
            fun=fun,
            trace_s=traces.get(fun[fun.find("(") + 1:-1], 0.0),
            lower_s=lower_s if lowered == fun else 0.0,
            backend_s=duration,
            cache=("hit" if "cache_hits" in cache
                   else "miss" if "compile_requests_use_cache" in cache
                   and _cache_dir() else "off"),
            retrieval_s=pieces.get("cache_retrieval_time_sec", 0.0),
            saved_s=pieces.get("compile_time_saved_sec", 0.0),
            t1_ns=time.perf_counter_ns(),
            it=0 if tel is None else tel.iteration,
            parent=stack[-1] if stack else None)
        pieces.clear()
        _report(tel, "compile", payload)


def _cache_dir():
    """The persistent cache's directory, if one is in effect: jax asks its
    cache for every program (``compile_requests_use_cache``) whether or
    not it has anywhere to keep one."""
    import jax

    return jax.config.jax_compilation_cache_dir


def _listen():
    """Register the two listeners, once a process (jax imported lazily,
    like ``_trace_annotation``; without jax nothing compiles)."""
    global _LISTENING
    if _LISTENING is None:
        try:
            from jax import monitoring
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _LISTENING = True
        except Exception:
            _LISTENING = False


class Telemetry:
    """Counters + an event stream over sinks + host spans.

    With no sinks the registry still accumulates (retrace/rollback
    counts can be read without writing files); ``event()``
    then costs one Counter bump — cheap enough for the hot loop.
    """

    def __init__(self, sinks=()):
        self.sinks = list(sinks)
        self.counters: Counter = Counter()
        #: the iteration at which the driver's current check window (or
        #: checked step) opened; every span is stamped with it
        self.iteration = 0
        self._seq = 0

    # -- scalar metrics ----------------------------------------------------
    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n

    # -- event stream ------------------------------------------------------
    def event(self, kind: str, **payload) -> None:
        """Emit one typed event to every sink (and count it regardless)."""
        self.counters[f"events.{kind}"] += 1
        if not self.sinks:
            return
        e = {
            "v": SCHEMA_VERSION,
            "seq": self._seq,
            "t": round(time.time(), 6),
            "kind": kind,
            **{k: _jsonable(v) for k, v in payload.items()},
        }
        self._seq += 1
        for s in self.sinks:
            s.emit(e)

    def phases(self, it: int, laps: Dict[str, float]) -> None:
        """Per-iteration host phase laps (the Timer's pop) as one event."""
        self.event("phases",
                   it=int(it), **{k: round(float(v), 6)
                                  for k, v in laps.items()})

    # -- host spans --------------------------------------------------------
    def span(self, name: str, **payload) -> Span:
        """Context manager timing one stretch of host work where it
        happens. On exit it emits one ``span`` event (``t0_ns``/``dur_ns``
        on ``time.perf_counter_ns``, the clock of a harness's own
        ``perf_counter`` spans; ``id``, ``parent``, ``it``; the payload),
        so the span lands in whatever sinks the run has and with none
        costs the Counter bump of ``event``. For its whole extent it also
        holds ``jax.profiler.TraceAnnotation(name, id=id)``, which puts
        the same span, under the same name, on the clock of a profiler
        capture. Host-only: a span never touches a device array."""
        return Span(self, name, payload)

    # -- console routing ---------------------------------------------------
    def console_printer(self, fallback: Callable = print) -> Callable:
        """The first console sink's line writer, else ``fallback`` —
        Simulation.run routes its per-iteration report through this."""
        for s in self.sinks:
            w = getattr(s, "write_line", None)
            if w is not None:
                return w
        return fallback

    def close(self) -> None:
        global _CURRENT
        if _CURRENT is self:
            # a closed JsonlSink reopens (and truncates) on its next emit
            _CURRENT = None
        for s in self.sinks:
            s.close()


def set_current(telemetry: Optional[Telemetry]) -> None:
    """Name the registry that handle-less spans and the compile listeners
    report to, and hand it what closed while none was current. Called
    where a ``Simulation`` is constructed and by ``main()``;
    ``Telemetry.close`` un-names a registry that is current."""
    global _CURRENT
    _listen()
    _CURRENT = telemetry
    while telemetry is not None and _PENDING:
        kind, payload = _PENDING.popleft()
        telemetry.event(kind, **payload)


def current() -> Optional[Telemetry]:
    """The process-current registry (None: no Simulation constructed or
    ``main()`` entered yet, or the last one closed)."""
    return _CURRENT


def span(name: str, **payload) -> Span:
    """A span for code that is called without a telemetry handle
    (``analysis/compare.py``, ``io/snapshot.py``, ``init``): it reports to
    the process-current registry, and with none current when it closes it
    is kept for the first one ``set_current`` names."""
    _listen()
    return Span(None, name, payload)


# ---------------------------------------------------------------------------
# lap timing + per-iteration series (the util/timer.py implementations,
# now living on the registry so every consumer shares one accumulation)
# ---------------------------------------------------------------------------


class LapTimer:
    """Accumulates named wall-clock laps within one iteration
    (timer.hpp:46 semantics); ``pop`` hands them to whoever records the
    iteration (``StepSeries.record`` / ``Telemetry.phases``)."""

    def __init__(self):
        self.laps: Dict[str, float] = {}
        self._t = time.perf_counter()

    def start(self) -> None:
        self._t = time.perf_counter()

    def lap(self, name: str) -> float:
        """Record time since the last mark under ``name``."""
        now = time.perf_counter()
        elapsed = now - self._t
        self.laps[name] = self.laps.get(name, 0.0) + elapsed
        self._t = now
        return elapsed

    # reference-parity alias (util/timer.hpp's Timer::step)
    step = lap

    def pop(self) -> Dict[str, float]:
        out = self.laps
        self.laps = {}
        return out


class StepSeries:
    """Per-iteration timing/metric rows, saved as an npz series
    (ipropagator.hpp:83-87 writes the analogous HDF5 series). With a
    telemetry registry attached, every row is also emitted as a
    ``phases`` event."""

    def __init__(self, telemetry: Optional[Telemetry] = None):
        self.telemetry = telemetry
        self.rows: List[Dict[str, float]] = []

    def record(self, iteration: int, laps: Dict[str, float], **metrics):
        self.rows.append({"iteration": float(iteration), **laps, **metrics})
        if self.telemetry is not None:
            self.telemetry.phases(iteration, {**laps, **metrics})

    def save(self, path: str) -> bool:
        """Write the series. Returns whether a file was written — with
        zero rows nothing is, and the caller must not report a series
        that doesn't exist (app/main.py --profile)."""
        if not self.rows:
            return False
        keys = sorted({k for row in self.rows for k in row})
        # ragged rows (a metric recorded only on some iterations) are
        # NaN-padded so every column is one dense array
        arrays = {
            k: np.array([row.get(k, np.nan) for row in self.rows])
            for k in keys
        }
        np.savez(path, **arrays)
        return True

    def summary(self) -> Dict[str, float]:
        """Mean seconds per iteration for each recorded phase."""
        if not self.rows:
            return {}
        keys = {k for row in self.rows for k in row} - {"iteration"}
        return {
            k: float(np.nanmean([row.get(k, np.nan) for row in self.rows]))
            for k in sorted(keys)
        }
