"""The in-graph phase taxonomy: ``jax.named_scope("sphexa/<phase>")``.

Everything a profiler capture should be able to attribute gets its ops
stamped with one of THESE names — the step builders, the gravity solve,
the neighbor machinery and the halo exchange wrap their stages in
``phase_scope``/``@named_phase`` so XLA op *metadata* carries the phase
end-to-end: through fusion, through ``shard_map``, onto the device
timeline. ``sphexa-telemetry trace <dir>`` (telemetry/traceview.py)
aggregates a ``--trace-dir`` capture back into a per-phase device-time
table keyed on exactly this list; the HLO pin test
(tests/test_phase_attr.py) fails any refactor that silently strips a
scope.

The taxonomy mirrors the reference lineage's per-phase breakdowns (the
SPH-EXA ``Timer`` phases; Bédorf et al. 2014's tree-code phase tables,
SURVEY §6) transposed to the fused one-program step: phases are trace
METADATA here, not host-timed barriers — zero runtime cost, visible
only in a profiler capture.

A STAGE is a second name inside a phase: ``sphexa/<phase>~<stage>``
(``stage_scope`` / ``@named_stage``, the ``STAGES`` table). ``~`` lies
outside the character class every reader's phase pattern takes
(``sphexa/([A-Za-z0-9_.:+-]+)``, first match of the path), so an op reads
under the phase it read under before the stage existed, wherever the
stage scope is opened; a reader that wants the stage takes the LAST
``sphexa/`` token of the same path (benchmarks/stage_times.py,
benchmarks/STAGES.md). Not ``@``: this jax's lowering cuts an
``op_name`` there. A stage scope is opened only inside a phase scope
(its own phase's or the caller's): the first phase of no op changes.

``named_scope`` is pure tracing machinery (it pushes a name onto jax's
name stack; no primitive, no callback, no host boundary), so the
jaxaudit JXA104 host-boundary rule has nothing to flag — pinned by the
audit gate staying at zero findings with every scope below traced.
"""

import functools

import jax

#: every phase name in the taxonomy (docs/OBSERVABILITY.md schema-v4
#: table). Tests and the traceview renderer key on these.
PHASES = (
    "sort",             # SFC keygen + argsort + field permute, box regrow
    "neighbors",        # cell-table build / group windows / pair lists
    "halo-exchange",    # sparse/windowed halo negotiation + serves
    "density",          # std density pair op
    "xmass",            # VE generalized volume elements
    "gradh",            # VE kx / gradh pair op
    "eos",              # equation of state
    "iad",              # IAD tensor pass (std; VE on the xla backend)
    "divv-curlv",       # VE divergence / curl (+gradv); on the pair engine
                        # the IAD moments ride this pass (no iad scope)
    "av-switches",      # VE artificial-viscosity switches
    "momentum-energy",  # momentum + energy pair op
    "gravity-upsweep",  # multipole upsweep (psum-reduced when sharded)
    "gravity-mac",      # MAC classification + interaction-list compaction
    "gravity-m2p",      # far-field multipole-to-particle evaluation
    "gravity-p2p",      # near-field particle-to-particle evaluation
    "gravity-exchange", # sharded solve: upsweep psums, near-field leaf serve
    "cooling",          # radiative-cooling timestep + source integration
    "turbulence",       # OU stirring accelerations
    "timestep",         # dt candidate min-reduction + limiter attribution
    "dt-bins",          # block-timestep bin assignment, active compaction
    "integrate",        # drift/kick, PBC wrap, smoothing-length nudge
    "ledger",           # in-graph conservation/numerics science ledger
    "snapshot",         # in-graph downsampled field-grid deposit
    "shard-metrics",    # per-shard telemetry pack + gather
    "output-fields",    # a dump's recompute: keygen, sort/unsort permutes
)

#: the stages of a phase, ``{phase: (stage, ...)}``: what the records put
#: at 10 ms a step or more in some cell, and every collective
STAGES = {
    "sort": (
        "keys",     # SFC keys of every particle
        "order",    # the argsort and the sorted keys
        "permute",  # the state's fields stacked and gathered by row
        "aux",      # the same gather of the aux pytree (chemistry), alone
    ),
    "neighbors": (
        "windows",      # group bboxes and window cells (their curve keys
                        # on the deep-grid searchsorted fallback only)
        "cell-ranges",  # cell table into grid order, each window read as
                        # rows of it; cull, compaction sorts and run merge
    ),
    "halo-exchange": (
        "table",     # global cell table: slab histogram + cumsum
        "cover",     # coverage bitmap of the runs' cells (+-1 scatters)
        "localize",  # slab-boundary split, runs -> j-buffer rows, bounds
        "pack",      # packed layout of a serve, row indices, row gather
        "wire",      # every collective of the exchange, and nothing else
        "jbuf",      # own + annex concatenates
    ),
    "gravity-mac": (
        "geometry",  # per-solve node MAC geometry and packed node rows
        "let",       # the slab's essential (LET) node list
        "prepass",   # superblock candidate cut: class + compaction
        "classify",  # a super's candidates: row gather + per-block class
        "compact",   # the two interaction lists of every block
    ),
    "gravity-p2p": (
        "leaf-ranges",  # row range of every near-field leaf of a list
        "merge-runs",   # the near field's run merge (its two sorts)
        "kernel",       # the streamed pair kernel and its blocked inputs
    ),
    "gravity-exchange": (
        "psum",  # the sharded upsweep's all-reduces
        "jbuf",  # own + annex concatenates of the near field
    ),
    "cooling": (
        "limiter",  # cool_timestep: the rates once more, a min over N
        "network",  # cool_step: the subcycled species + energy update
    ),
}
assert set(STAGES) <= set(PHASES)
assert all(len(set(v)) == len(v) for v in STAGES.values())

_PREFIX = "sphexa/"
#: between phase and stage; outside the readers' phase pattern
STAGE_SEP = "~"


def phase_scope(phase: str):
    """``jax.named_scope`` context for one taxonomy phase (asserted
    against PHASES so a typo cannot silently open a new bucket)."""
    assert phase in PHASES, f"unknown phase {phase!r} (util/phases.PHASES)"
    return jax.named_scope(_PREFIX + phase)


def _scoped(name: str):
    """Decorator: every op the wrapped function traces carries ``name``.
    Zero runtime cost outside tracing: the context manager only runs
    while jax is building the jaxpr."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def named_phase(phase: str):
    """Decorator form of ``phase_scope``."""
    assert phase in PHASES, f"unknown phase {phase!r} (util/phases.PHASES)"
    return _scoped(_PREFIX + phase)


def _stage_name(phase: str, stage: str) -> str:
    assert stage in STAGES.get(phase, ()), (
        f"unknown stage {stage!r} of phase {phase!r} (util/phases.STAGES)")
    return _PREFIX + phase + STAGE_SEP + stage


def stage_scope(phase: str, stage: str):
    """``jax.named_scope`` context for one stage of a phase. Open it only
    where a phase scope already is (module docstring)."""
    return jax.named_scope(_stage_name(phase, stage))


def named_stage(phase: str, stage: str):
    """Decorator form of ``stage_scope``."""
    return _scoped(_stage_name(phase, stage))
