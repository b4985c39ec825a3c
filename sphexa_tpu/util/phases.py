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

_PREFIX = "sphexa/"


def phase_scope(phase: str):
    """``jax.named_scope`` context for one taxonomy phase (asserted
    against PHASES so a typo cannot silently open a new bucket)."""
    assert phase in PHASES, f"unknown phase {phase!r} (util/phases.PHASES)"
    return jax.named_scope(_PREFIX + phase)


def named_phase(phase: str):
    """Decorator form: every op the wrapped function traces carries the
    phase. Zero runtime cost outside tracing — the context manager only
    runs while jax is building the jaxpr."""
    assert phase in PHASES, f"unknown phase {phase!r} (util/phases.PHASES)"
    name = _PREFIX + phase

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapper

    return deco
