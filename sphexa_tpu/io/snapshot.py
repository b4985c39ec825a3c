"""Snapshot writer/reader: HDF5 (H5Part-style Step#n groups) + npz fallback.

Layout mirrors the reference (main/src/io/ifile_io_hdf5.cpp:49-314):

    dump.h5
    └── Step#0
        ├── attrs: iteration, time, minDt, minDt_m1, gravConstant, gamma,
        │          ng0, ngmax, Kcour, mui, box_lo, box_hi, box_boundaries, ...
        ├── x, y, z, x_m1, ..., alpha   (one dataset per conserved field)
        └── rho, p, ...                 (optional derived output fields)

Restart = read the conserved fields + attributes back into a ParticleState
and SimConstants (the FileInit path, main/src/init/file_init.hpp).
"""

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from sphexa_tpu.dtypes import COORD_DTYPE, HYDRO_DTYPE
from sphexa_tpu.sfc.box import BoundaryType, Box
from sphexa_tpu.sph.particles import ParticleState, SimConstants
from sphexa_tpu.telemetry.registry import span

try:
    import h5py

    _HAVE_H5PY = True
except ImportError:  # pragma: no cover - h5py is present in the image
    _HAVE_H5PY = False

# conserved per-particle fields: the restartable set (ipropagator
# conservedFields + particles_data.hpp checkpoint list)
CONSERVED_FIELDS = (
    "x", "y", "z", "x_m1", "y_m1", "z_m1", "vx", "vy", "vz",
    "h", "m", "temp", "du", "du_m1", "alpha",
)

# SimConstants fields serialized as attributes, reference attribute names
# (particles_data.hpp:170-191)
_CONST_ATTRS = {
    "ng0": "ng0", "ngmax": "ngmax", "k_cour": "Kcour", "k_rho": "Krho",
    "gamma": "gamma", "mui": "muiConst", "alphamin": "alphamin",
    "alphamax": "alphamax", "decay_constant": "decay_constant",
    "at_min": "Atmin", "at_max": "Atmax", "g": "gravConstant",
    "eps": "eps", "eta_acc": "etaAcc", "max_dt_increase": "maxDtIncrease",
    "sinc_index": "sincIndex", "kernel_choice": "kernelChoice",
    # pair-cutoff convention: restarts must reproduce the writing run's
    # force convention (min-h symmetric vs reference one-sided) — a
    # continuation that silently flips it changes energies mid-run
    "sym_pairs": "symPairs",
}


def _is_h5(path: str) -> bool:
    return os.path.splitext(path)[1].lower() in (".h5", ".hdf5", ".h5part")


def _step_attrs(state: ParticleState, box: Box, const: SimConstants,
                iteration: int,
                num_particles_global: Optional[int] = None
                ) -> Dict[str, np.ndarray]:
    attrs = {
        "iteration": np.int64(iteration),
        # the H5Part convention (ifile_io_hdf5.cpp) records the GLOBAL
        # count on every rank's output; sharded part files override this
        # so external tools probing any single part see the true total
        "numParticlesGlobal": np.int64(
            state.n if num_particles_global is None else num_particles_global),
        "time": np.float64(state.ttot),
        "minDt": np.float64(state.min_dt),
        "minDt_m1": np.float64(state.min_dt_m1),
        "box_lo": np.asarray(box.lo, np.float64),
        "box_hi": np.asarray(box.hi, np.float64),
        "box_boundaries": np.asarray([int(b) for b in box.boundaries], np.int64),
    }
    for field, name in _CONST_ATTRS.items():
        v = getattr(const, field)
        attrs[name] = (
            np.bytes_(v.encode()) if isinstance(v, str) else np.float64(v)
        )
    return attrs


def write_snapshot(
    path: str,
    state: ParticleState,
    box: Box,
    const: SimConstants,
    iteration: int = 0,
    extra_fields: Optional[Dict[str, np.ndarray]] = None,
    case: str = "",
    case_settings: Optional[Dict] = None,
    num_particles_global: Optional[int] = None,
) -> int:
    """Append one restartable snapshot; returns the step index written.

    ``extra_fields`` adds derived output datasets (rho, p, ...) alongside
    the conserved set — the analog of the -f/--wextra field selection.
    ``case`` records the originating test-case name so a restarted run can
    re-select the matching observable (the reference records its init
    settings as file attributes for the same reason, settings.hpp:45-57).
    ``num_particles_global`` overrides the numParticlesGlobal attribute
    (sharded part files record the global count, not their row count).
    """
    with span("sphexa:dump-fetch") as sp:
        sources = {f: getattr(state, f) for f in CONSERVED_FIELDS}
        sources.update(extra_fields or {})
        fields = {k: np.asarray(v) for k, v in sources.items()}
        _fetched(sp, sources, fields)
    attrs = _step_attrs(state, box, const, iteration, num_particles_global)
    if case:
        attrs["initCase"] = np.bytes_(case)
    if case_settings:
        # the applied case-settings overrides ride along so a restart can
        # rebuild threshold-bearing observables identically (the reference
        # writes its init settings as file attributes, settings.hpp:45-57)
        import json

        attrs["caseSettings"] = np.bytes_(json.dumps(case_settings))

    nbytes = sum(v.nbytes for v in fields.values())
    if _is_h5(path):
        if not _HAVE_H5PY:
            raise RuntimeError("h5py unavailable; use a .npz path instead")
        with span("sphexa:dump-h5", bytes=nbytes, format="h5"), \
                h5py.File(path, "a") as f:
            step = len([k for k in f.keys() if k.startswith("Step#")])
            g = f.create_group(f"Step#{step}")
            for k, v in attrs.items():
                g.attrs[k] = v
            for k, v in fields.items():
                g.create_dataset(k, data=v)
            return step

    arrays = {f"field_{k}": v for k, v in fields.items()}
    arrays.update({f"attr_{k}": v for k, v in attrs.items()})
    with span("sphexa:dump-h5", bytes=nbytes, format="npz"):
        np.savez_compressed(path, **arrays)
    return 0


def _fetched(sp, sources, fields) -> None:
    """Payload of a ``sphexa:dump-fetch`` span: the arrays that were not
    on the host already, and their bytes."""
    copied = [k for k, v in sources.items() if not isinstance(v, np.ndarray)]
    sp["fields"] = len(copied)
    sp["bytes"] = sum(fields[k].nbytes for k in copied)


def _part_path(path: str, k: int, P: int) -> str:
    base, ext = os.path.splitext(path)
    return f"{base}.part{k:03d}of{P:03d}{ext}"


def _find_parts(path: str) -> List[str]:
    """Existing part files of a sharded snapshot base path (sorted)."""
    import glob as _glob

    base, ext = os.path.splitext(path)
    return sorted(_glob.glob(f"{base}.part*of*{ext}"))


def write_snapshot_sharded(
    path: str,
    state: ParticleState,
    box: Box,
    const: SimConstants,
    iteration: int = 0,
    extra_fields: Optional[Dict[str, np.ndarray]] = None,
    case: str = "",
    case_settings: Optional[Dict] = None,
) -> int:
    """Parallel snapshot: one part file per device shard, NO global
    gather — the role of the reference's collective MPI-IO writer
    (main/src/io/ifile_io_hdf5.cpp:49-314), transposed to the
    file-per-shard pattern: every host writes only the slab rows its
    devices own (on a multi-host mesh each process sees only its own
    ``addressable_shards``), so dump bandwidth scales with hosts and the
    64M-particle funnel through one writer disappears.

    Part files are ordinary snapshots (same Step# layout) of their slab
    rows; ``read_snapshot`` on the BASE path reassembles them. Returns
    the step index written (parts stay step-aligned because every dump
    writes all parts)."""
    xarr = state.x
    shards = getattr(xarr, "addressable_shards", None)
    if not shards or len(getattr(xarr.sharding, "device_set", [])) <= 1:
        # single-device state: plain snapshot (no parts)
        return write_snapshot(path, state, box, const, iteration,
                              extra_fields, case, case_settings)
    P = len(xarr.sharding.device_set)
    n = xarr.shape[0]
    if n % P != 0:
        raise ValueError(
            f"sharded snapshot requires n divisible by the device count "
            f"(n={n}, P={P}); the CLI trims ICs to a multiple of P")
    rows = n // P
    # ONE host fetch per extra field (inside the shard loop each
    # np.asarray would re-gather the full array P times)
    with span("sphexa:dump-fetch") as sp:
        extras_np = {k2: np.asarray(v)
                     for k2, v in (extra_fields or {}).items()}
        _fetched(sp, extra_fields or {}, extras_np)
    step = 0
    for sh in shards:
        sl = sh.index[0] if sh.index else slice(0, n)
        start = sl.start or 0
        k = start // rows

        class _Part:
            pass

        part = _Part()
        with span("sphexa:dump-fetch",
                  fields=len(CONSERVED_FIELDS)) as sp:
            for f in CONSERVED_FIELDS:
                a = getattr(state, f)
                starts = [s.index[0].start or 0 for s in a.addressable_shards]
                if start not in starts:
                    raise ValueError(
                        f"field {f}: no shard starting at row {start} "
                        f"(shard starts {sorted(starts)}) — uneven or "
                        "mismatched sharding across fields")
                ash = a.addressable_shards[starts.index(start)]
                if ash.data.shape[0] != rows:
                    raise ValueError(
                        f"field {f}: shard at row {start} has "
                        f"{ash.data.shape[0]} rows, expected {rows} — "
                        "sharded snapshots require equal-size shards")
                setattr(part, f, np.asarray(ash.data))
            sp["bytes"] = sum(getattr(part, f).nbytes
                              for f in CONSERVED_FIELDS)
        part.n = rows
        part.ttot = state.ttot
        part.min_dt = state.min_dt
        part.min_dt_m1 = state.min_dt_m1
        ex = None
        if extra_fields:
            # per-particle extras are sliced to the part's rows;
            # global tables (turbulence phases, chemistry scalars) go to
            # part 0 ONLY (the reader takes part-0-only fields verbatim)
            ex = {}
            for k2, va in extras_np.items():
                if va.ndim >= 1 and va.shape[0] == n:
                    ex[k2] = va[start:start + rows]
                elif k == 0:
                    ex[k2] = va
        step = write_snapshot(
            _part_path(path, k, P), part, box, const, iteration, ex,
            case, case_settings, num_particles_global=n,
        )
    return step


def list_steps(path: str) -> List[int]:
    """Step indices present in a snapshot file.

    On a sharded base path this is the INTERSECTION across part files, so
    a torn dump's extra part-0 step (which ``_read_raw`` would refuse to
    assemble) is never reported as readable."""
    if not os.path.exists(path):
        parts = _find_parts(path)
        if parts:
            common: Optional[set] = None
            for p in parts:
                s = set(list_steps(p))
                common = s if common is None else (common & s)
            return sorted(common or ())
    if _is_h5(path):
        with h5py.File(path, "r") as f:
            return sorted(
                int(k.split("#")[1]) for k in f.keys() if k.startswith("Step#")
            )
    return [0]


def _resolve_step(steps: List[int], step: int, path: str) -> int:
    """Validate a step selector against the file's Step#n indices;
    negative counts from the end."""
    if not steps:
        raise ValueError(f"{path} contains no Step#n groups")
    if step < 0:
        if -step > len(steps):
            raise ValueError(f"step {step} out of range for {path}; have {steps}")
        return steps[step]
    if step not in steps:
        raise ValueError(f"step {step} not in {path}; have {steps}")
    return step


def _h5_steps(f) -> List[int]:
    return sorted(int(k.split("#")[1]) for k in f.keys() if k.startswith("Step#"))


def _read_raw(path: str, step: int):
    if not os.path.exists(path):
        parts = _find_parts(path)
        if parts:
            # sharded snapshot: concatenate the slab-row parts in part
            # order (file names carry the order); attrs from part 0.
            # Guards: the part set must be complete (file names encode
            # P), and every part must resolve to the SAME dump — a torn
            # write (crash mid-dump) leaves later parts one step behind
            import re

            mP = re.search(r"part\d+of(\d+)", parts[0])
            P_declared = int(mP.group(1)) if mP else len(parts)
            if len(parts) != P_declared:
                raise ValueError(
                    f"{path}: sharded snapshot has {len(parts)} part files "
                    f"but names declare {P_declared} shards (incomplete "
                    "dump or mixed part sets from different runs)")
            # resolve the selector against the steps COMPLETE across all
            # parts (a torn dump leaves part 0 a step ahead; -1 must mean
            # the newest ASSEMBLABLE step, matching list_steps)
            step = _resolve_step(list_steps(path), step, path)
            fields_all, attrs = None, None
            for p in parts:
                f, a = _read_raw_one(p, step)
                if fields_all is None:
                    fields_all, attrs = {k: [v] for k, v in f.items()}, a
                else:
                    if (int(a["iteration"]) != int(attrs["iteration"])
                            or float(a["time"]) != float(attrs["time"])):
                        raise ValueError(
                            f"{p}: part resolves to iteration "
                            f"{int(a['iteration'])} != part 0's "
                            f"{int(attrs['iteration'])} — torn sharded "
                            "dump (crash mid-write?); pass an explicit "
                            "step index for the last complete dump")
                    for k, v in f.items():
                        fields_all.setdefault(k, []).append(v)
            # fields present only in part 0 are global tables — verbatim;
            # per-particle fields (present in every part) concatenate
            out = {k: (np.concatenate(v) if len(v) == len(parts) else v[0])
                   for k, v in fields_all.items()}
            return out, attrs
    return _read_raw_one(path, step)


def _read_raw_one(path: str, step: int):
    if _is_h5(path):
        with h5py.File(path, "r") as f:
            idx = _resolve_step(_h5_steps(f), step, path)
            g = f[f"Step#{idx}"]
            fields = {k: np.asarray(g[k]) for k in g.keys()}
            attrs = {k: np.asarray(v) for k, v in g.attrs.items()}
            return fields, attrs
    _resolve_step([0], step, path)  # npz files hold exactly one snapshot
    data = np.load(path)
    fields = {k[6:]: data[k] for k in data.files if k.startswith("field_")}
    attrs = {k[5:]: data[k] for k in data.files if k.startswith("attr_")}
    return fields, attrs


def read_step_attrs(path: str, step: int = -1) -> Dict[str, np.ndarray]:
    """Step attributes only (iteration, time, constants) — cheap restart
    metadata probe without loading the particle datasets."""
    if not os.path.exists(path):
        parts = _find_parts(path)
        if parts:
            # resolve the selector against the steps COMPLETE across all
            # parts (matching what _read_raw will accept), then probe
            # part 0's attrs for that step
            idx = _resolve_step(list_steps(path), step, path)
            step, path = idx, parts[0]
    if _is_h5(path):
        with h5py.File(path, "r") as f:
            idx = _resolve_step(_h5_steps(f), step, path)
            return {k: np.asarray(v) for k, v in f[f"Step#{idx}"].attrs.items()}
    _, attrs = _read_raw(path, step)
    return attrs


def read_snapshot(
    path: str, step: int = -1
) -> Tuple[ParticleState, Box, SimConstants, Dict[str, np.ndarray]]:
    """Restore (state, box, const, extra_fields) from a snapshot.

    ``step``: index into the file's Step#n groups; negative counts from the
    end (the reference's ``--init dump.h5:-1`` semantics, file_init.hpp).
    """
    state, box, const, extra, _ = read_snapshot_full(path, step)
    return state, box, const, extra


def read_snapshot_full(
    path: str, step: int = -1
) -> Tuple[ParticleState, Box, SimConstants, Dict[str, np.ndarray],
           Dict[str, np.ndarray]]:
    """read_snapshot + the raw step attributes (iteration, initCase, ...) —
    single-read restore for callers that need the restart metadata too."""
    fields, attrs = _read_raw(path, step)

    missing = [f for f in CONSERVED_FIELDS if f not in fields]
    if missing:
        raise ValueError(f"{path} is not restartable: missing fields {missing}")

    const_kw = {}
    for field, name in _CONST_ATTRS.items():
        if name in attrs:
            if field == "kernel_choice":
                v = attrs[name]
                v = v.item() if hasattr(v, "item") else v
                const_kw[field] = v.decode() if isinstance(v, bytes) else str(v)
            elif field == "sym_pairs":
                const_kw[field] = bool(int(float(attrs[name])))
            else:
                cast = int if field in ("ng0", "ngmax") else float
                const_kw[field] = cast(attrs[name])
    const = SimConstants(**const_kw).normalized()

    box = Box(
        lo=jnp.asarray(attrs["box_lo"], COORD_DTYPE),
        hi=jnp.asarray(attrs["box_hi"], COORD_DTYPE),
        boundaries=tuple(BoundaryType(int(b)) for b in attrs["box_boundaries"]),
    )

    f32 = lambda k: jnp.asarray(fields[k], HYDRO_DTYPE)
    state = ParticleState(
        **{f: f32(f) for f in CONSERVED_FIELDS},
        # the energy-update compensation carry is not serialized (it is
        # < 1 ulp of temp); restarting resets it
        temp_lo=jnp.zeros_like(jnp.asarray(fields["temp"], HYDRO_DTYPE)),
        ttot=HYDRO_DTYPE(attrs["time"]),
        min_dt=HYDRO_DTYPE(attrs["minDt"]),
        min_dt_m1=HYDRO_DTYPE(attrs["minDt_m1"]),
    )
    extra = {k: v for k, v in fields.items() if k not in CONSERVED_FIELDS}
    return state, box, const, extra, attrs


def write_ascii(
    path: str, columns: Dict[str, np.ndarray], delimiter: str = " "
) -> None:
    """Plain-text column dump (the --ascii output path,
    main/src/io/ifile_io_ascii.cpp): one header line, one row per particle."""
    names = list(columns)
    data = np.column_stack([np.asarray(columns[k]) for k in names])
    np.savetxt(path, data, delimiter=delimiter, header=delimiter.join(names))
