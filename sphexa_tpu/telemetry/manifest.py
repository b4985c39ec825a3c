"""Run manifest: the who/what/where stamp that makes runs comparable.

Every telemetry-enabled run writes ``manifest.json`` next to
``events.jsonl``.
``sphexa-telemetry diff`` refuses nothing but warns on mismatched
environments — a regression across different jax versions or mesh shapes
is a different conversation than one on identical setups.
"""

import datetime
import json
import os
import subprocess
import sys
from typing import Dict, Optional

#: manifest schema version (independent of the event schema)
MANIFEST_SCHEMA = 1


def git_rev() -> str:
    """Short git revision of the source tree, or 'unknown' outside a
    checkout (installed wheels, stripped containers)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "-C", root, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def build_manifest(config: Optional[Dict] = None,
                   particles: Optional[int] = None,
                   mesh_shape=None,
                   extra: Optional[Dict] = None) -> Dict:
    """Assemble the manifest dict (jax/backend versions resolved here, so
    callers that already initialized a backend pay nothing extra)."""
    try:
        import jax

        jax_version = jax.__version__
        backend = jax.default_backend()
        device_count = jax.device_count()
    except Exception:  # manifest must never sink the run it describes
        jax_version, backend, device_count = "unknown", "unknown", 0
    from sphexa_tpu.telemetry.registry import SCHEMA_VERSION

    return {
        "schema": MANIFEST_SCHEMA,
        # the event-stream schema this run's writer speaks (events.jsonl
        # carries it per event too; stamped here so readers can tell a
        # pre-v3 run without scanning the stream)
        "events_schema": SCHEMA_VERSION,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "git_rev": git_rev(),
        "jax_version": jax_version,
        "backend": backend,
        "device_count": device_count,
        "mesh_shape": list(mesh_shape) if mesh_shape is not None else None,
        "python": sys.version.split()[0],
        "platform": sys.platform,
        "particles": int(particles) if particles is not None else None,
        "config": config or {},
        **(extra or {}),
    }


def write_manifest(run_dir: str, **kwargs) -> Dict:
    """Build + persist ``<run_dir>/manifest.json``; returns the dict."""
    manifest = build_manifest(**kwargs)
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2, default=str)
        f.write("\n")
    return manifest


def read_manifest(run_dir: str) -> Optional[Dict]:
    path = os.path.join(run_dir, "manifest.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)
