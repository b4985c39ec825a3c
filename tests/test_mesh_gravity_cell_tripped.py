"""The ``tripped`` case of tests/mesh_gravity_case.py (which see)."""

CASE = "tripped"

from mesh_gravity_case import *  # noqa: E402,F401,F403  (the case's tests)
