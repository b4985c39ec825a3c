"""The ``ve`` case of tests/mesh_list_cases.py (which see)."""

CASE = "ve"

from mesh_list_cases import *  # noqa: E402,F401,F403  (the case's tests)
