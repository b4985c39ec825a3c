"""The ``std-cooling`` case of tests/mesh_gravity_list_cases.py (which see),
and a gravity cap overflow under the mesh's lists."""

CASE = "std-cooling"

from mesh_gravity_list_cases import *  # noqa: E402,F401,F403  (the case's tests)
from mesh_gravity_list_cases import (  # noqa: E402
    check_gravity_overflow_under_lists_resizes_and_rebuilds)

test_gravity_overflow_under_lists_resizes_and_rebuilds = (
    check_gravity_overflow_under_lists_resizes_and_rebuilds)
