"""The ``ve`` case of tests/mesh_gravity_list_cases.py (which see), and the
mesh's solve on a shuffled state."""

CASE = "ve"

from mesh_gravity_list_cases import *  # noqa: E402,F401,F403  (the case's tests)
from mesh_gravity_list_cases import (  # noqa: E402
    check_add_gravity_sorts_its_own_copy_on_the_mesh)

test_add_gravity_sorts_its_own_copy_on_the_mesh = (
    check_add_gravity_sorts_its_own_copy_on_the_mesh)
