"""The ``sedov`` case of tests/pair_list_tile_cases.py (which see)."""

CASE = "sedov"

from pair_list_tile_cases import *  # noqa: E402,F401,F403  (the case's tests)
