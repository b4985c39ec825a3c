"""The ``lists`` case of tests/ve_reference_cases.py (which see)."""

CASE = "lists"

from ve_reference_cases import *  # noqa: E402,F401,F403  (the case's tests)
