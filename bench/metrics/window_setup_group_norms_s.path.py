"""Seconds per path in ``setup.group_norms``: the per-group spectral norms
(power iteration) or Frobenius norms of X, synced."""
from bench.program_spans import seconds

LAYER = "path engine setup (core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "setup.group_norms")
