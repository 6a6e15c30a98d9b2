"""Seconds per path in the ``segment.gather`` spans: the bucketed spec,
the numpy gather of X_sub, its upload and its spectral norm, synced."""
from bench.program_spans import seconds

LAYER = "sweep (core/solver.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "segment.gather")
