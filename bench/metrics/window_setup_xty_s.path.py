"""Seconds per path in ``setup.xty``: the loss gradient at zero, X^T r0
and lambda_max to a host float."""
from bench.program_spans import seconds

LAYER = "path engine setup (core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "setup.xty")
