"""Seconds per path in ``session.init``: the session's own X^T y, synced."""
from bench.program_spans import seconds

LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "session.init")
