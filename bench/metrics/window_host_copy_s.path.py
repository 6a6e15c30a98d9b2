"""Seconds per path in ``host_copy``: X and the group arrays to numpy
before the segment loop (free once jax holds X's host copy)."""
from bench.program_spans import seconds

LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "host_copy")
