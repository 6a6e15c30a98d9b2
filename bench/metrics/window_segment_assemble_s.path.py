"""Seconds per path in the ``segment.assemble`` spans: the accepted rows
into the path's betas and the warm start back to the device."""
from bench.program_spans import seconds

LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "segment.assemble")
