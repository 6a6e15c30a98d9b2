"""Seconds per path in the ``segment.expand`` spans: the host set
expansion, the fully-screened prefix skip and the margin fill, with the
pull of the latest dual correlation."""
from bench.program_spans import seconds

LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "segment.expand")
