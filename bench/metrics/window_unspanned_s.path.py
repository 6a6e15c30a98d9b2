"""Seconds per path that no program span covers: the call's wall time
less its leaf spans (Problem construction, small dispatches between
spans, the bench's own harvest)."""
from bench.program_spans import unspanned

LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return unspanned(run)
