"""Seconds per path in the ``segment.sweep`` spans: the sweep's inputs,
its launch, and its certificates, rows and iterations on the host."""
from bench.program_spans import seconds

LAYER = "sweep (core/solver.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "segment.sweep")
