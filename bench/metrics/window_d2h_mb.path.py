"""Megabytes per path pulled to the host by the engine (``d2h_bytes``):
the screen's keep mask, the dual correlation for the margin fill, the
sweep's certificates, rows and iterations, and X's host copy where jax
did not hold it yet."""
from bench.program_spans import counted

LAYER = "host-device transfers (core/path_engine.py)"
UNIT, BETTER, SOURCE = "MB", "lower", "program_counter"
MOVES, TASK = "path_s", "path"


def read(run):
    value = counted(run, "d2h_bytes")
    return None if value is None else value / 1e6
