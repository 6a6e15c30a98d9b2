"""Megabytes per path uploaded by the engine (``h2d_bytes``): X_sub, the
sweep's grid, mask and warm start, the screen's grid and the assembled
warm start."""
from bench.program_spans import counted

LAYER = "host-device transfers (core/path_engine.py)"
UNIT, BETTER, SOURCE = "MB", "lower", "program_counter"
MOVES, TASK = "path_s", "path"


def read(run):
    value = counted(run, "h2d_bytes")
    return None if value is None else value / 1e6
