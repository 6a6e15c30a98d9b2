"""Seconds per path in ``setup.spectral_norm``: spectral_norm(X)**2, the
Lipschitz constant of the full-bucket fallback, synced."""
from bench.program_spans import seconds

LAYER = "path engine setup (core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return seconds(run, "setup.spectral_norm")
