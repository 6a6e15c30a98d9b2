"""Seconds per path of backend compiles (persistent-cache reads included)
that JAX reported inside the ``segment.gather`` and ``segment.sweep``
spans."""
from bench.program_spans import compile_seconds

LAYER = "compilation (XLA, launch/compile_cache.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return compile_seconds(run, "segment.gather", "segment.sweep")
