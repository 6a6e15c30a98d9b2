"""Seconds per path spent in backend compiles and persistent-cache reads
inside the measured window (JAX's ``backend_compile_duration`` events),
so that the share of ``path_s`` that compiling takes can be read."""
LAYER = "compilation (XLA, launch/compile_cache.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return run.window_compile_s / len(run.units)
