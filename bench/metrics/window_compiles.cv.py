"""Backend compiles (persistent-cache reads included) inside the measured
window, from JAX's monitoring events.  Set-up solves its own warm-up
responses; a shape that only a fresh window response meets is compiled,
or read from the cache, inside the window and counts here."""
LAYER = "compilation (XLA, launch/compile_cache.py)"
UNIT, BETTER, SOURCE = "compiles", "lower", "program_counter"
MOVES, TASK = "cv_s", "cv"


def read(run):
    return run.window_compiles
