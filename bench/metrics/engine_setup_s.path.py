"""Engine set-up seconds per path: X^T y, lambda_max, column norms, group
spectral norms by power iteration and spectral_norm(X), all X-only but
recomputed on every ``path`` call (``PathResult.setup_time``)."""
LAYER = "path engine setup (core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return sum(u.setup for u in run.units) / len(run.units)
