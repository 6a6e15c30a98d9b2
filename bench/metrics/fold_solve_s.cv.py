"""Vmapped fold-sweep seconds per K-fold CV (``CVResult.solve_time``):
elastic cohorts, each sweep running until its slowest fold certifies."""
LAYER = "fold engine (core/cv.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "cv_s", "cv"


def read(run):
    return sum(u.solve for u in run.units) / len(run.units)
