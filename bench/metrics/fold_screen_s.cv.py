"""Fold-stacked screen seconds per K-fold CV (``CVResult.screen_time``)."""
LAYER = "fold engine (core/cv.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "cv_s", "cv"


def read(run):
    return sum(u.screen for u in run.units) / len(run.units)
