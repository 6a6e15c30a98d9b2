"""Sweep seconds per path (``PathResult.solve_time``): the host gather of
X_sub, its spectral norm, and the bucketed FISTA sweeps with in-scan
full-X certification, to the certified rows on the host."""
LAYER = "sweep (core/solver.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return sum(u.solve for u in run.units) / len(run.units)
