"""FISTA iterations per path, summed over its rows (``PathResult.iters``)."""
LAYER = "sweep (core/solver.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "iters", "lower", "program_counter"
MOVES, TASK = "path_s", "path"


def read(run):
    return sum(u.iters for u in run.units) / len(run.units)
