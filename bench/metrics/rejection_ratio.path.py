"""Share of the p features kept out of the solver, averaged over every
row of every path: 100 * mean(1 - kept / p)."""
LAYER = "screening (core/screening.py, core/dpc.py)"
UNIT, BETTER, SOURCE = "%", "higher", "program_counter"
MOVES, TASK = "path_s", "path"


def read(run):
    rows = [1.0 - k / run.n_features for u in run.units for k in u.kept]
    return 100.0 * sum(rows) / len(rows)
