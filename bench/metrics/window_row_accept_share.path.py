"""Share of the speculative rows the sweeps solved that certified and
were accepted: 100 * sum ``rows_accepted`` / sum ``rows_solved``."""
from bench.program_spans import ratio

LAYER = "sweep (core/solver.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "%", "higher", "program_counter"
MOVES, TASK = "path_s", "path"


def read(run):
    return ratio(run, "rows_accepted", "rows_solved")
