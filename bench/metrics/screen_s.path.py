"""Grid-screen seconds per path (``PathResult.screen_time``): the (L, N) x
(N, p) screening GEMM and the TLFre / DPC rule, to the keep mask on the
host."""
LAYER = "screening (core/screening.py, core/dpc.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return sum(u.screen for u in run.units) / len(run.units)
