"""Host seconds per path outside the engine's three timers: Problem and
SGLSession construction (X^T y), the X -> host copy before the segment
loop, the host set expansion and the result assembly.  The call's wall
time minus ``setup_time + screen_time + solve_time``."""
LAYER = "session and host glue (core/session.py, core/path_engine.py)"
UNIT, BETTER, SOURCE = "s", "lower", "program_span"
MOVES, TASK = "path_s", "path"


def read(run):
    return sum(u.wall - u.setup - u.screen - u.solve
               for u in run.units) / len(run.units)
