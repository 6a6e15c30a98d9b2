"""Roofline share of the in-scan full-X certification GEMV (kernel ``xtv``,
``path_engine._xtv``, called by the SGL and the nonnegative sweep cores).
Bytes from the shapes in each op's HLO text: one pass over X (N, p)
float32 plus v and the output, per call; bandwidth-bound at 0.5
flop/byte."""
from bench import costs

LAYER = "kernel xtv (kernels/xtv.py)"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
MOVES, TASK = "path_s", "path"
EVENTS = ("xtv",)


def read(run):
    if run.trace is None:
        return None
    flops = nbytes = seconds = 0.0
    for op in run.trace.matching(EVENTS):
        designs = [s for dt, s in op.shapes() if dt == "f32" and len(s) >= 2]
        if not designs:
            return None
        N, p = max(designs, key=lambda s: s[-2] * s[-1])[-2:]
        f, b = costs.xtv(N, p)
        flops, nbytes = flops + op.count * f, nbytes + op.count * b
        seconds += op.seconds
    if seconds <= 0:
        return None
    return costs.roofline_share(flops, nbytes, seconds,
                                costs.peaks(run.device_kind))
