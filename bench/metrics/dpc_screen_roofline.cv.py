"""Roofline share of the fused DPC screening rule (kernel
``dpc_screen_folds``): bytes from its (K, L, p) stack, read from the
shapes in each op's HLO text, per call."""
from bench import costs

LAYER = "kernel dpc_screen_folds (kernels/screen_norms.py)"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
MOVES, TASK, PENALTY = "cv_s", "cv", "nn_lasso"
EVENTS = ("dpc_screen_folds",)


def read(run):
    if run.trace is None:
        return None
    flops = nbytes = seconds = 0.0
    for op in run.trace.matching(EVENTS):
        stacks = [s for dt, s in op.shapes() if dt == "f32" and len(s) == 3]
        if not stacks:
            return None
        f, b = costs.dpc_screen(*max(stacks, key=lambda s: s[0] * s[1]
                                     * s[2]))
        flops, nbytes = flops + op.count * f, nbytes + op.count * b
        seconds += op.seconds
    if seconds <= 0:
        return None
    return costs.roofline_share(flops, nbytes, seconds,
                                costs.peaks(run.device_kind))
