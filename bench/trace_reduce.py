"""Reduce a profiler trace (``.xplane.pb``) of a slice of the window to
the numbers the per-layer readers need.

* The traced slice: the host span ``bench.window`` the runner records.
* Device busy time: the union of the op intervals on each TPU plane's
  ``XLA Ops`` line, clipped to the slice and averaged over the chips.
* Per device op, keyed by its program (the enclosing ``XLA Modules``
  event, hash dropped) and HLO op name: count and self time (an op's
  duration less that of the ops nested in it, as a loop holds its body),
  and the HLO text of its first event, whose shapes a kernel reader uses.
* Idle gaps: the stretches of the slice in which no op ran, each labelled
  by the innermost host span covering its midpoint: the runner's
  ``bench.*`` spans and what the runtime records on the same thread.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
TOP = 10
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Op:
    module: str
    name: str                   # HLO op name, e.g. "%xtv.1"
    count: int
    seconds: float              # self time inside the slice
    text: str = ""              # HLO text of the first event

    @property
    def base(self) -> str:
        """The op name without '%' and numeric suffix: 'xtv'."""
        return _SUFFIX.sub("", self.name.lstrip("%"))

    def shapes(self) -> list:
        """(dtype, dims) of every array the op's HLO text names."""
        return [(dt, tuple(int(d) for d in dims.split(",") if d))
                for dt, dims in _SHAPE.findall(self.text)]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: list                   # [Op], by self time, longest first
    gaps: list                  # [(label, seconds)], longest first

    def breakdown(self) -> dict:
        return {"device_ops": [[f"{o.module}:{o.name}", o.seconds]
                               for o in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}

    def matching(self, bases) -> list:
        """Ops whose base name is one of ``bases``."""
        return [op for op in self.ops if op.base in bases]


def _merge(intervals, lo, hi):
    """Merged [s, e) intervals clipped to [lo, hi)."""
    merged = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_events(device, host, window) -> Summary:
    """The reduction on plain events, read from a trace or made up.

    ``device``: per chip, (module, name, start_ns, end_ns, text) in start
    order; nested ops lie inside their parent.  ``host``: (name, start_ns,
    end_ns) spans that label gaps.  ``window``: (start_ns, end_ns)."""
    lo, hi = window
    ops: dict = {}
    busy, merged0 = 0.0, []
    for chip, events in enumerate(device):
        merged = _merge([(s, e) for _, _, s, e, _ in events], lo, hi)
        busy += sum(e - s for s, e in merged)
        if chip == 0:
            merged0 = merged
        stack, texts = [], {}
        for module, name, s, e, text in events:
            if text is not None:
                texts.setdefault((module, name), text)
            while stack and stack[-1][0] <= s:
                stack.pop()
            inside = max(0, min(e, hi) - max(s, lo))
            if stack:
                stack[-1][1].seconds -= inside * 1e-9
            op = ops.get((module, name))
            if op is None:
                op = ops[(module, name)] = Op(
                    module, name, 0, 0.0, texts.get((module, name), ""))
            if inside > 0:
                op.count += 1
                op.seconds += inside * 1e-9
            stack.append((e, op))
    gaps = []
    edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            mid = 0.5 * (s + e)
            inner = [(he - hs, n) for n, hs, he in host
                     if hs <= mid < he and n != WINDOW_SPAN]
            gaps.append((min(inner)[1] if inner else "(no host span)",
                         (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy * 1e-9 / max(len(device), 1),
                   ops=sorted((o for o in ops.values() if o.count),
                              key=lambda o: -o.seconds),
                   gaps=gaps)


def summarize(trace_dir) -> Summary:
    """Read the one ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(files)}")
    pd = ProfileData.from_file(files[0])
    device, host, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OP_LINE in lines:
                device.append(_device_events(lines[OP_LINE],
                                             lines.get(MODULE_LINE)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(n.startswith("bench.") for n, _, _ in spans):
                    host.extend(spans)
                for n, s, e in spans:
                    if n == WINDOW_SPAN:
                        window = (s, e)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return reduce_events(device, host, window)


def _device_events(op_line, module_line):
    """(module, op name, start, end, HLO text) of each op on the line; the
    module is the ``XLA Modules`` event enclosing the op.  The text, which
    is long for a loop, is kept for the first event of a key only."""
    modules = ([] if module_line is None else
               [(e.start_ns, e.end_ns, e.name.split("(")[0])
                for e in module_line.events])
    out, seen, i = [], set(), 0
    for e in op_line.events:
        s = e.start_ns
        while i < len(modules) and modules[i][1] <= s:
            i += 1
        mod = modules[i][2] if i < len(modules) and modules[i][0] <= s else ""
        name, _, text = e.name.partition(" = ")
        if (mod, name) in seen:
            text = None
        else:
            seen.add((mod, name))
        out.append((mod, name, s, e.end_ns, text))
    return out
