"""Named host spans and counters: the engine's one timing system.

``span(name)`` times a block on ``time.perf_counter_ns`` and
records a ``Span``: its name, the id of the top-level call it belongs to,
the index of the enclosing span in that call's list, its start and end,
the backend compiles JAX reported while it was the innermost open span of
its thread, and the counters ``add`` put on it.  A block that times device
work ends at a host sync or a ``block_until_ready``, so its span holds the
device time and not the enqueue time.

Each span also opens a ``jax.profiler.TraceAnnotation`` of its name: under
a running profiler it lands in the trace on the host clock of the device
ops.  That clock and ``perf_counter_ns`` differ by one constant, so one
span that the trace holds maps every recorded span onto the trace, the
spans that the profiler dropped included.

The record is always on; a path records on the order of a hundred spans.
``record(root)`` returns a closed span's subtree, ``history()`` the span
lists of the process's latest top-level calls, for tools that watch the
program from outside.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# top-level calls that ``history()`` keeps: a path call makes two
# (``session.init``, ``path``), so 1024 calls, four times the most (252)
# a 51-s window of the image-dictionary path cell held on one v5e; about
# 13 MB
HISTORY = 2048


@dataclasses.dataclass
class Span:
    name: str
    call: int                    # id of the top-level call
    parent: Optional[int]        # index of the enclosing span in the list
    start_ns: int
    end_ns: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    compiles: int = 0            # backend compiles while innermost
    compile_s: float = 0.0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_local = threading.local()       # .log: the call's spans; .open: indices
_call_ids = itertools.count(1)
_history: collections.deque = collections.deque(maxlen=HISTORY)


def _innermost() -> Optional[Span]:
    open_ = getattr(_local, "open", None)
    return _local.log[open_[-1]] if open_ else None


@contextlib.contextmanager
def span(name: str):
    """Record the block as a ``Span``, which it yields."""
    if not getattr(_local, "open", None):
        _local.log, _local.open = [], []
        call, parent = next(_call_ids), None
    else:
        call, parent = _local.log[0].call, _local.open[-1]
    log, open_ = _local.log, _local.open
    s = Span(name, call, parent, time.perf_counter_ns())
    open_.append(len(log))
    log.append(s)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        s.end_ns = time.perf_counter_ns()
        open_.pop()
        if not open_:
            _history.append(log)


def add(key: str, n) -> None:
    """Add ``n`` to counter ``key`` of the innermost open span, if any."""
    s = _innermost()
    if s is not None:
        s.counters[key] = s.counters.get(key, 0) + n


def record(root: Span) -> list:
    """``root`` and the spans opened inside it, in opening order, with
    ``parent`` an index into the returned list.  Call it on ``root``'s
    thread, right after ``root`` closed."""
    log = _local.log
    i = next(k for k in range(len(log) - 1, -1, -1) if log[k] is root)
    if i == 0:
        return list(log)
    return [dataclasses.replace(s, parent=None if k == 0 else s.parent - i)
            for k, s in enumerate(log[i:])]


def history() -> list:
    """The span lists of the latest top-level calls, oldest first."""
    return list(_history)


def total(spans, *names) -> float:
    """Seconds summed over the spans named one of ``names``."""
    return sum(s.seconds for s in spans if s.name in names)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        s = _innermost()
        if s is not None:
            s.compiles += 1
            s.compile_s += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)
