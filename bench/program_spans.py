"""The program's own spans for the window's calls, read by the
``window_*`` span and counter metrics.

The program (``repro.core.spans``) keeps the span lists of its latest
top-level calls.  A window call of a path cell makes two of them, the
session's ``session.init`` and the engine's ``path``, and the window's
calls are the last the run made, so the last ``2 n`` lists are the ``n``
window calls, in order.  The program keeps ``spans.HISTORY`` lists, so a
window of more than ``HISTORY / 2`` calls cannot be read.  Where the
program keeps no spans (an older program) ``calls`` returns None, and so
do the readers; where it keeps too few, or they do not pair as above, it
warns and returns None.
"""
from __future__ import annotations

import warnings


def calls(run):
    """[(unit, [its session.init spans, its path spans])] per window call."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    n = len(run.units)
    lists = spans.history()[-2 * n:]
    if n == 0:
        return None
    if len(lists) < 2 * n:
        warnings.warn(f"the program kept {len(lists)} call records of the "
                      f"{2 * n} that {n} window calls make (it keeps "
                      f"{spans.HISTORY}): no span metrics", RuntimeWarning)
        return None
    pairs = list(zip(lists[0::2], lists[1::2]))
    if any(a[0].name != "session.init" or b[0].name != "path"
           for a, b in pairs):
        warnings.warn("the last call records do not pair a session.init "
                      "with a path per window call: no span metrics",
                      RuntimeWarning)
        return None
    return [(u, list(pair)) for u, pair in zip(run.units, pairs)]


def per_call(run, value):
    """Mean over the window's calls of ``value(unit, span lists)``."""
    found = calls(run)
    if found is None:
        return None
    return sum(value(u, lists) for u, lists in found) / len(found)


def _all(lists):
    return [s for spans in lists for s in spans]


def seconds(run, *names):
    """Seconds per call in the spans named one of ``names``."""
    return per_call(run, lambda _, lists: sum(
        s.seconds for s in _all(lists) if s.name in names))


def counted(run, key):
    """Counter ``key`` per call, summed over the call's spans."""
    return per_call(run, lambda _, lists: sum(
        s.counters.get(key, 0) for s in _all(lists)))


def compile_seconds(run, *names):
    """Compile seconds per call on the spans named one of ``names``."""
    return per_call(run, lambda _, lists: sum(
        s.compile_s for s in _all(lists) if s.name in names))


def unspanned(run):
    """Seconds per call that no leaf span covers: the call's wall time
    less its spans that enclose no other span."""
    def value(unit, lists):
        covered = 0.0
        for spans in lists:
            parents = {s.parent for s in spans}
            covered += sum(s.seconds for i, s in enumerate(spans)
                           if i not in parents)
        return unit.wall - covered
    return per_call(run, value)


def ratio(run, num: str, den: str):
    """100 * sum of counter ``num`` / sum of counter ``den`` over the
    window's calls."""
    found = calls(run)
    if found is None:
        return None
    spans = [s for _, lists in found for s in _all(lists)]
    total = sum(s.counters.get(den, 0) for s in spans)
    if total == 0:
        return None
    return 100.0 * sum(s.counters.get(num, 0) for s in spans) / total
