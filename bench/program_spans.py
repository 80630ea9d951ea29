"""What the program's own instrumentation (``repro.obs.spans``) left in a
run: its host spans in the reduced trace, and whether there are any.

The planner names its spans ``repro.<step>`` (``repro.plan.size``,
``repro.sweep.dispatch``, ...) and its trace counters ``/repro/<event>``.
A program without them (an older commit) leaves none, and a metric that
reads them then reads nothing rather than zero.
"""

from __future__ import annotations

PREFIX = "repro."


def spans(w, name: str) -> list:
    """The host spans called ``name`` in the traced window, clipped to it,
    as ``[start_ns, end_ns]``."""
    lo, hi = w.trace["window"]
    out = []
    for h in w.trace["host"]:
        if h[0] == name and h[2] > lo and h[1] < hi:
            out.append([max(h[1], lo), min(h[2], hi)])
    return out


def ms_per_traced_call(w, name: str):
    """Summed duration of the ``name`` spans, in ms per traced call; None
    where the trace holds no such span."""
    found = spans(w, name)
    if not found or not w.n_traced:
        return None
    return sum(e - s for s, e in found) / 1e6 / w.n_traced


def per_call(w, event: str, name: str):
    """Window events ``event`` per call, where the traced window holds a
    span ``name`` (the program has the instrumentation); None elsewhere.
    No event in the window is then a count of zero."""
    if w.trace is None or not spans(w, name):
        return None
    return w.counters.get(event, 0) / w.n_calls
