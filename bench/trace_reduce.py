"""From a profiler trace to the numbers the per-layer metrics read.

    python bench/trace_reduce.py TRACE.xplane.pb > reduced.json

:func:`load` keeps, from the ``.xplane.pb`` that ``jax.profiler`` writes,
only what the metrics need, as plain lists (this is also the form of the
recorded trace under ``tests/data/``):

* ``devices``: per device plane (``/device:TPU:n``), the ``XLA Ops``
  events (one per operation run on the device, named by its HLO
  instruction) and the ``XLA Modules`` events (one per program
  execution), each ``[name, start_ns, end_ns]``;
* ``host``: the host's named spans, ``[name, start_ns, end_ns, thread]``;
* ``window``: ``[start_ns, end_ns]`` of the benchmark's ``bench.window``
  span, which encloses the measured calls.

The reductions below clip everything to the window:

* :func:`busy_ns`: the union of a device's op intervals (a time counts once
  however many ops overlap it);
* :func:`durations_by_name`: summed op time per op name;
* :func:`executions`: program executions that start in the window;
* :func:`idle_gaps`: the stretches in which a device ran nothing, each
  named after the innermost host span open at its middle.
"""

from __future__ import annotations

import bisect
import collections
import json
import sys

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(text: str) -> str:
    """An operation's HLO instruction name (``%fusion.12``), without the
    instruction's text that follows it in the trace."""
    return text.split(" = ", 1)[0]


def load(path: str) -> dict:
    """The reduced form of one ``.xplane.pb`` trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[op_name(e.name), int(e.start_ns),
                                 int(e.end_ns)] for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.end_ns),
                             line.name] for e in line.events
                            if e.end_ns > e.start_ns)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(windows)}")
    return {"window": windows[0][1:3], "devices": devices, "host": host}


def _clip(events, window):
    lo, hi = window
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def busy_intervals(dev: dict, window) -> list:
    """Merged [start, end) intervals in which the device ran an op."""
    merged = []
    for _, s, e in sorted(_clip(dev["ops"], window), key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(dev: dict, window) -> int:
    return sum(e - s for s, e in busy_intervals(dev, window))


def idle_percent(devices: list, window) -> float:
    """100 (1 - busy / window), the busy time averaged over ``devices``."""
    busy = sum(busy_ns(d, window) for d in devices) / len(devices)
    return 100.0 * (1.0 - busy / (window[1] - window[0]))


# control flow: such an op's interval holds the ops of its body
CONTAINERS = ("%while", "%conditional", "%call")


def durations_by_name(dev: dict, window) -> dict:
    """Summed op time (ns) per op name within the window, control-flow
    ops left out (their time is their body's)."""
    out = collections.Counter()
    for name, s, e in _clip(dev["ops"], window):
        if not name.startswith(CONTAINERS):
            out[name] += e - s
    return dict(out)


def executions(dev: dict, window) -> int:
    """Program executions that start within the window."""
    lo, hi = window
    return sum(1 for _, s, _ in dev["modules"] if lo <= s < hi)


def idle_gaps(dev: dict, window, host) -> dict:
    """Idle ns of the device per name of what the host was doing then.

    A gap is named after the innermost span open at its middle on the
    thread that ran the window, other than the benchmark's own window and
    call spans; "host: between calls" where none is open.  Spans of one
    thread nest, so the innermost open span is the latest-starting one.
    """
    thread = next(h[3] for h in host if h[0] == WINDOW_SPAN)
    spans = sorted((h for h in host if h[3] == thread
                    and h[0] not in (WINDOW_SPAN, CALL_SPAN)),
                   key=lambda h: h[1])
    starts = [h[1] for h in spans]
    out = collections.Counter()
    edge = window[0]
    for s, e in busy_intervals(dev, window) + [[window[1], window[1]]]:
        if s > edge:
            mid = (edge + s) // 2
            name = "host: between calls"
            i = bisect.bisect_right(starts, mid) - 1
            while i >= 0:
                if spans[i][2] > mid:
                    name = spans[i][0]
                    break
                i -= 1
            out[name] += s - edge
        edge = max(edge, e)
    return dict(out)


def top(counter: dict, n: int = 10) -> list:
    """The n largest entries as [[name, seconds], ...]."""
    return [[k, v / 1e9] for k, v in
            sorted(counter.items(), key=lambda kv: -kv[1])[:n]]


if __name__ == "__main__":
    json.dump(load(sys.argv[1]), sys.stdout)
