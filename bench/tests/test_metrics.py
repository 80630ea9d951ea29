"""Each metric file reduces a fixture to the expected number.

The per-layer metrics read a trace recorded on a TPU v5 lite (two what-if
calls of t6.whatif, reduced by ``trace_reduce.load``, in ``data/``); the
expected numbers are worked out here from the raw events by other means
(a sweep over sorted interval ends, plain counting).
"""

import json
import pathlib

import numpy as np
import pytest

import run as bench_run
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = json.loads((bench_run.BENCH / "peaks.json").read_text())["devices"]


def window(kind, starts, ends, trace=None, counters=None, chips=1,
           config=None, work=1000.0, setup_s=12.5, n_traced=0, family=None):
    return bench_run.Window(
        kind=kind, chips=chips, setup_s=setup_s, work=work, starts=starts,
        ends=ends, counters=counters or {}, peaks=PEAKS["TPU v5 lite"],
        config=config or {"p": 100, "result_cache": None}, trace=trace,
        n_traced=n_traced, family=family)


def read(name, w):
    return bench_run.metric_reader(name)(w)


def test_end_to_end_metrics():
    g = window("grid", [0.0, 1.0, 2.5], [1.0, 2.5, 4.0], work=2048 * 20000)
    assert read("grid_queries_per_s", g) == pytest.approx(
        3 * 2048 * 20000 / 4.0)
    assert read("whatif_mean_s", g) is None
    w = window("whatif", list(np.arange(10.0)), list(np.arange(10.0) + 0.5
                                                   + 0.01 * np.arange(10)))
    assert read("whatif_mean_s", w) == pytest.approx(9.59 / 10)
    lat = 0.5 + 0.01 * np.arange(10)
    assert read("whatif_p90_s", w) == pytest.approx(np.percentile(lat, 90))
    assert read("setup_s", w) == 12.5
    assert read("grid_queries_per_s", w) is None


def test_end_to_end_metrics_follow_the_family():
    """Another kind of call of the what-if family reads what a what-if
    call reads; a kind of the grid family reads no what-if metric."""
    starts = list(np.arange(10.0))
    ends = list(np.arange(10.0) + 0.5 + 0.01 * np.arange(10))
    w = window("whatif_n1", starts, ends, family="whatif")
    assert w.kind == "whatif_n1"
    for name in ("whatif_mean_s", "whatif_p90_s"):
        assert read(name, w) == read(name, window("whatif", starts, ends))
    assert read("grid_queries_per_s", w) is None
    g = window("grid_n1", starts, ends, family="grid", work=2048 * 20000)
    assert read("whatif_mean_s", g) is None
    assert read("whatif_p90_s", g) is None
    assert read("grid_queries_per_s", g) == pytest.approx(
        10 * 2048 * 20000 / ends[-1])


def test_traces_per_call():
    counters = {"/jax/core/compile/jaxpr_trace_duration": 6}
    g = window("grid", [0.0, 1.0], [1.0, 2.0], counters=counters)
    assert read("traces_per_call.grid", g) == 3.0
    assert read("traces_per_call.whatif", g) is None
    assert read("traces_per_call.grid", window("grid", [0.0], [1.0])) == 0.0
    w = window("whatif", [0.0, 1.0, 2.0], [1.0, 2.0, 3.0], counters=counters)
    assert read("traces_per_call.whatif", w) == 2.0


def _union_ns(events, lo, hi):
    """Busy time by a sweep over interval ends (a second method)."""
    points = []
    for _, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    depth, last, total = 0, None, 0
    for t, d in points:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "whatif_trace.json").read_text())


def test_trace_reduce_on_recorded_trace(recorded):
    win = recorded["window"]
    dev = recorded["devices"][0]
    busy = trace_reduce.busy_ns(dev, win)
    assert busy == _union_ns(dev["ops"], *win)
    assert 0 < busy < win[1] - win[0]
    by_name = trace_reduce.durations_by_name(dev, win)
    assert by_name and not any(k.startswith("%while") for k in by_name)
    assert trace_reduce.executions(dev, win) == sum(
        1 for m in dev["modules"] if win[0] <= m[1] < win[1])
    gaps = trace_reduce.idle_gaps(dev, win, recorded["host"])
    assert sum(gaps.values()) == (win[1] - win[0]) - busy
    assert trace_reduce.idle_percent([dev], win) == pytest.approx(
        100.0 * (1 - busy / (win[1] - win[0])))


def test_per_layer_metrics_on_recorded_trace(recorded):
    win = recorded["window"]
    dev = recorded["devices"][0]
    n_calls = recorded["calls"]
    w = window("whatif", [0.0] * n_calls, [1.0] * n_calls, trace=recorded,
               work=60000.0, n_traced=n_calls)
    launches = sum(1 for m in dev["modules"] if win[0] <= m[1] < win[1])
    assert read("launches_per_call.whatif", w) == launches / n_calls
    busy = _union_ns(dev["ops"], *win)
    assert read("device_idle.whatif", w) == pytest.approx(
        100.0 * (1 - busy / (win[1] - win[0])))
    kernel_ns = sum(min(e, win[1]) - max(s, win[0])
                    for name, s, e in dev["ops"]
                    if "maxplus" in name and e > win[0] and s < win[1])
    assert kernel_ns > 0
    need_s = n_calls * 60000 * 101 * 12 / 819e9
    assert read("scan_kernel_roofline.whatif", w) == pytest.approx(
        100.0 * need_s / (kernel_ns / 1e9))
    assert read("device_idle.grid", w) is None
    g = window("grid", [0.0] * n_calls, [1.0] * n_calls, trace=recorded,
               work=1e6, n_traced=n_calls)
    assert read("engine_busy_ms_per_mquery.grid", g) == pytest.approx(
        busy / 1e6 / n_calls)


def test_per_layer_metrics_follow_the_family(recorded):
    """On the recorded trace, another kind of the what-if family reads
    every what-if per-layer metric as the what-if kind does, except the
    scan kernel's roofline, which counts one simulation a call and so
    reads the kind ``whatif`` alone."""
    n_calls = recorded["calls"]
    args = ([0.0] * n_calls, [1.0] * n_calls)
    kw = dict(trace=recorded, work=60000.0, n_traced=n_calls,
              counters={"/jax/core/compile/jaxpr_trace_duration": 4})
    same = window("whatif", *args, **kw)
    other = window("whatif_n1", *args, family="whatif", **kw)
    for name in ("launches_per_call.whatif", "device_idle.whatif",
                 "traces_per_call.whatif"):
        assert read(name, same) is not None
        assert read(name, other) == read(name, same)
    assert read("scan_kernel_roofline.whatif", same) is not None
    assert read("scan_kernel_roofline.whatif", other) is None
