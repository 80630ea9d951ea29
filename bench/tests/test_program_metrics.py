"""The metrics that read the planner's own spans and trace counters.

Each reduces a synthesized window to a number worked out by hand, reads
nothing in a cell of the other kind and nothing where the trace holds no
``repro.*`` span (a program without the instrumentation).  The same
readers then run on a trace recorded on a TPU v5 lite with the spans in
it (``data/whatif_trace_spans.json``, two t6.whatif calls, written by
``tools/record_trace.py --out``).  Last, the four-chip grid cell's files
pass the generator's key checks.
"""

import json
import pathlib

import pytest

import calls as calls_mod
import run as bench_run
import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = json.loads((bench_run.BENCH / "peaks.json").read_text())["devices"]
MS = 1_000_000  # ns


def window(kind, host, counters=None, n_calls=6, n_traced=2,
           span=(10 * MS, 400 * MS), family=None):
    trace = {"window": list(span), "devices": [], "host": host}
    return bench_run.Window(
        kind=kind, chips=1, setup_s=1.0, work=1000.0,
        starts=[0.0] * n_calls, ends=[1.0] * n_calls,
        counters=counters or {}, peaks=PEAKS["TPU v5 lite"],
        config={"p": 100, "result_cache": None}, trace=trace,
        n_traced=n_traced, family=family)


def read(name, w):
    return bench_run.metric_reader(name)(w)


WHATIF_HOST = [
    ["repro.plan", 5 * MS, 100 * MS, "python"],
    ["repro.plan.size", 5 * MS, 65 * MS, "python"],      # 55 ms inside
    ["repro.plan.simulate", 65 * MS, 70 * MS, "python"],
    ["repro.plan.read", 70 * MS, 72 * MS, "python"],     # 2 ms
    ["repro.plan", 190 * MS, 300 * MS, "python"],
    ["repro.plan.size", 200 * MS, 250 * MS, "python"],   # 50 ms
    ["repro.plan.read", 260 * MS, 266 * MS, "python"],   # 6 ms
    ["repro.plan.size", 500 * MS, 600 * MS, "python"],   # after the window
]
GRID_HOST = [
    ["repro.grid", 20 * MS, 390 * MS, "python"],
    ["repro.sweep.dispatch", 20 * MS, 23 * MS, "python"],   # 3 ms
    ["repro.sweep.dispatch", 23 * MS, 30 * MS, "python"],   # 7 ms
    ["repro.sweep.gather", 30 * MS, 31 * MS, "python"],
]
WHATIF_SPANS = ("sizing_ms_per_call.whatif", "result_wait_ms_per_call.whatif")
WHATIF_COUNTERS = ("sizing_traces_per_call.whatif",)
GRID = ("dispatch_ms_per_call.grid", "engine_traces_per_call.grid")


def test_whatif_metrics_by_hand():
    w = window("whatif", WHATIF_HOST,
               counters={"/repro/plan/size_traced": 6})
    assert read("sizing_ms_per_call.whatif", w) == pytest.approx(
        (55 + 50) / 2)
    assert read("result_wait_ms_per_call.whatif", w) == pytest.approx(
        (2 + 6) / 2)
    assert read("sizing_traces_per_call.whatif", w) == 1.0
    # a bisection built once: no trace in the window is a count of zero
    assert read("sizing_traces_per_call.whatif",
                window("whatif", WHATIF_HOST)) == 0.0


def test_grid_metrics_by_hand():
    w = window("grid", GRID_HOST, n_calls=3, n_traced=1)
    assert read("dispatch_ms_per_call.grid", w) == pytest.approx(10.0)
    assert read("engine_traces_per_call.grid", w) == 0.0
    w = window("grid", GRID_HOST, n_calls=3, n_traced=1,
               counters={"/repro/stream/traced": 6})
    assert read("engine_traces_per_call.grid", w) == 2.0


@pytest.mark.parametrize("name", WHATIF_SPANS + WHATIF_COUNTERS + GRID)
def test_nothing_in_the_other_kind_or_without_spans(name):
    whatif = name.endswith(".whatif")
    other = window("grid" if whatif else "whatif",
                   GRID_HOST if whatif else WHATIF_HOST,
                   counters={"/repro/plan/size_traced": 6,
                             "/repro/stream/traced": 6})
    assert read(name, other) is None
    bare = window("whatif" if whatif else "grid",
                  [["bench.call", 10 * MS, 20 * MS, "python"]],
                  counters={"/repro/plan/size_traced": 6,
                            "/repro/stream/traced": 6})
    assert read(name, bare) is None
    untraced = window("whatif" if whatif else "grid", [])
    untraced.trace = None
    assert read(name, untraced) is None


@pytest.mark.parametrize("name", WHATIF_SPANS + WHATIF_COUNTERS + GRID)
def test_another_kind_of_the_family_reads_the_same(name):
    family = name.rsplit(".", 1)[1]
    host = WHATIF_HOST if family == "whatif" else GRID_HOST
    counters = {"/repro/plan/size_traced": 6, "/repro/stream/traced": 6}
    want = read(name, window(family, host, counters=counters))
    assert want is not None
    assert read(name, window(family + "_n1", host, counters=counters,
                             family=family)) == want


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "whatif_trace_spans.json").read_text())


def _summed_ms(recorded, name):
    """Plain loop: the spans' time inside the window, in ms."""
    lo, hi = recorded["window"]
    total = 0
    for h in recorded["host"]:
        if h[0] == name:
            total += max(0, min(h[2], hi) - max(h[1], lo))
    return total / MS


def test_metrics_on_recorded_trace(recorded):
    n = recorded["calls"]
    w = bench_run.Window(
        kind="whatif", chips=1, setup_s=1.0, work=60000.0,
        starts=[0.0] * n, ends=[1.0] * n,
        counters={"/repro/plan/size_traced": n},
        peaks=PEAKS["TPU v5 lite"], config={"p": 100, "result_cache": None},
        trace=recorded, n_traced=n)
    names = [h[0] for h in recorded["host"]]
    assert names.count("repro.plan") == n
    size = read("sizing_ms_per_call.whatif", w)
    wait = read("result_wait_ms_per_call.whatif", w)
    assert size == pytest.approx(_summed_ms(recorded, "repro.plan.size") / n)
    assert wait == pytest.approx(_summed_ms(recorded, "repro.plan.read") / n)
    assert 0 < size and 0 < wait
    # both lie inside the calls they belong to
    assert size + wait < _summed_ms(recorded, "repro.plan") / n
    assert read("sizing_traces_per_call.whatif", w) == 1.0
    assert read("dispatch_ms_per_call.grid", w) is None


def test_four_chip_grid_cell_files():
    cell = tiny.cell("t6.grid4")
    assert cell["chips"] == 4 and cell["traffic"] == "grid64"
    assert [w["name"] for w in tiny.bench()["workloads"]
            if w["chips"] == 4] == ["t6.grid4"]
    traffic = tiny.load(tiny.BENCH / "traffic" / "grid64.json")
    calls = calls_mod.make(tiny.config(cell), traffic, cell["chips"], 7)
    assert calls.grid.n_scenarios == 8192
    assert calls.work == 8192 * 20000
    assert tiny.load(tiny.BENCH / "limits" / "t6.grid4.json") == tiny.load(
        tiny.BENCH / "limits" / "t6.grid.json")


def test_stage_times_reduces_a_built_trace(tmp_path):
    """``tools/stage_times.py`` on a trace built by hand: each op's time
    goes to the innermost ``stream.<stage>`` of its metadata's stats
    (inline or by reference); control flow, ops outside the window and
    unscoped ops are kept apart."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "stage_times", tiny.BENCH / "tools" / "stage_times.py")
    st = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(st)
    space = st.xplane_pb2().XSpace()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench.window"
    host.event_metadata[2].name = "repro.grid"
    line = host.lines.add(name="python", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=100 * MS)
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=90 * MS)
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(f)/while/body/stream.route/stream.server"
    ops = {10: "%fusion.1 = f32[8] fusion(%p)",
           11: "%while.3 = (f32[8]) while(%t)",
           12: "%copy.2 = f32[8] copy(%x)", 13: "%fusion.5 = f32[8] fusion"}
    for k, name in ops.items():
        dev.event_metadata[k].name = name
    dev.event_metadata[10].stats.add(metadata_id=1, ref_value=2)
    dev.event_metadata[11].stats.add(metadata_id=1, ref_value=2)
    dev.event_metadata[13].stats.add(metadata_id=1,
                                     str_value="jit(f)/stream.draws/mul")
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for k, start_us, dur_us in [(11, 0, 90), (10, 10, 30), (12, 50, 10),
                                (13, 60, 20), (13, 200, 5)]:
        line.events.add(metadata_id=k, offset_ps=start_us * MS,
                        duration_ps=dur_us * MS)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    out = st.reduce_stages(str(path), chips=1)
    assert out["stages_ns"] == {"server": 30_000, "draws": 20_000,
                                st.UNSCOPED: 10_000}
    assert out["scope_stat"] == {"tf_op": 2, None: 1}
    assert out["host_spans"] == {"repro.grid": 1}
