#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip; the last line is its result.

    python3 bench/run.py --workload t6.grid --seed 7 --seconds 51 --trace 0

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``,
read by the generator of its kind, ``kinds/<kind>.py``; see ``calls.py``).
Its limits are ``limits/<cell>.json``, and each metric is read by
``metrics/<metric>.py`` (most read the windows of one family of calls;
see ``calls.py``).  A run:

1. fails, printing no result, unless JAX finds a TPU with the cell's chips;
2. keeps JAX's compilation cache in ``.jax_cache/`` at the checkout's root;
3. sets up: builds the cell's calls and warms every program they use
   (``setup_s`` runs from the start of this script to here; its phases
   go to standard error);
4. measures a closed-loop window: one planner issues whole calls, each
   after the last returned, until ``--seconds`` have passed; the window
   runs from the first call's start to the last call's end.  With
   ``--trace 1`` the profiler records the window's last calls
   (``TRACE_SECONDS``);
5. reads the chips' peak memory, then compares a sample of the window's
   answers, drawn from the seed, with the plain reference (the kind's
   ``numbers`` and ``check.py``), and prints each number beside its limit
   on standard error;
6. prints one JSON line: the end-to-end metrics (``--trace 0``) or the
   per-layer metrics (``--trace 1``) of the cell, the device, and the
   numbers compared, last.
"""

import time

T_START = time.perf_counter()
T_CHIP = T_START        # when the chip was found (main sets it)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import calls as calls_mod  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
# with --trace 1 the profiler records the window's last whole calls, from
# this long before its end (one call at least): enough for the per-layer
# shares, and a trace whose reading stays well inside a run's time limit
TRACE_SECONDS = 2.0
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


@dataclasses.dataclass
class Window:
    """What a metric reader sees of one run."""

    kind: str
    chips: int
    setup_s: float
    work: float                 # simulated queries per call
    starts: list                # host clock, seconds
    ends: list
    counters: dict              # jax.monitoring events within the window
    peaks: dict                 # the chip's row of peaks.json
    config: dict
    trace: dict = None          # trace_reduce.load(...) with --trace 1
    n_traced: int = 0           # the window's last calls, which it holds
    family: str = None          # the kind's FAMILY; the kind where not given

    def __post_init__(self):
        if self.family is None:
            self.family = self.kind

    @property
    def n_calls(self) -> int:
        return len(self.starts)

    @property
    def window_s(self) -> float:
        return self.ends[-1] - self.starts[0]

    @property
    def latencies(self) -> list:
        return [e - s for s, e in zip(self.starts, self.ends)]

    def trace_devices(self) -> list:
        """The traced planes of the chips this cell uses."""
        return self.trace["devices"][:self.chips]


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def metric_reader(name: str):
    return calls_mod.load_module(calls_mod.METRICS / f"{name}.py",
                                 "bench_metric_").read


def metrics_of(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def require_chip(chips: int):
    """The cell's TPU devices, or exit without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} "
                         "devices; the benchmark runs only on the chip")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program written to it.

    ``plan_capacity`` builds its bisection anew on every call, so each
    what-if call lowers a program again.  With JAX's default thresholds a
    program that compiles in under a second is never written, and every
    call of the window would compile it; written, a call finds it in the
    cache, and nothing compiles inside the window.
    """
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(args, bench: dict, cell: dict, devices) -> dict:
    """Set up, measure and check one cell; the result line's object."""
    import jax
    import check
    import trace_reduce

    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    config = load_json(ROOT / conf_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    enable_cache()

    counters, live = {}, [False]

    def count(event, *_, **__):
        if live[0]:
            counters[event] = counters.get(event, 0) + 1

    jax.monitoring.register_event_listener(count)
    jax.monitoring.register_event_duration_secs_listener(count)

    t_built = time.perf_counter()
    calls = calls_mod.make(config, traffic, cell["chips"], args.seed)
    calls.warm()
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f} s = to the chip {T_CHIP - T_START:.3f} s "
          f"+ load {t_built - T_CHIP:.3f} s + build and warm "
          f"{T_START + setup_s - t_built:.3f} s", file=sys.stderr)

    records, starts, ends = [], [], []

    def issue(until: float, at_least_one: bool = False) -> None:
        """Whole calls, each after the last, until the window is this long."""
        n0 = len(records)
        while (not starts or ends[-1] - starts[0] < until
               or (at_least_one and len(records) == n0)):
            with jax.profiler.TraceAnnotation(trace_reduce.CALL_SPAN):
                starts.append(time.perf_counter())
                records.append(calls.call())
                ends.append(time.perf_counter())

    reduced, n_traced = None, 0
    live[0] = True
    if args.trace:
        issue(args.seconds - TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        untraced = len(records)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            issue(args.seconds, at_least_one=True)
        jax.profiler.stop_trace()
        n_traced = len(records) - untraced
    else:
        issue(args.seconds)
    live[0] = False
    if args.trace:
        reduced = trace_reduce.load(
            glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
    memory = peak_bytes(devices)

    window = Window(kind=calls.kind, family=calls.family,
                    chips=cell["chips"], setup_s=setup_s, work=calls.work,
                    starts=starts, ends=ends, counters=counters,
                    peaks=peaks[kind], config=config, trace=reduced,
                    n_traced=n_traced)
    failed = sum(calls.failed(rec) for rec in records)
    numbers = calls.numbers(records, args.seed, window.latencies)
    correct, table = check.judge(numbers, check.limits(cell["name"]))

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, cell["name"]):
        value = metric_reader(m["name"])(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": correct and failed == 0, "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        win = reduced["window"]
        devs = window.trace_devices()
        device["busy_s"] = sum(trace_reduce.busy_ns(d, win)
                               for d in devs) / len(devs) / 1e9
        device["window_s"] = (win[1] - win[0]) / 1e9
        ops, gaps = {}, {}
        for d in devs:
            for k, v in trace_reduce.durations_by_name(d, win).items():
                ops[k] = ops.get(k, 0) + v
            for k, v in trace_reduce.idle_gaps(
                    d, win, reduced["host"]).items():
                gaps[k] = gaps.get(k, 0) + v
        out["breakdown"] = {"device_ops": trace_reduce.top(ops),
                            "idle_gaps": trace_reduce.top(gaps)}
    print(f"window: {len(records)} calls in {window.window_s:.3f} s; "
          f"events in the window: {counters}", file=sys.stderr)
    for name, t in table.items():
        verdict = ("ok" if t["limit"] is not None
                   and t["value"] <= t["limit"] else "FAIL")
        print(f"check {name}: {t['value']!r} limit {t['limit']!r} "
              f"{verdict}", file=sys.stderr)
    out["check"] = table
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    devices = require_chip(int(cell["chips"]))
    global T_CHIP
    T_CHIP = time.perf_counter()
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    out = run(args, bench, cell, devices)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
