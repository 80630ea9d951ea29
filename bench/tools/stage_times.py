#!/usr/bin/env python3
"""Device time per stage of the stream engine in one cell, on the chip.

    python3 bench/tools/stage_times.py --workload t6.grid --calls 2 \
        --out stages_t6.grid.json

Warms the cell's calls, times ``--calls`` calls with the profiler off,
then traces as many under the benchmark's own window and call spans.
Each device operation of the traced window is assigned to the innermost
``stream.<stage>`` scope (``jax.named_scope`` in ``_simulate_stream``)
named in a string stat of its trace event or of the event's metadata; on
a TPU v5e the scope path is the metadata's ``tf_op`` stat, and the stat
that held it is printed.  Control-flow containers are left out, as
``trace_reduce.durations_by_name`` leaves them out: their time is their
body's.  Prints, per stage, device ms per call and the share of all
leaf-op device time, the operations no scope claims, the mean latency of
the untraced and the traced calls, and the program's ``repro.*`` host
spans per traced call.  Reads nothing the benchmark's metrics read.
"""

from __future__ import annotations

import argparse
import collections
import glob
import importlib.util
import json
import pathlib
import re
import shutil
import statistics
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402

SCOPE = re.compile(r"stream\.([A-Za-z_]+)")
UNSCOPED = "(no stream scope)"


def xplane_pb2():
    """The ``XSpace`` protobuf classes.  ``jax.profiler.ProfileData`` gives
    an event's own stats but not those of its metadata, where the device
    trace keeps an operation's scope path; the installed TensorFlow ships
    the generated module, which is loaded here by path, without importing
    TensorFlow itself."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise SystemExit("reading op metadata needs tensorflow's "
                         "tsl/profiler/protobuf/xplane_pb2.py")
    path = (pathlib.Path(spec.origin).parent
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _strings(stats, stat_names) -> list:
    """(stat name, text) of the string-valued stats (inline or by ref)."""
    out = []
    for st in stats:
        kind = st.WhichOneof("value")
        if kind == "str_value":
            out.append((stat_names.get(st.metadata_id), st.str_value))
        elif kind == "ref_value":
            out.append((stat_names.get(st.metadata_id),
                        stat_names.get(st.ref_value, "")))
    return out


def stage_of(texts) -> tuple:
    """(stage, stat name): the innermost ``stream.<stage>`` in the first
    text that names one."""
    for key, text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1], key
    return UNSCOPED, None


def _events(plane):
    """(line name, event, start_ns, end_ns) of every event of the plane."""
    for line in plane.lines:
        for e in line.events:
            start = line.timestamp_ns + e.offset_ps / 1000.0
            yield line.name, e, start, start + e.duration_ps / 1000.0


def reduce_stages(path: str, chips: int) -> dict:
    """Leaf-op device ns per stage over the cell's chips, the stat that
    held the scope, the top unscoped ops and the host spans."""
    space = xplane_pb2().XSpace()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    host, devices = [], []
    for plane in space.planes:
        if plane.name.startswith("/host:CPU"):
            host += [(plane.event_metadata[e.metadata_id].name, s, t)
                     for _, e, s, t in _events(plane)]
        elif (plane.name.startswith("/device:TPU:")
              and plane.name[len("/device:TPU:"):].isdigit()):
            devices.append(plane)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    lo, hi = next((s, t) for n, s, t in host
                  if n == trace_reduce.WINDOW_SPAN)
    stages, unscoped = collections.Counter(), collections.Counter()
    keys = collections.Counter()
    for plane in devices[:chips]:
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        for line, e, s, t in _events(plane):
            meta = plane.event_metadata[e.metadata_id]
            name = trace_reduce.op_name(meta.name)
            s, t = max(s, lo), min(t, hi)
            if (line != trace_reduce.OPS_LINE or t <= s
                    or name.startswith(trace_reduce.CONTAINERS)):
                continue
            stage, key = stage_of(_strings(e.stats, stat_names)
                                  + _strings(meta.stats, stat_names))
            stages[stage] += t - s
            keys[key] += 1
            if stage == UNSCOPED:
                unscoped[name] += t - s
    spans = collections.Counter(n for n, s, t in host
                                if n.startswith("repro.") and t > lo
                                and s < hi)
    return {"stages_ns": dict(stages), "scope_stat": dict(keys),
            "unscoped_top": trace_reduce.top(unscoped),
            "host_spans": dict(spans)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    chips = int(cell["chips"])
    bench_run.require_chip(chips)
    bench_run.enable_cache()
    import jax
    import calls as calls_mod
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    calls = calls_mod.make(
        bench_run.load_json(bench_run.ROOT / conf["file"]),
        bench_run.load_json(bench_run.BENCH / "traffic"
                            / f"{cell['traffic']}.json"), chips, args.seed)
    calls.warm()

    def timed() -> float:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.CALL_SPAN):
            calls.call()
        return time.perf_counter() - t0

    untraced = [timed() for _ in range(args.calls)]
    tdir = tempfile.mkdtemp(prefix="stage_trace_")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        traced = [timed() for _ in range(args.calls)]
    jax.profiler.stop_trace()
    out = reduce_stages(
        glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0], chips)
    shutil.rmtree(tdir, ignore_errors=True)
    total = sum(out["stages_ns"].values())
    out.update(workload=args.workload, calls=args.calls,
               untraced_mean_s=statistics.mean(untraced),
               traced_mean_s=statistics.mean(traced),
               untraced_s=untraced, traced_s=traced)
    print(f"{args.workload}: {args.calls} calls, leaf-op device time "
          f"{total / 1e6 / args.calls:.3f} ms/call over {chips} chip(s); "
          f"scope stat {out['scope_stat']}")
    for stage, ns in sorted(out["stages_ns"].items(), key=lambda kv: -kv[1]):
        print(f"  {stage:18s} {ns / 1e6 / args.calls:12.3f} ms/call "
              f"{100.0 * ns / max(total, 1):6.2f}%")
    print(f"unscoped, longest: {out['unscoped_top'][:5]}")
    print(f"mean latency: untraced {out['untraced_mean_s']:.6f} s, traced "
          f"{out['traced_mean_s']:.6f} s")
    print("repro.* spans per traced call: "
          f"{sum(out['host_spans'].values()) / args.calls:g} "
          f"{out['host_spans']}")
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
