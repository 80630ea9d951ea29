#!/usr/bin/env python3
"""Record the small trace that the metric tests read, on the chip.

    python3 bench/tools/record_trace.py --out whatif_trace.json

Warms the t6.whatif cell, traces two of its calls under the
benchmark's own window and call spans, and writes the reduced trace
(``trace_reduce.load``) with the host spans of the window's thread only,
0.1 ms or longer, and the number of calls, as JSON.
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run as bench_run  # noqa: E402

# the host's python spans come by the hundred thousand; the fixture keeps
# those that can name an idle gap worth reading
MIN_HOST_SPAN_NS = 100_000
CALLS = 2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == "t6.whatif")
    bench_run.require_chip(1)
    bench_run.enable_cache()
    import jax
    import calls as calls_mod
    import trace_reduce
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    calls = calls_mod.make(
        bench_run.load_json(bench_run.ROOT / conf["file"]),
        bench_run.load_json(bench_run.BENCH / "traffic"
                            / f"{cell['traffic']}.json"), 1, 5)
    calls.warm()
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation(trace_reduce.CALL_SPAN):
                calls.call()
    jax.profiler.stop_trace()
    red = trace_reduce.load(
        glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0])
    thread = next(h[3] for h in red["host"]
                  if h[0] == trace_reduce.WINDOW_SPAN)
    lo, hi = red["window"]
    red["host"] = [h for h in red["host"] if h[3] == thread and h[2] > lo
                   and h[1] < hi and h[2] - h[1] >= MIN_HOST_SPAN_NS]
    red["devices"] = red["devices"][:1]
    red["calls"] = CALLS
    pathlib.Path(args.out).write_text(json.dumps(red))


if __name__ == "__main__":
    main()
