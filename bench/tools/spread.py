#!/usr/bin/env python3
"""Runs of one cell in sets, as a bound is set from them, and their spreads.

    python3 bench/tools/spread.py --workload t6.whatif --runs 6 --sets 2 \
        --seconds 51 --traced 3 --out chiprun_out/P

Each run is a process of its own (``bench/run.py``; this parent never
touches JAX, so the child has the chip).  The sets run the same seeds, one
per run; ``--traced`` runs more with ``--trace 1`` on further seeds.  Each
run's output and errors go to ``<out>/<cell>.<set><i>.out|err``.  Prints,
per end-to-end metric, each set's median and quartile spread (the distance
between the first and third quartile of ``statistics.quantiles``, as a
share of the median) and whether every run was correct.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one(args, tag: str, seed: int, trace: int) -> dict:
    out = pathlib.Path(args.out)
    base = out / f"{args.workload}.{tag}"
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds),
           "--trace", str(trace)]
    with open(f"{base}.out", "w") as fo, open(f"{base}.err", "w") as fe:
        rc = subprocess.run(cmd, stdout=fo, stderr=fe,
                            timeout=args.timeout).returncode
    lines = pathlib.Path(f"{base}.out").read_text().strip().splitlines()
    res = json.loads(lines[-1]) if rc == 0 and lines else {}
    vals = {k: v["value"] for k, v in res.get("metrics", {}).items()}
    print(json.dumps({"run": tag, "seed": seed, "rc": rc,
                      "correct": res.get("correct"),
                      "attempted": res.get("attempted"), "metrics": vals,
                      "device": res.get("device")}), flush=True)
    return {"correct": res.get("correct") is True, "metrics": vals}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1001)
    ap.add_argument("--timeout", type=float, default=1300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    pathlib.Path(args.out).mkdir(parents=True, exist_ok=True)
    seeds = [args.first_seed + 104729 * i for i in range(args.runs)]
    sets, ok = [], True
    for s in range(args.sets):
        runs = [one(args, f"{'AB'[s] if s < 2 else s}{i + 1}", seed, 0)
                for i, seed in enumerate(seeds)]
        ok &= all(r["correct"] for r in runs)
        sets.append(runs)
    for i in range(args.traced):
        ok &= one(args, f"T{i + 1}", seeds[-1] + 7 * (i + 1), 1)["correct"]
    summary = {}
    for name in (sets[0][0]["metrics"] if sets and sets[0] else {}):
        summary[name] = [
            {"median": statistics.median(v), "spread": spread(v)}
            for v in ([r["metrics"][name] for r in runs] for runs in sets)]
    print(json.dumps({"workload": args.workload, "all_correct": ok,
                      "sets": summary}), flush=True)


if __name__ == "__main__":
    main()
