"""Tiny versions of the benchmark's traffic mixes, for the CPU tests.

Every cell keeps its configuration, routing and kind of call; only the
grid's axes, the rate sets and the simulated query counts shrink.
"""

import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

SIZES = {
    "grid16": dict(n_queries=2000, lam={"linspace": [10.0, 160.0, 2]},
                   cpu=[1.0, 4.0], disk=[4.0], hit=[0.02, 0.18]),
    "whatif_50to300": dict(n_queries=3000, rates=[50.0, 150.0, 300.0]),
    "whatif_100to300": dict(n_queries=3000, rates=[100.0, 250.0]),
}


def load(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def bench() -> dict:
    return load(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    return next(w for w in bench()["workloads"] if w["name"] == name)


def sharded_grid_cell() -> dict:
    """t6.grid's configuration and traffic with the scenario axis sharded
    over four devices, the four-chip path of ``kinds/grid.py``."""
    return dict(cell("t6.grid"), chips=4)


def config(cell_entry: dict) -> dict:
    conf = next(c for c in bench()["configs"]
                if c["name"] == cell_entry["config"])
    return load(ROOT / conf["file"])


def traffic(name: str) -> dict:
    t = load(BENCH / "traffic" / f"{name}.json")
    t = copy.deepcopy(t)
    t.update(SIZES[name])
    t["check"]["calls"] = 2
    if "scenarios" in t["check"]:
        t["check"]["scenarios"] = 6
    return t


def load_shrunk(path: pathlib.Path) -> dict:
    """``run.load_json`` with every traffic file shrunk."""
    path = pathlib.Path(path)
    if path.parent.name == "traffic":
        return traffic(path.stem)
    return load(path)
