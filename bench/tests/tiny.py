"""Tiny versions of the benchmark's traffic mixes, for the CPU tests.

Every cell keeps its configuration, routing and kind of call; only the
sizes that its traffic file's own ``tiny`` object gives (the grid's
axes, the rate sets, the simulated query counts) and the checked sample
shrink.
"""

import json
import pathlib

import calls

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


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


def family(cell_entry: dict) -> str:
    """The ``FAMILY`` of the kind of call that the cell's traffic names."""
    kind = load(BENCH / "traffic" / f"{cell_entry['traffic']}.json")["kind"]
    return getattr(calls.kind_module(kind), "FAMILY", None)


def shrink(path: pathlib.Path) -> dict:
    """The traffic file at ``path`` with its ``tiny`` sizes applied."""
    t = load(path)
    if "tiny" not in t:
        raise ValueError(f"{path} has no 'tiny' object: the CPU tests "
                         "cannot shrink its traffic")
    t.update(t.pop("tiny"))
    t["check"]["calls"] = 2
    if "scenarios" in t["check"]:
        t["check"]["scenarios"] = 6
    return t


def traffic(name: str) -> dict:
    return shrink(BENCH / "traffic" / f"{name}.json")


def load_shrunk(path: pathlib.Path) -> dict:
    """``run.load_json`` with every traffic file shrunk."""
    path = pathlib.Path(path)
    if path.parent.name == "traffic":
        return shrink(path)
    return load(path)
