"""The calls of a cell: its traffic file, read by the generator of its kind.

A traffic mix is a data file, ``traffic/<mix>.json``, whose ``kind``
names the generator that reads it, ``kinds/<kind>.py``, found by name as
the metric readers are.  A generator module declares its ``FAMILY``, the
traffic keys it reads (``TRAFFIC``, and ``CHECK`` in the ``check`` group)
and the configuration keys (``CONFIG``), and gives ``Calls(config,
traffic, chips, seed)``:

* ``work``: simulated queries per call;
* ``warm()``: one call of every shape the window uses;
* ``call()``: one timed call, a :class:`Record` of what it was asked and
  what it answered;
* ``failed(record)``: whether the call gave no answer to judge;
* ``numbers(records, seed, latencies, control=False)``: the numbers
  compared with the plain reference after the window (``control=True``
  puts the reference in bfloat16 in the program's place).

The family says what a window of the kind's calls is, and so which
metrics read it: ``"whatif"``, whole planning calls, each one answer a
planner waits for (the mean and 90th percentile of call latency);
``"grid"``, calls whose ``work`` is scenarios x queries, so that
simulated queries per second is defined.  A metric reader that holds for
any call of a family declares it as its own ``FAMILY`` and reads nothing
in a window of another; a kind whose family no end-to-end metric of
``BENCHMARK.json`` reads is refused, since its cells could report only
their set-up time.

A key that the generator does not read is refused, in the traffic file
and in the configuration: a setting the run would drop is an error, not
a different cell under the same name.  A traffic file's ``tiny`` object
holds the sizes the CPU tests shrink it to; no timed run reads it.  A
new mix of a known kind is a data file; a new kind of call of a known
family is a new ``kinds/<kind>.py`` (with its reference under
``reference/``), and neither edits a file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

import numpy as np

from reference import deployment

BENCH = pathlib.Path(__file__).resolve().parent
KINDS = BENCH / "kinds"
METRICS = BENCH / "metrics"
# keys of a traffic file that every kind reads or that only describe it
TRAFFIC_COMMON = {"kind", "why", "tiny"}


def key_seeds(seed: int):
    """Fresh 31-bit key seeds for the calls of a run, from its seed."""
    rng = np.random.default_rng([int(seed), 0])
    while True:
        yield int(rng.integers(0, 2**31 - 1))


@dataclasses.dataclass
class Record:
    """One call: what it was asked and what it answered."""

    key_seed: int
    rate: float = None
    answer: object = None


def load_module(path: pathlib.Path, prefix: str):
    """The Python file ``path``, loaded as a module of its own."""
    name = prefix + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    """The generator of a traffic kind, ``kinds/<kind>.py``."""
    path = KINDS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no generator {path.name} for traffic kind "
                         f"{kind!r}")
    return load_module(path, "bench_kind_")


def families_read() -> set:
    """The families that some end-to-end metric of ``BENCHMARK.json``
    reads: the ``FAMILY`` of its reader.  ``setup_s``, which every cell
    reports, declares none."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    readers = (load_module(METRICS / f"{m['name']}.py", "bench_metric_")
               for m in bench["end_to_end"])
    return {getattr(r, "FAMILY", None) for r in readers} - {None}


def make(config: dict, traffic: dict, chips: int, seed: int):
    """The calls of a cell, once its files hold only keys they use and
    its kind declares a family that an end-to-end metric reads."""
    kind = kind_module(traffic["kind"])
    where = f"traffic kind {traffic['kind']!r}"
    family = getattr(kind, "FAMILY", None)
    if family is None:
        raise ValueError(f"{where}: kinds/{traffic['kind']}.py declares no "
                         "FAMILY")
    if family not in families_read():
        raise ValueError(f"{where}: no end-to-end metric of BENCHMARK.json "
                         f"reads its family {family!r}")
    deployment.check_keys(traffic, kind.TRAFFIC | TRAFFIC_COMMON, where,
                          required=kind.TRAFFIC | {"kind"})
    deployment.check_keys(traffic["check"], kind.CHECK, f"{where} check",
                          required=kind.CHECK)
    deployment.validate(config, kind.CONFIG)
    calls = kind.Calls(config, traffic, chips, seed)
    calls.kind = traffic["kind"]
    calls.family = family
    return calls
