"""The calls of a cell: its traffic file, read by the generator of its kind.

A traffic mix is a data file, ``traffic/<mix>.json``, whose ``kind``
names the generator that reads it, ``kinds/<kind>.py``, found by name as
the metric readers are.  A generator module declares the traffic keys it
reads (``TRAFFIC``, and ``CHECK`` in the ``check`` group) and the
configuration keys (``CONFIG``), and gives ``Calls(config, traffic,
chips, seed)``:

* ``work``: simulated queries per call;
* ``warm()``: one call of every shape the window uses;
* ``call()``: one timed call, a :class:`Record` of what it was asked and
  what it answered;
* ``failed(record)``: whether the call gave no answer to judge;
* ``numbers(records, seed, latencies, control=False)``: the numbers
  compared with the plain reference after the window (``control=True``
  puts the reference in bfloat16 in the program's place).

A key that the generator does not read is refused, in the traffic file
and in the configuration: a setting the run would drop is an error, not
a different cell under the same name.  A new mix of a known kind is a
data file; a new kind of call is a new ``kinds/<kind>.py`` (with its
reference under ``reference/``), and neither edits a file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import numpy as np

from reference import deployment

KINDS = pathlib.Path(__file__).resolve().parent / "kinds"
# keys of a traffic file that every kind reads or that only describe it
TRAFFIC_COMMON = {"kind", "why"}


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


def kind_module(kind: str):
    """The generator of a traffic kind, ``kinds/<kind>.py``."""
    path = KINDS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"no generator {path.name} for traffic kind "
                         f"{kind!r}")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(config: dict, traffic: dict, chips: int, seed: int):
    """The calls of a cell, once its files hold only keys they use."""
    kind = kind_module(traffic["kind"])
    where = f"traffic kind {traffic['kind']!r}"
    deployment.check_keys(traffic, kind.TRAFFIC | TRAFFIC_COMMON, where,
                          required=kind.TRAFFIC | {"kind"})
    deployment.check_keys(traffic["check"], kind.CHECK, f"{where} check",
                          required=kind.CHECK)
    deployment.validate(config, kind.CONFIG)
    calls = kind.Calls(config, traffic, chips, seed)
    calls.kind = traffic["kind"]
    return calls
