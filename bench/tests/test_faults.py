"""A whole benchmark run on the CPU, past the look for a chip, with the
timed path broken underneath: ``correct`` has to come out false.

The faults a planning cell can have: an answer altered where it is
produced; half of the batch left out and the mean taken over the rest;
and, for a grid sharded over four chips, the exchange between chips left
out.  Every one-chip cell of ``BENCHMARK.json`` is run sound and with
the faults of its kind's family; a sound run comes out correct.  Last, a
new kind of call in new files alone reports its family's metrics.
"""

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import calls as calls_mod
import check
import run as bench_run
import tiny
from repro.core import simulator


class FakeChip:
    """Stands in for the TPU device the harness looks for."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def run_cell(name, monkeypatch, tmp_path, cell=None):
    monkeypatch.setattr(bench_run, "load_json", tiny.load_shrunk)
    monkeypatch.setattr(bench_run, "CACHE_DIR", tmp_path / "cache")
    args = argparse.Namespace(workload=name, seed=2**31 + 99, seconds=0.5,
                              trace=0)
    cell = cell or tiny.cell(name)
    return bench_run.run(args, tiny.bench(), cell,
                         [FakeChip()] * cell["chips"])


def altered(fn):
    """The simulator's answer nudged by 1% where it is produced."""
    def wrapper(*a, **kw):
        res = fn(*a, **kw)
        return dataclasses.replace(res, sum_response=res.sum_response * 1.01)
    return wrapper


def half_batch_grid(fn):
    """Only the first half of a grid dispatch's scenarios simulated; the
    rest take their answers."""
    def wrapper(key, lam, params, n_queries, **kw):
        half = lam.rates.shape[0] // 2
        res = fn(key, dataclasses.replace(lam, rates=lam.rates[:half]),
                 jax.tree_util.tree_map(lambda x: x[:half], params),
                 n_queries, **kw)
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x, x], axis=0), res)
    return wrapper


def half_batch_whatif(fn):
    """Only the first half of the what-if's queries simulated."""
    def wrapper(key, lam, n_queries, *a, **kw):
        return fn(key, lam, n_queries // 2, *a, **kw)
    return wrapper


# the simulator entry each family's calls go through, and its faults
FAULTS = {"whatif": ("simulate_fork_join", (altered, half_batch_whatif)),
          "grid": ("simulate_fork_join_batch", (altered, half_batch_grid))}
ONE_CHIP = [w for w in tiny.bench()["workloads"] if w["chips"] == 1]


def fault_cases():
    """(cell, simulator entry, fault) for every one-chip cell and each
    fault of its family; a family with none known is a case that fails."""
    for cell in ONE_CHIP:
        family = tiny.family(cell)
        target, faults = FAULTS.get(family, (None, (None,)))
        for fault in faults:
            yield pytest.param(
                cell["name"], target, fault,
                id=f"{cell['name']}-{getattr(fault, '__name__', family)}")


@pytest.mark.parametrize("name", [w["name"] for w in ONE_CHIP])
def test_sound_run_is_correct(name, monkeypatch, tmp_path):
    out = run_cell(name, monkeypatch, tmp_path)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert {"setup_s"} < set(out["metrics"])
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("name,target,fault", list(fault_cases()))
def test_fault_is_caught(name, target, fault, monkeypatch, tmp_path):
    assert fault is not None, f"no faults known for the family of {name}"
    monkeypatch.setattr(simulator, target,
                        fault(getattr(simulator, target)))
    out = run_cell(name, monkeypatch, tmp_path)
    assert not out["correct"], out["check"]


SHARDED = r"""
import sys
sys.path[:0] = {paths!r}
import jax, jax.numpy as jnp, pytest
import run as bench_run, tiny, test_faults
from repro.core import sweep

def no_exchange(fn):
    # every chip's block replaced by chip 0's: the shards never gathered
    def wrapper(run, mesh, key, proc, params):
        res = fn(run, mesh, key, proc, params)
        n = mesh.devices.size
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x[:x.shape[0] // n]] * n, axis=0), res)
    return wrapper

mp = pytest.MonkeyPatch()
tmp = __import__("pathlib").Path({tmp!r})
cell = tiny.sharded_grid_cell()
out = test_faults.run_cell("t6.grid", mp, tmp, cell)
print("SOUND", out["correct"])
mp.setattr(sweep, "_sharded_batch", no_exchange(sweep._sharded_batch))
out = test_faults.run_cell("t6.grid", mp, tmp, cell)
print("FAULT", out["correct"])
"""


def test_sharded_exchange_left_out_is_caught(tmp_path):
    script = tmp_path / "sharded_fault.py"
    script.write_text(SHARDED.format(
        paths=[str(tiny.BENCH), str(tiny.ROOT / "src"),
               str(tiny.BENCH / "tests")], tmp=str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SOUND True" in out.stdout and "FAULT False" in out.stdout, \
        out.stdout


def test_traced_run_reports_the_layers(monkeypatch, tmp_path):
    """A ``--trace 1`` run's line: per-layer metrics, busy and window
    seconds, a breakdown of at most 10 entries each.  The CPU's trace holds
    no TPU plane, so the recorded chip trace stands in for it."""
    import json
    import trace_reduce
    recorded = json.loads((tiny.BENCH / "tests" / "data"
                           / "whatif_trace.json").read_text())
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    monkeypatch.setattr(bench_run, "load_json", tiny.load_shrunk)
    monkeypatch.setattr(bench_run, "CACHE_DIR", tmp_path / "cache")
    args = argparse.Namespace(workload="s6jsq.whatif", seed=2**31 + 7,
                              seconds=3.0, trace=1)
    out = bench_run.run(args, tiny.bench(), tiny.cell("s6jsq.whatif"),
                        [FakeChip()])
    assert out["correct"], out["check"]
    assert {"launches_per_call.whatif", "traces_per_call.whatif",
            "device_idle.whatif", "scan_kernel_roofline.whatif"} == set(
                out["metrics"])
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    for entries in out["breakdown"].values():
        assert 0 < len(entries) <= 10
    assert list(out)[-1] == "check"


def test_a_new_kind_is_new_files_alone(monkeypatch, tmp_path):
    """The promise of ``calls.py``: a new kind of call of a known family
    needs only new files (here a copy of the what-if generator, a traffic
    file with its ``tiny`` object and limits) to report the family's
    end-to-end metrics, and comes out correct; a kind without a
    ``FAMILY``, or of a family no end-to-end metric reads, is refused."""
    kinds = tmp_path / "kinds"
    shutil.copytree(tiny.BENCH / "kinds", kinds,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(kinds / "whatif.py", kinds / "whatif_copy.py")
    for sub in ("traffic", "limits"):
        (tmp_path / sub).mkdir()
    shutil.copy(tiny.BENCH / "peaks.json", tmp_path)
    traffic = tiny.load(tiny.BENCH / "traffic" / "whatif_50to300.json")
    traffic["kind"] = "whatif_copy"
    (tmp_path / "traffic" / "whatif_copy.json").write_text(
        json.dumps(traffic))
    shutil.copy(tiny.BENCH / "limits" / "t6.whatif.json",
                tmp_path / "limits" / "t6.whatif_copy.json")
    monkeypatch.setattr(calls_mod, "KINDS", kinds)
    monkeypatch.setattr(bench_run, "BENCH", tmp_path)
    monkeypatch.setattr(check, "HERE", tmp_path)

    bench = tiny.bench()
    cell = dict(tiny.cell("t6.whatif"), name="t6.whatif_copy",
                traffic="whatif_copy")
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if "t6.whatif" in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    monkeypatch.setattr(bench_run, "load_json", tiny.load_shrunk)
    monkeypatch.setattr(bench_run, "CACHE_DIR", tmp_path / "cache")
    args = argparse.Namespace(workload=cell["name"], seed=2**31 + 5,
                              seconds=0.5, trace=0)
    out = bench_run.run(args, bench, cell, [FakeChip()])
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"whatif_mean_s", "whatif_p90_s",
                                   "setup_s"}

    source = (kinds / "whatif.py").read_text()
    traffic = tiny.shrink(tmp_path / "traffic" / "whatif_copy.json")
    for kind, family, refusal in [("nofamily", "", "declares no FAMILY"),
                                  ("unread", 'FAMILY = "nosuch"\n',
                                   "reads its family 'nosuch'")]:
        (kinds / f"{kind}.py").write_text(
            source.replace('FAMILY = "whatif"\n', family))
        with pytest.raises(ValueError, match=f"'{kind}'.*{refusal}"):
            calls_mod.make(tiny.config(cell), dict(traffic, kind=kind), 1,
                           7)
