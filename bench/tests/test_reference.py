"""The plain reference against the planner, at tiny sizes on the CPU.

Each traffic mix's calls agree with ``reference/`` under every number the
benchmark compares; the control (the reference's simulation in bfloat16)
fails them; the frontier arithmetic agrees with ``chip_smoke.py``'s.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import calls as calls_mod
import check
import tiny
from reference import analytic, cluster, frontier

grid_kind = calls_mod.kind_module("grid")

ONE_CHIP = [w["name"] for w in tiny.bench()["workloads"] if w["chips"] == 1]
SEED = 2**31 + 12345


def _calls_and_records(name, n_calls=3):
    cell = tiny.cell(name)
    calls = calls_mod.make(tiny.config(cell), tiny.traffic(cell["traffic"]),
                           1, SEED)
    records = [calls.call() for _ in range(n_calls)]
    return cell, calls, records


@pytest.mark.parametrize("name", ONE_CHIP)
def test_calls_agree_with_reference(name):
    cell, calls, records = _calls_and_records(name)
    numbers = calls.numbers(records, SEED, [1.0] * len(records))
    ok, table = check.judge(numbers, check.limits(name))
    assert ok, table


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_fails(name):
    cell, calls, records = _calls_and_records(name, n_calls=2)
    numbers = calls.numbers(records, SEED, [1.0, 1.0], control=True)
    ok, table = check.judge(numbers, check.limits(name))
    assert not ok, table
    assert max(numbers["mean_rel"], numbers["p95_rel"]) > 0.1


SHARDED = """
import sys
sys.path[:0] = {paths!r}
import calls, check, tiny
cell = tiny.sharded_grid_cell()
g = calls.make(tiny.config(cell), tiny.traffic(cell["traffic"]), 4, 7)
recs = [g.call() for _ in range(2)]
print(check.judge(g.numbers(recs, 7), check.limits("t6.grid")))
"""


def test_sharded_grid_agrees_with_reference(tmp_path):
    """The sharded grid's draw plan, on four virtual CPU devices."""
    script = tmp_path / "sharded.py"
    script.write_text(SHARDED.format(paths=[
        str(tiny.BENCH), str(tiny.ROOT / "src"), str(tiny.BENCH / "tests")]))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1].startswith("(True,"), \
        out.stdout


def test_fcfs_unrolled_equals_recurrence():
    rng = np.random.default_rng(0)
    a = np.cumsum(rng.exponential(1.0, 500))
    s = rng.exponential(0.9, 500)
    c, want = -np.inf, []
    for ai, si in zip(a, s):
        c = max(c, ai) + si
        want.append(c)
    np.testing.assert_allclose(cluster.fcfs(a, s), want, rtol=1e-12)


def test_analytic_matches_paper_section6():
    # Section 6: memory+cpus+disks, p = 100, 200 qps at 300 ms -> 4 x 100
    cfg = tiny.config(tiny.cell("t6.whatif"))
    from reference import deployment
    plan = analytic.plan(deployment.scenario_params(cfg), 200.0, 0.300)
    assert plan["n_replicas"] == 4
    assert analytic.harmonic(100) == pytest.approx(5.187377517639621)


def test_frontier_agrees_with_chip_smoke():
    sys.path.insert(0, str(tiny.ROOT))
    import chip_smoke
    from repro.core import sweep
    grid = sweep.SweepGrid.build(lam=[10.0, 50.0, 90.0], cpu=[1.0, 2.0],
                                 disk=[1.0, 3.0], hit=[0.02, 0.18],
                                 p=[100.0], r=[1.0, 2.0], memory=4)
    rng = np.random.default_rng(3)
    surf = rng.uniform(0.1, 0.5, grid.shape).astype(np.float32)

    class Res:
        def quantile(self, q):
            return surf

    res = Res()
    res.grid = grid
    fr = sweep.extract_frontier(res, 0.300, surface=surf)
    chip_smoke.check_frontier(res, fr, grid)      # raises on a mismatch
    costs = frontier.cell_costs([100.0], [1.0, 2.0], [1.0, 3.0],
                                [0.02, 0.18], [1.0, 2.0])
    ours = frontier.frontier(surf.reshape(3, -1), costs, 0.300)
    answer = {"quantile": surf.reshape(3, -1),
              "frontier": grid_kind.fr_arrays(fr)}
    traffic = {"cpu": [1.0, 2.0], "disk": [1.0, 3.0], "hit": [0.02, 0.18],
               "r": [1.0, 2.0], "slo_s": 0.300}
    assert grid_kind.frontier_mismatch({"p": 100}, traffic, answer) == 0
    np.testing.assert_array_equal(ours["feasible"],
                                  np.asarray(fr.feasible))
    np.testing.assert_allclose(ours["cost"][ours["feasible"]],
                               np.asarray(fr.cost)[ours["feasible"]],
                               rtol=1e-6)
    bad = dict(answer, frontier=dict(answer["frontier"],
                                     cost=np.asarray(fr.cost) * 1.01))
    assert grid_kind.frontier_mismatch({"p": 100}, traffic, bad) > 0


@pytest.mark.parametrize("group,key", [
    ("traffic", "draw_chunk"), ("traffic", "profile"),
    ("check", "seconds"), ("config", "fault"),
    ("result_cache", "ttl_s"), ("scenario", "memory_gb"),
])
def test_unread_key_is_refused(group, key):
    """A setting that no generator reads fails the run, not silently."""
    cell = tiny.cell("s6jsq.whatif")
    config, traffic = tiny.config(cell), tiny.traffic(cell["traffic"])
    target = {"traffic": traffic, "check": traffic["check"],
              "config": config, "result_cache": config["result_cache"],
              "scenario": config["scenario"]}[group]
    target[key] = 1
    with pytest.raises(ValueError, match=key):
        calls_mod.make(config, traffic, 1, SEED)


@pytest.mark.parametrize("kind,routing", [("nosuch", None),
                                          ("whatif", "random")])
def test_unknown_kind_or_routing_is_refused(kind, routing):
    cell = tiny.cell("t6.whatif")
    config, traffic = tiny.config(cell), tiny.traffic(cell["traffic"])
    traffic["kind"] = kind
    if routing:
        config["routing"] = routing
    with pytest.raises(ValueError, match=routing or kind):
        calls_mod.make(config, traffic, 1, SEED)


def test_traffic_without_tiny_is_refused_by_name(tmp_path):
    """A traffic file the CPU tests cannot shrink fails its tests with a
    message that names the file and the missing ``tiny`` object."""
    path = tmp_path / "mix.json"
    path.write_text(json.dumps({"kind": "whatif", "check": {"calls": 8}}))
    with pytest.raises(ValueError, match=r"mix\.json has no 'tiny' object"):
        tiny.shrink(path)
