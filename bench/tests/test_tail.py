"""The N+k what-if on a tail-tolerant cluster (``kinds/whatif_n1.py``,
``reference/tail.py``, the ``repro.plan.faults`` metrics).

The reference reduces to the plain cluster's where nothing is switched
on, a quorum never cut degrades nothing, failover splits evenly, and the
fleet search finds the smallest fleet that holds; the kind checks its
``fault`` keys; the two metrics of the N+k check read a synthesized window
by hand and nothing where the span is missing; and a planner that leaves
the hedge out comes out not correct.
"""

import dataclasses
import math

import numpy as np
import pytest

import calls as calls_mod
import tiny
from reference import cluster, deployment, draws, tail
from repro.core import simulator
from test_faults import run_cell
from test_program_metrics import GRID_HOST, MS, WHATIF_HOST, read, window

SEED = 2**31 + 12345
CELL = "t6n1.whatif"


def _tail_row(rate, n, key_seed=5):
    """A t6 scenario row and its draws, for ``tail.simulate``."""
    prm = deployment.scenario_params(tiny.config(tiny.cell("t6.whatif")))
    key = draws.key_of(key_seed)
    chunk = cluster.chunk_size(n)

    def d(c):
        return draws.chunk_draws(
            key, c, [0], n_scen=1, chunk=chunk, p=prm["p"],
            lam32=np.asarray([rate], np.float32),
            prm32=draws.params32(prm))

    def h(c):
        return tail.hedge_draws(key, c, chunk=chunk, p=prm["p"], attempts=1)

    row = {k: np.asarray([v]) if k != "p" else v for k, v in prm.items()}
    return d, h, row


@pytest.mark.parametrize("r", [1, 3])
def test_tail_with_nothing_on_is_the_plain_cluster(r):
    """No replica down, no timeout and no hedge: ``tail.simulate`` is
    ``cluster.simulate`` under round robin, bit for bit."""
    d, h, row = _tail_row(150.0, 3000)
    lam = np.asarray([150.0])
    plain = cluster.simulate(d, lam=lam, prm=row, r=r,
                             routing="round_robin", cache=None,
                             n_queries=3000, quantile_q=0.95)
    ours = tail.simulate(d, h, lam=lam, prm=row, r=r, n_queries=3000)
    assert ours["mean"] == plain["mean"][0]
    assert ours["quantile"] == plain["quantile"][0]
    assert ours["degraded"] == 0.0


@pytest.mark.parametrize("quorum_k", [95, 100])
def test_tail_quorum_never_cut_is_never_degraded(quorum_k):
    """A timeout no query reaches, or a quorum of all p, never answers on
    a partial quorum: the degraded share is 0 and the answers are the
    full join's."""
    d, h, row = _tail_row(200.0, 3000)
    lam = np.asarray([200.0])
    full = tail.simulate(d, h, lam=lam, prm=row, r=4, n_queries=3000)
    timeout = 1e9 if quorum_k < 100 else 0.05
    cut = tail.simulate(d, h, lam=lam, prm=row, r=4, n_queries=3000,
                        timeout=timeout, quorum_k=quorum_k)
    assert cut["degraded"] == 0.0
    assert cut["mean"] == full["mean"]


def test_tail_failover_splits_evenly_and_quorum_degrades():
    """Replica 0 down: the survivors share the load as the fault-free
    fleet one smaller does (the same draws, the same queues); a quorum
    past a short timeout degrades some answers and shortens the tail, and
    a hedge can only take answers earlier and whole."""
    d, h, row = _tail_row(200.0, 3000)
    lam = np.asarray([200.0])
    down = tail.simulate(d, h, lam=lam, prm=row, r=4, down=(0,),
                         n_queries=3000)
    three = tail.simulate(d, h, lam=lam, prm=row, r=3, n_queries=3000)
    np.testing.assert_array_equal(down["fork_to_join"],
                                  three["fork_to_join"])
    cut = tail.simulate(d, h, lam=lam, prm=row, r=4, down=(0,),
                        n_queries=3000, timeout=0.15, quorum_k=95)
    assert 0.0 < cut["degraded"] < 1.0
    assert cut["fork_to_join"].max() < down["fork_to_join"].max()
    hedged = tail.simulate(d, h, lam=lam, prm=row, r=4, down=(0,),
                           n_queries=3000, timeout=0.15, quorum_k=95,
                           delays=[0.12])
    assert np.all(hedged["fork_to_join"] <= cut["fork_to_join"])
    assert hedged["degraded"] <= cut["degraded"]


@pytest.mark.parametrize("need", [4, 5, 9, 30, 255, 256, 400])
def test_fleet_search_finds_the_smallest_in_log_steps(need):
    """``tail.grow`` from 4 replicas: the smallest fleet whose faulted
    p95 meets the SLO (the cap where none below it does), trying each
    fleet once and O(log n) of them."""
    tried = []

    def faulted_run(n):
        tried.append(n)
        return {"quantile": 0.2 if n >= need else 0.4}

    n, run = tail.grow(faulted_run, 0.3, 4)
    assert n == min(need, tail.REPLICA_CAP)
    assert run["quantile"] == (0.2 if need <= tail.REPLICA_CAP else 0.4)
    assert len(tried) == len(set(tried))
    assert len(tried) <= 2 * math.ceil(math.log2(tail.REPLICA_CAP)) + 1


def test_unread_fault_key_is_refused():
    """The N+k kind checks its ``fault`` group's keys itself."""
    cell = tiny.cell(CELL)
    config, traffic = tiny.config(cell), tiny.traffic(cell["traffic"])
    config["fault"]["retry_budget"] = 1
    with pytest.raises(ValueError, match="retry_budget"):
        calls_mod.make(config, traffic, 1, SEED)


NK_HOST = WHATIF_HOST + [
    ["repro.plan.faults", 250 * MS, 258 * MS, "python"],  # 8 ms
    ["repro.plan.faults.read", 252 * MS, 257 * MS, "python"],
]
NK = ("faults_ms_per_call.whatif", "faulted_sims_per_call.whatif")
COUNTERS = {"/repro/plan/size_traced": 6, "/repro/stream/traced": 6,
            "/repro/plan/faulted_sim": 6}


def test_nk_check_metrics_by_hand():
    """The N+k check: its span's time per traced call, and its faulted
    simulations per call of the window (where one call in three tried a
    second fleet)."""
    w = window("whatif_n1", NK_HOST, family="whatif",
               counters={"/repro/plan/faulted_sim": 8})
    assert read("faults_ms_per_call.whatif", w) == pytest.approx(8 / 2)
    assert read("faulted_sims_per_call.whatif", w) == pytest.approx(8 / 6)


@pytest.mark.parametrize("name", NK)
def test_nk_metrics_read_nothing_without_the_check(name):
    """A what-if without ``survive_faults`` has no ``repro.plan.faults``
    span, a grid call no what-if spans, an untraced run no trace: the
    metric reads nothing in each."""
    assert read(name, window("whatif", WHATIF_HOST,
                             counters=COUNTERS)) is None
    assert read(name, window("grid", GRID_HOST, counters=COUNTERS)) is None
    untraced = window("whatif_n1", [], family="whatif")
    untraced.trace = None
    assert read(name, untraced) is None


def test_hedge_left_out_is_caught(monkeypatch, tmp_path):
    """A planner that simulates the spec without its hedge comes out not
    correct: the hedge answers some of the faulted run's stragglers
    whole, which moves the degraded share past its limit."""
    def no_hedge(fn):
        def wrapper(*a, cluster=None, **kw):
            if cluster is not None and cluster.fault is not None:
                cluster = dataclasses.replace(cluster, fault=(
                    dataclasses.replace(cluster.fault,
                                        hedge_after_seconds=None)))
            return fn(*a, cluster=cluster, **kw)
        return wrapper

    sound = run_cell(CELL, monkeypatch, tmp_path)
    assert sound["correct"], sound["check"]
    monkeypatch.setattr(simulator, "simulate_fork_join",
                        no_hedge(simulator.simulate_fork_join))
    out = run_cell(CELL, monkeypatch, tmp_path)
    assert not out["correct"], out["check"]
