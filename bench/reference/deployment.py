"""Server parameters of a deployment, read from its configuration file.

A configuration states the paper's measured times in milliseconds (Table
6 or Table 5), the broker's linear fit in p, and the hardware scenario;
this module turns them into the seconds the model and the simulation use
(paper Section 6: a k-times faster CPU divides every CPU time, the broker's
included, by k; a k-times faster disk divides the disk time by k).
"""

from __future__ import annotations

import numpy as np

MS = 1e-3
# keys that describe a configuration and change nothing that runs
DESCRIBES = frozenset({"name", "source", "deployment", "pages_per_replica",
                       "precision", "guarantees", "assumed", "reduced",
                       "chips"})
# the keys of the groups this module reads, and the routings the
# reference simulates
GROUPS = {"broker_fit_ms": {"per_server", "fixed"},
          "scenario": {"name", "memory", "cpu", "disk"},
          "result_cache": {"hit_r", "s_cache_ms"}}
TABLE_COLUMN = {"s_hit", "s_miss", "s_disk", "hit"}
ROUTINGS = ("round_robin", "jsq")
# the precision the planner computes in; the control is one step below
PRECISION = "float32"


def check_keys(group: dict, allowed, where: str, required=()) -> None:
    """Refuse a key that nothing reads, and a missing required one: a
    setting the benchmark drops would run another cell than it names."""
    unknown = sorted(set(group) - set(allowed))
    missing = sorted(set(required) - set(group))
    if unknown or missing:
        raise ValueError(f"{where}: unknown keys {unknown}, missing keys "
                         f"{missing}")


def validate(config: dict, reads) -> None:
    """Refuse a configuration with a key outside ``reads`` (what the
    cell's calls read) and :data:`DESCRIBES`, or a group, routing or
    precision that the reference does not model."""
    where = f"configuration {config.get('name')!r}"
    check_keys(config, DESCRIBES | set(reads), where, required=reads)
    for name, keys in GROUPS.items():
        if config.get(name) is not None:
            check_keys(config[name], keys, f"{where} {name}", keys)
    for col, times in config.get("table_ms", {}).items():
        check_keys(times, TABLE_COLUMN, f"{where} table_ms {col}",
                   TABLE_COLUMN)
    if config.get("routing", ROUTINGS[0]) not in ROUTINGS:
        raise ValueError(f"{where}: routing {config['routing']!r} is not "
                         f"one of {ROUTINGS}")
    if config.get("precision") != PRECISION:
        raise ValueError(f"{where}: precision {config.get('precision')!r}"
                         f"; the control is set below {PRECISION}")


def broker_time(config: dict, p, cpu):
    fit = config["broker_fit_ms"]
    return (fit["per_server"] * np.asarray(p, np.float64) + fit["fixed"]) \
        * MS / np.asarray(cpu, np.float64)


def table_column(config: dict, memory) -> dict:
    return config["table_ms"][str(int(memory))]


def scenario_params(config: dict) -> dict:
    """The configuration's own scenario (the what-if's cluster), in seconds."""
    sc = config["scenario"]
    col = table_column(config, sc["memory"])
    cpu, disk = float(sc["cpu"]), float(sc["disk"])
    return {"p": int(config["p"]),
            "s_broker": float(broker_time(config, config["p"], cpu)),
            "s_hit": col["s_hit"] * MS / cpu,
            "s_miss": col["s_miss"] * MS / cpu,
            "s_disk": col["s_disk"] * MS / disk,
            "hit": col["hit"]}


def grid_params(config: dict, memory, cpu, disk, hit) -> dict:
    """Per-scenario parameters of grid cells (arrays broadcast together).

    The grid varies cpu, disk and the disk-cache hit ratio over the times
    measured at ``memory``.
    """
    col = table_column(config, memory)
    cpu = np.asarray(cpu, np.float64)
    disk = np.asarray(disk, np.float64)
    return {"p": int(config["p"]),
            "s_broker": broker_time(config, config["p"], cpu),
            "s_hit": col["s_hit"] * MS / cpu,
            "s_miss": col["s_miss"] * MS / cpu,
            "s_disk": col["s_disk"] * MS / disk,
            "hit": np.asarray(hit, np.float64)}


def result_cache(config: dict):
    """``(hit_r, s_cache seconds)`` of the Eq 8 result cache, or None."""
    rc = config.get("result_cache")
    if rc is None:
        return None
    return (float(rc["hit_r"]), float(rc["s_cache_ms"]) * MS)
