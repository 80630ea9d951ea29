"""The reference's answer to a planning-grid call (``plan_over_grid``).

The grid's scenarios are laid out in the planner's order (lam, p, cpu,
disk, hit, r); each (p, r) pair is one dispatch with its own key, and on
n devices each device simulates its block of the dispatch's scenarios
from its own key (``draws.py``).
"""

from __future__ import annotations

import numpy as np

from . import cluster, deployment, draws


def lam_axis(spec) -> np.ndarray:
    """A rate axis: a list of rates or ``{"linspace": [lo, hi, n]}``."""
    if isinstance(spec, dict):
        lo, hi, n = spec["linspace"]
        return np.linspace(float(lo), float(hi), int(n))
    return np.asarray(spec, np.float64)


def cells(config: dict, traffic: dict):
    """(shape, lam, params, r) of every grid scenario, flattened in the
    planner's order."""
    lam = lam_axis(traffic["lam"])
    axes = (lam, [config["p"]], traffic["cpu"], traffic["disk"],
            traffic["hit"], traffic["r"])
    shape = tuple(len(a) for a in axes)
    g = np.meshgrid(*[np.asarray(a, np.float64) for a in axes],
                    indexing="ij")
    flat = [x.reshape(-1) for x in g]
    prm = deployment.grid_params(config, config["scenario"]["memory"],
                                 flat[2], flat[3], flat[4])
    return shape, flat[0], prm, flat[5]


def simulate(config: dict, traffic: dict, chips: int, key_seed: int, idx,
             dtype=np.float64) -> dict:
    """Reference mean and quantile of grid scenarios ``idx`` of one call."""
    shape, lam, prm, r_of = cells(config, traffic)
    n_l, n_p, n_c, n_d, n_h, n_r = shape
    n_batch = n_l * n_c * n_d * n_h
    n_dev = max(int(chips), 1)
    if n_batch % n_dev:
        raise ValueError("the reference assumes equal device blocks")
    per = n_batch // n_dev
    d_keys = draws.dispatch_keys(draws.key_of(key_seed), n_p * n_r)
    n = int(traffic["n_queries"])
    cache = deployment.result_cache(config)
    idx = np.asarray(idx)
    out = {"mean": np.zeros(len(idx)), "quantile": np.zeros(len(idx))}
    l, ip, ic, id_, ih, ir = np.unravel_index(idx, shape)
    batch = ((l * n_c + ic) * n_d + id_) * n_h + ih
    dispatch = ip * n_r + ir
    hit_r = None if cache is None else cache[0]
    for disp in np.unique(dispatch):
        for dev in np.unique(batch[dispatch == disp] // per):
            sel = np.flatnonzero((dispatch == disp) & (batch // per == dev))
            key = d_keys[disp]
            if n_dev > 1:
                key = draws.device_keys(key, n_dev)[dev]
            # every scenario of this device block, as flat grid indices
            bl, bc, bd, bh = np.unravel_index(
                np.arange(dev * per, (dev + 1) * per), (n_l, n_c, n_d, n_h))
            blk = np.ravel_multi_index(
                (bl, np.full_like(bl, ip[sel[0]]), bc, bd, bh,
                 np.full_like(bl, ir[sel[0]])), shape)
            rows = batch[sel] - dev * per
            k_rows = idx[sel]

            def d(c, key=key, rows=rows, blk=blk):
                return draws.chunk_draws(
                    key, c, rows, n_scen=per, chunk=cluster.chunk_size(n),
                    p=int(config["p"]), lam32=lam[blk].astype(np.float32),
                    prm32=draws.params32(prm, blk), hit_r=hit_r)

            res = cluster.simulate(
                d, lam=lam[k_rows],
                prm={k: (np.asarray(v)[k_rows] if np.ndim(v) else v)
                     for k, v in prm.items()},
                r=int(r_of[k_rows[0]]), routing=config["routing"],
                cache=cache, n_queries=n,
                quantile_q=float(traffic["quantile"]), dtype=dtype)
            out["mean"][sel] = res["mean"]
            out["quantile"][sel] = res["quantile"]
    return out
