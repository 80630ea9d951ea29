"""The planner's random numbers, drawn again from the same keys.

A simulated answer is a function of its key, so the reference must see the
same random numbers as the planner, or it can only be compared in
distribution.  The planner documents its draw plan, and this module draws
it again with ``jax.random`` (the only JAX in the reference):

* chunk ``c`` of ``CHUNK`` queries (fewer where a run simulates fewer)
  takes ``fold_in(key, c)``, split in
  three: unit-rate gaps (S, chunk), unit-mean broker draws (S, chunk) and
  unit-mean server draws (S, p, chunk);
* the result cache takes ``fold_in(fold_in(key, c), 0xCA8E)``, split in
  two: a Bernoulli(hit_r) hit coin and unit-mean cache draws (S, chunk);
* a grid dispatches one batch per (p, r), in the grid's order, each with
  its own key from ``split(call_key, n_dispatches)``; on a mesh of n
  devices each device runs its block of the batch's scenarios from its
  own ``split(dispatch_key, n)``.

Only the rows of the scenarios under comparison come back to the host.
The JSQ dispatcher decides on carried float32 work, so this module also
hands back the gaps and service times as the planner forms them in
float32 (``gap32``, ``svc32``), for the reference's float32 JSQ tracker.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CACHE_SALT = 0xCA8E
# the planner's chunk of queries (``simulator.DEFAULT_CHUNK``): its entry
# points are called without a chunk size, so this is part of the draw plan
CHUNK = 4096


@functools.partial(jax.jit, static_argnames=("n_scen", "chunk", "p",
                                              "cache"))
def _chunk(key, c, rows, rate32, prm32, hit_r, *, n_scen, chunk, p, cache):
    kc = jax.random.fold_in(key, c)
    k_arr, k_brk, k_srv = jax.random.split(kc, 3)
    u_gap = jax.random.exponential(k_arr, (n_scen, chunk))
    u_brk = jax.random.exponential(k_brk, (n_scen, chunk))
    u_srv = jax.random.exponential(k_srv, (n_scen, p, chunk))
    hit, s_hit, s_miss, s_disk = prm32
    s_mean32 = hit * s_hit + (1.0 - hit) * (s_miss + s_disk)
    out = {"u_gap": u_gap[rows], "u_brk": u_brk[rows], "u_srv": u_srv[rows],
           "gap32": (u_gap / rate32[:, None])[rows],
           "svc32": (u_srv * s_mean32[:, None, None])[rows]}
    if cache:
        kh, ks = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, c), CACHE_SALT))
        hit = jax.random.bernoulli(
            kh, jnp.broadcast_to(hit_r, (n_scen, chunk)))
        out["is_hit"] = hit[rows]
        out["u_cache"] = jax.random.exponential(ks, (n_scen, chunk))[rows]
    return out


def chunk_draws(key, c: int, rows, *, n_scen: int, chunk: int, p: int,
                lam32, prm32, hit_r=None) -> dict:
    """Host copies of chunk ``c``'s draws for batch rows ``rows``.

    ``lam32`` are the batch's (n_scen,) rates and ``prm32`` its (hit,
    s_hit, s_miss, s_disk), each (n_scen,), in float32 as the planner holds
    them; they enter only ``gap32``/``svc32``.
    """
    out = _chunk(key, jnp.int32(c), jnp.asarray(rows, jnp.int32),
                 jnp.asarray(lam32, jnp.float32),
                 tuple(jnp.asarray(x, jnp.float32) for x in prm32),
                 jnp.float32(0.0 if hit_r is None else hit_r),
                 n_scen=n_scen, chunk=chunk, p=p, cache=hit_r is not None)
    return {k: np.asarray(v) for k, v in out.items()}


def dispatch_keys(call_key, n_dispatches: int):
    """One key per (p, r) dispatch of a grid call, in the grid's order."""
    return jax.random.split(call_key, n_dispatches)


def device_keys(dispatch_key, n_devices: int):
    """One key per device block of a dispatch sharded over ``n_devices``."""
    return jax.random.split(dispatch_key, n_devices)


def key_of(seed: int):
    """The planner's key for an integer seed."""
    return jax.random.PRNGKey(seed)


def params32(prm: dict, idx=slice(None)) -> tuple:
    """(hit, s_hit, s_miss, s_disk) of scenarios ``idx`` in float32, as
    the planner holds them."""
    shape = np.shape(np.atleast_1d(prm["hit"]))
    return tuple(np.asarray(np.broadcast_to(prm[k], shape)[idx], np.float32)
                 for k in ("hit", "s_hit", "s_miss", "s_disk"))
