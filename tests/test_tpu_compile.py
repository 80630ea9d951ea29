"""Compile the main path's kernels and stream program for a described TPU.

No chip is attached: the TPU compiler builds for a v5e that is only
described, which catches what the interpreter cannot (tiling, fast-memory
limits, programs too large for the device).  The topology is described
inside a fixture, so importing this file loads no TPU library.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import simulator
from repro.core.cluster import ClusterSpec
from repro.core.faults import FaultSpec
from repro.core.queueing import ServerParams
from repro.kernels.maxplus_scan import ops as mp_ops
from repro.kernels.maxplus_scan.kernel import (maxplus_scan_pallas,
                                               maxplus_segment_scan_pallas)

V5E_HBM_BYTES = 16 * 10**9
# the planning grid's (p, r) dispatch: 1,024 scenarios x 100 servers,
# scanned one 4,096-query chunk at a time
MAIN_ROWS, MAIN_LEN = 1024 * 100, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel,n_in", [
    (maxplus_scan_pallas, 2), (maxplus_segment_scan_pallas, 3)])
def test_kernel_compiles_at_main_width(one_chip, kernel, n_in):
    x = jax.ShapeDtypeStruct((MAIN_ROWS, MAIN_LEN), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(functools.partial(kernel, interpret=False)).lower(
        *(x,) * n_in).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("r", [1, 2])
def test_stream_program_compiles_with_kernel(one_chip, monkeypatch, r):
    # on the CPU backend the wrapper would pick interpret mode; steer it
    # to the compiled kernel, and drop traces made under the CPU choice
    monkeypatch.setattr(mp_ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    n_scen = 16
    vec = jax.ShapeDtypeStruct((n_scen,), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        simulator.simulate_fork_join_batch, n_queries=3 * 1024, p=100,
        impl="pallas", chunk_size=1024, cluster=ClusterSpec(r=r))).lower(
            key, vec, ServerParams(*(vec,) * 6)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each scan kernel is named after its stage, and keeps "maxplus"
    for stage in ("broker", "server"):
        assert f"%maxplus_scan_{stage}" in text
        assert f"stream.{stage}/" in text
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


def test_nk_tail_stream_program_compiles_with_kernel(one_chip, monkeypatch):
    """The N+1 what-if's faulted run: replica 0 of 3 down for the whole run
    (round-robin on the sorted path, so the segmented kernel), the
    95-of-100 quorum's sort and the hedge draws."""
    monkeypatch.setattr(mp_ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    vec = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    fault = FaultSpec(outages=((0, 0.0, math.inf),),
                      broker_timeout_seconds=0.15, quorum_k=95,
                      hedge_after_seconds=0.21)
    compiled = jax.jit(functools.partial(
        simulator.simulate_fork_join_batch, n_queries=3 * 1024, p=100,
        impl="pallas", chunk_size=1024,
        cluster=ClusterSpec(r=3, fault=fault))).lower(
            key, vec, ServerParams(*(vec,) * 6)).compile()
    text = compiled.as_text()
    for stage in ("broker", "server"):
        assert f"%maxplus_segment_scan_{stage}" in text
    assert "stream.fault/" in text and "stream.join/" in text
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
