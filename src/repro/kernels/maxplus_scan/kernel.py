"""Pallas TPU kernel: blockwise inclusive scan in the (max, +) semiring.

The FCFS queueing recurrence C_i = max(a_i, C_{i-1} + b_i) composes
associatively over (a, b) pairs (see repro.core.simulator).  This kernel
scans along the last axis of (rows, length) inputs:

  * grid = (row_tiles, length_blocks); the length dimension is sequential
    ("arbitrary") so a VMEM carry persists across blocks of one row tile,
    while row tiles are embarrassingly parallel.
  * within a block: Hillis-Steele doubling scan (log2(block_len) vector
    steps) — each step is a lane-shifted max/add, which maps onto the VPU's
    8x128 vector registers with no MXU involvement.
  * the carry (a, b) of all previous blocks is composed on top, then
    updated from the block's last column.

VMEM budget: 4 buffers x row_tile x block_len x 4B (in/out a,b) + 2 carry
columns.  Default (8, 512) tile = 8 * 512 * 4 * 4B = 64 KiB — far under
the ~16 MiB/core VMEM, so several row tiles can stay resident and the
kernel is bandwidth-bound end to end (it is a pure streaming pass).

TPU is the target; CPU validation runs with interpret=True.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params

DEFAULT_BLOCK_LEN = 512
DEFAULT_ROW_TILE = 8

_NEG_INF = float("-inf")


def _shift_right(x: jax.Array, k: int, fill: float) -> jax.Array:
    """x[:, i] <- x[:, i-k], filling the first k columns."""
    pad = jnp.full((x.shape[0], k), fill, dtype=x.dtype)
    return jnp.concatenate([pad, x[:, :-k]], axis=1)


def _maxplus_block_kernel(a_ref, b_ref, out_a_ref, out_b_ref,
                          carry_a_ref, carry_b_ref, *, block_len: int):
    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init_carry():
        carry_a_ref[...] = jnp.full_like(carry_a_ref, _NEG_INF)
        carry_b_ref[...] = jnp.zeros_like(carry_b_ref)

    a = a_ref[...]
    b = b_ref[...]

    # Hillis-Steele doubling: x[i] = combine(x[i-k], x[i]) for k = 1,2,4...
    # combine((a1,b1) earlier, (a2,b2) later) = (max(a2, a1+b2), b1+b2).
    k = 1
    while k < block_len:
        a_prev = _shift_right(a, k, _NEG_INF)
        b_prev = _shift_right(b, k, 0.0)
        a = jnp.maximum(a, a_prev + b)
        b = b_prev + b
        k *= 2

    ca = carry_a_ref[...]  # (row_tile, 1)
    cb = carry_b_ref[...]
    out_a = jnp.maximum(a, ca + b)
    out_b = cb + b
    out_a_ref[...] = out_a
    out_b_ref[...] = out_b
    carry_a_ref[...] = out_a[:, -1:]
    carry_b_ref[...] = out_b[:, -1:]


def _maxplus_segment_block_kernel(a_ref, b_ref, f_ref, out_a_ref,
                                  out_b_ref, carry_a_ref, carry_b_ref,
                                  *, block_len: int):
    """Segmented variant: f = 1 resets the scan (replica segment head).

    Same Hillis-Steele doubling as `_maxplus_block_kernel`, lifted to the
    segmented combine: when the later operand contains a reset, the
    earlier map is discarded.  Flags are float 0/1 (VPU-friendly); the
    flag lane composes by max (logical or).  The cross-block carry needs
    no flag lane — the carry is always the *earlier* operand of the
    combine, whose flag is never consumed.  This is what lets one kernel
    launch cover all r replica subsequences of a routed chunk after they
    have been compacted into contiguous segments.
    """
    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init_carry():
        carry_a_ref[...] = jnp.full_like(carry_a_ref, _NEG_INF)
        carry_b_ref[...] = jnp.zeros_like(carry_b_ref)

    a = a_ref[...]
    b = b_ref[...]
    f = f_ref[...]

    k = 1
    while k < block_len:
        a_prev = _shift_right(a, k, _NEG_INF)
        b_prev = _shift_right(b, k, 0.0)
        f_prev = _shift_right(f, k, 0.0)
        cut = f > 0.0
        a = jnp.where(cut, a, jnp.maximum(a, a_prev + b))
        b = jnp.where(cut, b, b_prev + b)
        f = jnp.maximum(f, f_prev)
        k *= 2

    ca = carry_a_ref[...]  # (row_tile, 1)
    cb = carry_b_ref[...]
    cut = f > 0.0
    out_a = jnp.where(cut, a, jnp.maximum(a, ca + b))
    out_b = jnp.where(cut, b, cb + b)
    out_a_ref[...] = out_a
    out_b_ref[...] = out_b
    carry_a_ref[...] = out_a[:, -1:]
    carry_b_ref[...] = out_b[:, -1:]


def maxplus_scan_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_len: int = DEFAULT_BLOCK_LEN,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
    name: str = "maxplus_scan",
) -> tuple[jax.Array, jax.Array]:
    """Inclusive max-plus scan along axis -1 of (rows, length) arrays.

    Both dims must already be padded to multiples of (row_tile, block_len);
    `ops.maxplus_scan` handles padding/reshaping for arbitrary shapes.
    ``name`` is the kernel's name in compiled programs and device traces.
    """
    rows, length = a.shape
    assert rows % row_tile == 0 and length % block_len == 0, (rows, length)
    grid = (rows // row_tile, length // block_len)

    spec = pl.BlockSpec((row_tile, block_len), lambda r, l: (r, l))
    kernel = functools.partial(_maxplus_block_kernel, block_len=block_len)
    out_a, out_b = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((row_tile, 1), a.dtype),
            pltpu.VMEM((row_tile, 1), b.dtype),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(a, b)
    return out_a, out_b


def maxplus_segment_scan_pallas(
    a: jax.Array,
    b: jax.Array,
    f: jax.Array,
    *,
    block_len: int = DEFAULT_BLOCK_LEN,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
    name: str = "maxplus_segment_scan",
) -> tuple[jax.Array, jax.Array]:
    """Segmented inclusive max-plus scan along axis -1.

    ``f`` holds float 0/1 reset flags (1 = this element starts a new
    segment).  Shapes/dtypes must match ``a``; both dims must be padded
    to (row_tile, block_len) multiples — `ops.maxplus_segment_scan`
    handles arbitrary shapes.  ``name`` names the kernel, as in
    `maxplus_scan_pallas`.
    """
    rows, length = a.shape
    assert rows % row_tile == 0 and length % block_len == 0, (rows, length)
    grid = (rows // row_tile, length // block_len)

    spec = pl.BlockSpec((row_tile, block_len), lambda r, l: (r, l))
    kernel = functools.partial(_maxplus_segment_block_kernel,
                               block_len=block_len)
    out_a, out_b = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((row_tile, 1), a.dtype),
            pltpu.VMEM((row_tile, 1), b.dtype),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(a, b, f)
    return out_a, out_b
