"""Jitted public wrapper for the max-plus scan Pallas kernel.

Handles arbitrary leading shapes and pads the scan axis with the
semiring identity (a = -inf, b = 0).  The kernel runs compiled on a TPU;
only the CPU backend, where the tests run, interprets it
(:func:`interpret_mode`).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from repro.kernels.maxplus_scan.kernel import (
    DEFAULT_BLOCK_LEN,
    DEFAULT_ROW_TILE,
    maxplus_scan_pallas,
    maxplus_segment_scan_pallas,
)

SCAN_IMPLS = ("auto", "xla", "pallas")

_logger = logging.getLogger(__name__)
_logged_auto = False


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted on this backend.

    The one place that decides: compiled on a TPU, interpreted on the
    CPU backend that the tests run on, and an error anywhere else, so a
    kernel never runs interpreted where the caller expected the chip.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the maxplus Pallas kernel runs compiled on a TPU or interpreted "
        f"on the CPU test backend; backend {backend!r} is neither "
        "(use impl='xla')")


def resolve_scan_impl(impl: str = "auto") -> str:
    """Resolve the scan backend: "auto" -> "pallas" on TPU, else "xla".

    Interpret-mode Pallas is strictly slower than
    ``jax.lax.associative_scan`` off-TPU, so "auto" (now the default of
    the simulator entry points) only picks the kernel on real TPU
    hardware.  Pass "xla" or "pallas" explicitly to override; "pallas"
    raises on a backend that is neither a TPU nor the CPU test backend.
    Logs the auto choice once per process.
    """
    global _logged_auto
    if impl not in SCAN_IMPLS:
        raise ValueError(f"unknown scan impl {impl!r}; choose one of "
                         f"{SCAN_IMPLS}")
    if impl == "pallas":
        interpret_mode()  # raises on a backend that may not run it
    if impl != "auto":
        return impl
    resolved = "pallas" if jax.default_backend() == "tpu" else "xla"
    if not _logged_auto:
        _logger.info("maxplus scan impl=auto resolved to %r (backend %r)",
                     resolved, jax.default_backend())
        _logged_auto = True
    return resolved


def maxplus_scan(
    a: jax.Array,
    b: jax.Array,
    *,
    block_len: int = DEFAULT_BLOCK_LEN,
    row_tile: int = DEFAULT_ROW_TILE,
    name: str = "maxplus_scan",
) -> tuple[jax.Array, jax.Array]:
    """Inclusive (max, +) scan along the last axis; any leading shape.

    ``name`` names the kernel in compiled programs and device traces.
    """
    return _maxplus_scan(a, b, block_len=block_len, row_tile=row_tile,
                         interpret=interpret_mode(), name=name)


@functools.partial(jax.jit, static_argnames=("block_len", "row_tile",
                                             "interpret", "name"))
def _maxplus_scan(a, b, *, block_len: int, row_tile: int, interpret: bool,
                  name: str):
    orig_shape = a.shape
    n = orig_shape[-1]
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    a2 = a.reshape(rows, n)
    b2 = b.reshape(rows, n)

    pad_n = (-n) % block_len
    pad_r = (-rows) % row_tile
    if pad_n or pad_r:
        a2 = jnp.pad(a2, ((0, pad_r), (0, pad_n)),
                     constant_values=-jnp.inf)
        b2 = jnp.pad(b2, ((0, pad_r), (0, pad_n)), constant_values=0.0)

    out_a, out_b = maxplus_scan_pallas(
        a2, b2, block_len=block_len, row_tile=row_tile, interpret=interpret,
        name=name)
    out_a = out_a[:rows, :n].reshape(orig_shape)
    out_b = out_b[:rows, :n].reshape(orig_shape)
    return out_a, out_b


def maxplus_segment_scan(
    a: jax.Array,
    b: jax.Array,
    f: jax.Array,
    *,
    block_len: int = DEFAULT_BLOCK_LEN,
    row_tile: int = DEFAULT_ROW_TILE,
    name: str = "maxplus_segment_scan",
) -> tuple[jax.Array, jax.Array]:
    """Segmented inclusive (max, +) scan along the last axis.

    ``f`` is boolean (or 0/1) reset flags: True starts a new segment, so
    the scan never looks back across a flagged element.  Used by the
    fused replicated engine: all r replica subsequences of a routed chunk
    are compacted into contiguous segments of one row and scanned in a
    single kernel pass.  Any leading shape; padding uses the semiring
    identity (a = -inf, b = 0, f = 0), which cannot disturb real lanes.
    ``name`` names the kernel, as in :func:`maxplus_scan`.
    """
    return _maxplus_segment_scan(a, b, f, block_len=block_len,
                                 row_tile=row_tile,
                                 interpret=interpret_mode(), name=name)


@functools.partial(jax.jit, static_argnames=("block_len", "row_tile",
                                             "interpret", "name"))
def _maxplus_segment_scan(a, b, f, *, block_len: int, row_tile: int,
                          interpret: bool, name: str):
    orig_shape = a.shape
    n = orig_shape[-1]
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    a2 = a.reshape(rows, n)
    b2 = b.reshape(rows, n)
    f2 = f.astype(a.dtype).reshape(rows, n)

    pad_n = (-n) % block_len
    pad_r = (-rows) % row_tile
    if pad_n or pad_r:
        a2 = jnp.pad(a2, ((0, pad_r), (0, pad_n)),
                     constant_values=-jnp.inf)
        b2 = jnp.pad(b2, ((0, pad_r), (0, pad_n)), constant_values=0.0)
        f2 = jnp.pad(f2, ((0, pad_r), (0, pad_n)), constant_values=0.0)

    out_a, out_b = maxplus_segment_scan_pallas(
        a2, b2, f2, block_len=block_len, row_tile=row_tile,
        interpret=interpret, name=name)
    out_a = out_a[:rows, :n].reshape(orig_shape)
    out_b = out_b[:rows, :n].reshape(orig_shape)
    return out_a, out_b


def maxplus_scan_seeded(
    a: jax.Array,
    b: jax.Array,
    carry_a: jax.Array,
    carry_b: jax.Array | None = None,
    *,
    block_len: int = DEFAULT_BLOCK_LEN,
    row_tile: int = DEFAULT_ROW_TILE,
    name: str = "maxplus_scan",
) -> tuple[jax.Array, jax.Array]:
    """Inclusive (max, +) scan seeded by the carry of everything earlier.

    The streaming simulator's chunk entry point: ``(carry_a, carry_b)`` is
    the composed affine map of all previous chunks (for FCFS chaining,
    ``carry_a`` is the last completion time and ``carry_b`` defaults to 0,
    the identity offset).  Because affine max-plus maps compose
    associatively, seeding is one post-composition on top of the unseeded
    scan — the Pallas grid itself is unchanged:

        out_a' = max(out_a, carry_a + out_b),   out_b' = carry_b + out_b

    ``carry_a``/``carry_b`` broadcast against ``a.shape[:-1]``.
    """
    out_a, out_b = maxplus_scan(a, b, block_len=block_len,
                                row_tile=row_tile, name=name)
    carry_a = jnp.asarray(carry_a)
    if carry_b is None:
        carry_b = jnp.zeros_like(carry_a)
    out_a = jnp.maximum(out_a, carry_a[..., None] + out_b)
    out_b = jnp.asarray(carry_b)[..., None] + out_b
    return out_a, out_b
