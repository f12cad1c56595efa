"""Int4 <-> packed-int8 nibble layouts for the 4-bit deploy path.

Two int4 cells share one int8 byte, halving HBM bytes for the quantized KV
cache and for packed weight payloads. Everything here is pure jnp bit
arithmetic (int32 compare/shift/mask on the VPU — no gathers, no lane
shuffles in the pack direction), so the same helpers run host-side at pack
time and inside the Pallas kernel bodies at unpack time.

Two layouts, chosen for how each consumer blocks the packed axis:

* **split-half** (:func:`pack_nibbles` / :func:`unpack_nibbles`) — along
  ``axis`` of length ``n``, byte ``j`` holds cell ``j`` in its low nibble
  and cell ``j + ceil(n/2)`` in its high nibble. Unpack is a sign-extend +
  one concatenate — no element interleave. Used for the KV cache head_dim
  axis, which the decode kernels always load whole (one (C, hd/2) block
  unpacks to (C, hd) in VMEM). Odd ``n`` pads the tail nibble with 0.

* **pairwise rows** (:func:`pack_rows` / :func:`unpack_rows`) — along axis
  0 of a (K, N) weight, packed row ``r`` holds original row ``2r`` (low
  nibble) and ``2r + 1`` (high nibble). This layout COMPOSES with K-axis
  blocking: a block of packed rows [a, b) is exactly original rows
  [2a, 2b), so the matmul kernels' k-grid (and the PEG group boundaries,
  which stay row-aligned for even group sizes) never straddle a byte.
  Requires even K — pack-time gating falls back to 8-bit otherwise.

Sign convention: nibbles store two's-complement int4 in [-8, 7]
(``_sext4`` re-extends the sign), so both the symmetric [-7, 7] weight
grid and the shifted asymmetric cache grid (uint4 - 8) fit.
"""
from __future__ import annotations

import jax.numpy as jnp


def _sext4(v):
    """Sign-extend the low nibble of an int32 array to int4 values [-8, 7]."""
    return (jnp.bitwise_and(v, 15) ^ 8) - 8


def _pack_pair(lo, hi):
    """Two int arrays of int4-range values -> one int8 byte array."""
    b = jnp.bitwise_or(jnp.bitwise_and(lo.astype(jnp.int32), 15),
                       jnp.left_shift(jnp.bitwise_and(hi.astype(jnp.int32),
                                                      15), 4))
    return jnp.where(b >= 128, b - 256, b).astype(jnp.int8)


def packed_len(n: int) -> int:
    """Packed length of an ``n``-cell int4 axis."""
    return -(-n // 2)


def pack_nibbles(x, axis: int = -1):
    """Split-half pack: int4-range values (..., n, ...) -> packed int8 with
    ``ceil(n/2)`` along ``axis``. Odd ``n`` pads the spare high nibble
    with 0 (dropped again by :func:`unpack_nibbles`)."""
    x = jnp.asarray(x)
    axis = axis % x.ndim
    n = x.shape[axis]
    half = packed_len(n)
    if 2 * half != n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, 2 * half - n)
        x = jnp.pad(x, pad)
    lo = jnp.take(x, jnp.arange(half), axis=axis)
    hi = jnp.take(x, jnp.arange(half, 2 * half), axis=axis)
    return _pack_pair(lo, hi)


def unpack_halves(packed):
    """Split-half packed int8 -> its (low, high) int8 halves of int4 values
    (cells ``[0, ceil(n/2))`` and ``[ceil(n/2), n)`` plus the odd-``n`` zero
    pad). The decode kernels consume the halves as they are, so no lane
    concatenate at the unaligned half boundary is ever emitted."""
    b = jnp.asarray(packed).astype(jnp.int32)
    return (_sext4(b).astype(jnp.int8),
            _sext4(jnp.right_shift(b, 4)).astype(jnp.int8))


def unpack_nibbles(packed, n: int, axis: int = -1):
    """Inverse of :func:`pack_nibbles`: packed int8 -> int8 array of int4
    values with the original length ``n`` along ``axis``."""
    out = jnp.concatenate(unpack_halves(packed), axis=axis)
    axis = axis % out.ndim
    if out.shape[axis] != n:
        out = jnp.take(out, jnp.arange(n), axis=axis)
    return out


def pack_rows(w):
    """Pairwise-row pack for (K, N) int4-range weights: packed row ``r`` =
    original rows (2r | 2r+1). K must be even (gate at pack time)."""
    k = w.shape[0]
    if k % 2:
        raise ValueError(f"pack_rows needs even K, got {k}")
    return _pack_pair(w[0::2], w[1::2])


def unpack_rows(packed):
    """Inverse of :func:`pack_rows`: (K/2, N) packed int8 -> (K, N) int8.
    The stack-then-reshape interleave restores exact row order, so int8
    activations in original K order dot against the unpacked block
    unchanged (and PEG group boundaries stay where pack time put them)."""
    b = jnp.asarray(packed).astype(jnp.int32)
    lo = _sext4(b)
    hi = _sext4(jnp.right_shift(b, 4))
    k2, n = b.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, n).astype(jnp.int8)
