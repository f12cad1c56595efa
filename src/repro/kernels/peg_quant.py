"""Pallas TPU kernel: fused per-embedding-group quantize-dequantize.

The paper's PEG scheme (eq. 5) on TPU: the range-based permutation is folded
into the weights (DESIGN.md §3), so at runtime the embedding axis is already
group-sorted and groups are contiguous spans. The kernel tiles full token
rows per program (a group need not be a multiple of 128 lanes — d 3840 in 4
groups is 960 each — so a per-group block would not tile): the (K,) scales
and zero-points live in SMEM and broadcast to a per-lane row, the block in
VMEM, and quant->clip->dequant fuses into one VPU pass — no HBM round-trip
for the integer intermediate.

Grid: (T / block_t,). Block: (block_t, d), block_t capped as in
fused_ln_quant.row_block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_ln_quant import SMEM, group_row, row_block


def _peg_kernel(s_ref, z_ref, x_ref, o_ref, *, qmin, qmax, emit):
    x = x_ref[...].astype(jnp.float32)
    s = group_row(s_ref, x.shape[-1])
    z = group_row(z_ref, x.shape[-1])
    q = jnp.clip(jnp.round(x / s) + z, qmin, qmax)
    if emit:
        o_ref[...] = q.astype(o_ref.dtype)
    else:
        o_ref[...] = ((q - z) * s).astype(o_ref.dtype)


def _call(x, scales, zps, *, qmin, qmax, emit, out_dtype, block_t,
          interpret):
    t, d = x.shape
    k = scales.shape[0]
    assert d % k == 0, "PEG kernel requires uniform groups"
    bt = min(row_block(block_t, d), t)
    assert t % bt == 0, f"token count {t} not divisible by block {bt}"
    kernel = functools.partial(_peg_kernel, qmin=qmin, qmax=qmax, emit=emit)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, d), out_dtype),
        grid=(t // bt,),
        in_specs=[SMEM, SMEM, pl.BlockSpec((bt, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        interpret=interpret,
        name="peg_quantize" if emit else "peg_fake_quant",
    )(scales.astype(jnp.float32), zps.astype(jnp.float32), x)


def peg_fake_quant(x: jnp.ndarray, scales: jnp.ndarray, zps: jnp.ndarray,
                   *, qmin: int, qmax: int, block_t: int = 256,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (T, d) group-sorted activations; scales/zps: (K,) with d % K == 0.

    Returns fake-quantized x (same shape/dtype).
    """
    return _call(x, scales, zps, qmin=qmin, qmax=qmax, emit=False,
                 out_dtype=x.dtype, block_t=block_t, interpret=interpret)


def peg_quantize(x: jnp.ndarray, scales: jnp.ndarray, zps: jnp.ndarray,
                 *, qmin: int, qmax: int, out_dtype=jnp.int8,
                 block_t: int = 256, interpret: bool = False) -> jnp.ndarray:
    """Emit the integer tensor (deployment path). Same layout rules."""
    return _call(x, scales, zps, qmin=qmin, qmax=qmax, emit=True,
                 out_dtype=out_dtype, block_t=block_t, interpret=interpret)
