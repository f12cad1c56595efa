"""Pallas TPU kernels: s8 x s8 -> s32 matmul with fused PEG re-scaling and a
fused deployment epilogue.

Realizes the paper's eq. (3)->(5) on the MXU. Two kernels:

  * per-tensor (eq. 3): int32 accumulation over the K grid, one re-scale at
    the end. Asymmetric activations are handled with the standard fixed-point
    zero-point correction  out = s_a s_w (A_q @ W_q - z_a * colsum(W_q)).
  * PEG (eq. 4->5): with per-embedding-group activation scales the
    accumulator is re-scaled once per GROUP. The K grid walks lane-aligned
    blocks (a PEG group need not be a multiple of 128 lanes — d 3840 in 4
    groups is 960 each); each k-step adds  s_g * (A_g @ W_g)  for every
    group g its block overlaps (a block inside one group is one MXU pass, a
    block across a group boundary one masked pass per group), and the
    zero-point term  sum_g s_g z_g colsum(W_g)  is subtracted once in the
    epilogue. Accumulation stays in an f32 VMEM scratch, fused with the
    matmul (no extra HBM traffic).

Both kernels share a fused EPILOGUE executed on the last k-step while the
accumulator tile is still in VMEM:

    f  = dequantized accumulator                       (f32, in VMEM)
    f += bias                   (optional)
    f  = activation(f)          (optional: gelu / silu / relu)
    f *= mul                    (optional f32 operand — the GLU gating path)
    o  = requantize(f)          (optional: emit int8 for the next matmul)

With the requantizing epilogue the FFN chain  LN -> quant -> W_in matmul ->
GELU -> requant -> W_out matmul  keeps int8 in HBM end-to-end: the f32
intermediate never leaves VMEM.

All scales / zero-points are TRACED operands (not compile-time constants), so
freshly calibrated scales never trigger a recompile and per-layer scales can
ride through a lax.scan over stacked layer weights. They live in SMEM as
whole arrays: Mosaic tiles no rank-1 VMEM block narrower than its array.

Block sizes are upper bounds: :func:`fit_block` picks the largest multiple
of 128 lanes at most that size which divides the dimension (K 3840 takes
384-wide k-blocks, N 960 one 960-wide block), so real model widths never
need padding or an assert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Epilogue activations: the model-side table plus identity, shared so the
# DEPLOY epilogue can never diverge from the simulate-path activations.
from repro.models.common import ACTIVATIONS as _MODEL_ACTS
from repro.kernels.nibble import unpack_rows as _unpack_rows

EPILOGUE_ACTS = {"none": lambda x: x, **_MODEL_ACTS}

# explicit, so an enclosing jax.default_matmul_precision (a reference run
# at "highest") never asks Mosaic for an f32-precision int8 matmul
_INT_PRECISION = jax.lax.Precision.DEFAULT


SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def fit_block(dim: int, block: int, align: int = 128) -> int:
    """Largest multiple of ``align`` that is <= ``block`` and divides
    ``dim``; the whole ``dim`` when it fits in ``block`` or has no such
    divisor (a block spanning the array is always legal)."""
    if dim <= block:
        return dim
    for b in range(block - block % align, 0, -align):
        if dim % b == 0:
            return b
    return dim


def _vmem_scratch(shape, dtype):
    """VMEM scratch accumulator (TPU target; interpret mode emulates it)."""
    return pltpu.VMEM(shape, dtype)


def _epilogue(f, refs, *, activation: str, has_bias: bool, has_mul: bool,
              requant: bool, qmin: int, qmax: int, o_ref):
    """Shared fused epilogue. ``f``: f32 (bm, bn) dequantized accumulator.
    ``refs``: dict of the optional operand refs present for this call."""
    if has_bias:
        f = f + refs["bias"][0, :][None, :]
    f = EPILOGUE_ACTS[activation](f)
    if has_mul:
        f = f * refs["mul"][...]
    if requant:
        s_out = refs["outq"][0]
        z_out = refs["outq"][1]
        q = jnp.clip(jnp.round(f / s_out) + z_out, qmin, qmax)
        o_ref[...] = q.astype(o_ref.dtype)
    else:
        o_ref[...] = f.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Per-tensor path (paper eq. 3) + fused epilogue
# ---------------------------------------------------------------------------

def _int8_matmul_kernel(s_ref, za_ref, *rest, n_k: int, activation: str,
                        has_zp: bool, has_bias: bool, has_mul: bool,
                        requant: bool, qmin: int, qmax: int,
                        w_bits: int = 8):
    refs = {}
    rest = list(rest)
    if has_zp:
        refs["colsum"] = rest.pop(0)
    if has_bias:
        refs["bias"] = rest.pop(0)
    if has_mul:
        refs["mul"] = rest.pop(0)
    if requant:
        refs["outq"] = rest.pop(0)
    a_ref, w_ref, o_ref, acc_ref = rest

    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w_bits == 4:
        # unpack-to-int8 prologue: (bk/2, bn) row-packed nibbles -> (bk, bn)
        # in VMEM, so the MXU path below is byte-identical to the 8-bit one
        # while the HBM weight read halves.
        w = _unpack_rows(w)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w, (((1,), (0,)), ((), ())),
        precision=_INT_PRECISION, preferred_element_type=jnp.int32)

    @pl.when(k_idx == n_k - 1)
    def _done():
        acc = acc_ref[...].astype(jnp.float32)
        if has_zp:
            corr = refs["colsum"][0, :].astype(jnp.float32)
            acc = acc - za_ref[0] * corr[None, :]
        f = acc * s_ref[0]
        _epilogue(f, refs, activation=activation, has_bias=has_bias,
                  has_mul=has_mul, requant=requant, qmin=qmin, qmax=qmax,
                  o_ref=o_ref)


def int8_matmul(a_q: jnp.ndarray, w_q: jnp.ndarray, s_a, s_w, *,
                z_a=None, w_colsum: jnp.ndarray = None,
                bias: jnp.ndarray = None, mul: jnp.ndarray = None,
                activation: str = "none",
                out_scale=None, out_zp=None,
                qmin: int = -128, qmax: int = 127,
                out_dtype=jnp.float32, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                w_bits: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Per-tensor path (paper eq. 3) with fused epilogue.

    a_q: (M, K) int8, w_q: (K, N) int8; s_a/s_w traced scalars.
    z_a + w_colsum (N,): asymmetric-activation zero-point correction
    (for w_bits=4 the colsum must come from the UNPACKED int4 values).
    bias (N,), mul (M, N) f32, activation, out_scale/out_zp: the epilogue.
    When out_scale is given the output is int8 on the [qmin, qmax] grid.
    ``w_bits=4``: w_q is (K/2, N) pairwise-row-packed nibbles
    (repro.kernels.nibble.pack_rows); a packed k-block [a, b) is exactly
    original rows [2a, 2b), so the K grid walks packed rows directly.
    """
    m, k = a_q.shape
    _, n = w_q.shape
    bm, bn, bk = min(block_m, m), fit_block(n, block_n), fit_block(k, block_k)
    assert m % bm == 0, f"rows {m} not a multiple of block {bm}"
    if w_bits == 4:
        assert bk % 2 == 0, f"w_bits=4 needs even block_k, got {bk}"
        assert w_q.shape[0] == k // 2, (
            f"packed w rows {w_q.shape[0]} != K/2 = {k // 2}")

    has_zp = w_colsum is not None
    has_bias = bias is not None
    has_mul = mul is not None
    requant = out_scale is not None
    if requant:
        out_dtype = jnp.int8

    s_prod = (jnp.asarray(s_a, jnp.float32) *
              jnp.asarray(s_w, jnp.float32)).reshape(1)
    za = jnp.asarray(0.0 if z_a is None else z_a, jnp.float32).reshape(1)

    operands = [s_prod, za]
    in_specs = [SMEM, SMEM]
    if has_zp:
        operands.append(w_colsum.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if has_bias:
        operands.append(bias.astype(jnp.float32).reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if has_mul:
        operands.append(mul.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
    if requant:
        outq = jnp.stack([jnp.asarray(out_scale, jnp.float32).reshape(()),
                          jnp.asarray(0.0 if out_zp is None else out_zp,
                                      jnp.float32).reshape(())])
        operands.append(outq)
        in_specs.append(SMEM)
    bkw = bk // 2 if w_bits == 4 else bk
    operands += [a_q, w_q]
    in_specs += [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                 pl.BlockSpec((bkw, bn), lambda i, j, kk: (kk, j))]

    kernel = functools.partial(
        _int8_matmul_kernel, n_k=k // bk, activation=activation,
        has_zp=has_zp, has_bias=has_bias, has_mul=has_mul, requant=requant,
        qmin=qmin, qmax=qmax, w_bits=w_bits)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[_vmem_scratch((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(*operands)


# ---------------------------------------------------------------------------
# PEG path (paper eq. 4->5) + fused epilogue
# ---------------------------------------------------------------------------

def _int8_matmul_peg_kernel(sw_ref, sa_ref, za_ref, wcs_ref, *rest,
                            n_k: int, bk: int, gs: int, n_span: int,
                            activation: str, has_bias: bool, has_mul: bool,
                            requant: bool, qmin: int, qmax: int,
                            w_bits: int = 8):
    refs = {}
    rest = list(rest)
    if has_bias:
        refs["bias"] = rest.pop(0)
    if has_mul:
        refs["mul"] = rest.pop(0)
    if requant:
        refs["outq"] = rest.pop(0)
    a_ref, w_ref, o_ref, acc_ref = rest

    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w_bits == 4:
        # unpack-to-int8 prologue (see _int8_matmul_kernel); packed rows
        # stay pairs of original rows, so the k-block is row-aligned.
        w = _unpack_rows(w)
    a = a_ref[...]
    k0 = k_idx * bk
    g_lo = k0 // gs

    def _dot(x):
        return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                   precision=_INT_PRECISION,
                                   preferred_element_type=jnp.int32
                                   ).astype(jnp.float32)

    if n_span == 1:
        # the k-block lies inside one PEG group: one MXU pass, one re-scale
        acc_ref[...] += sa_ref[g_lo] * _dot(a)
    else:
        # the k-block crosses group boundaries: one masked MXU pass per
        # group it overlaps, each re-scaled by that group's step
        col_g = (k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)) // gs
        g_hi = (k0 + bk - 1) // gs
        for j in range(n_span):
            g = g_lo + j

            @pl.when(g <= g_hi)
            def _group(g=g):
                part = _dot(jnp.where(col_g == g, a, jnp.zeros_like(a)))
                acc_ref[...] += sa_ref[g] * part

    @pl.when(k_idx == n_k - 1)
    def _done():
        # zero-point term sum_g s_g z_g colsum(W_g), once per output tile
        corr = jnp.zeros((1, acc_ref.shape[1]), jnp.float32)
        for g in range(wcs_ref.shape[0]):
            corr += (sa_ref[g] * za_ref[g]) * \
                wcs_ref[g:g + 1, :].astype(jnp.float32)
        f = (acc_ref[...] - corr) * sw_ref[0]
        _epilogue(f, refs, activation=activation, has_bias=has_bias,
                  has_mul=has_mul, requant=requant, qmin=qmin, qmax=qmax,
                  o_ref=o_ref)


def int8_matmul_peg(a_q: jnp.ndarray, w_q: jnp.ndarray,
                    act_scales: jnp.ndarray, act_zps: jnp.ndarray,
                    w_scale, w_colsum_g: jnp.ndarray, *,
                    bias: jnp.ndarray = None, mul: jnp.ndarray = None,
                    activation: str = "none",
                    out_scale=None, out_zp=None,
                    qmin: int = -128, qmax: int = 127,
                    out_dtype=jnp.float32, block_m: int = 256,
                    block_n: int = 256, block_k: int = 512, w_bits: int = 8,
                    interpret: bool = False) -> jnp.ndarray:
    """a_q: (M, K) int8 group-sorted; w_q: (K, N) int8; act_scales/zps: (G,);
    w_colsum_g: (G, N) int32 = per-group column sums of w_q (always from the
    UNPACKED values); w_scale traced scalar. K % G == 0; group_size = K // G
    need not align with the k-block (see the module docstring).
    ``w_bits=4``: w_q is (K/2, N) row-packed nibbles; needs an even group
    size. Epilogue args as in :func:`int8_matmul`."""
    m, k = a_q.shape
    k2, n = w_q.shape
    assert k == (2 * k2 if w_bits == 4 else k2)
    g = act_scales.shape[0]
    assert k % g == 0
    gs = k // g
    if w_bits == 4:
        assert gs % 2 == 0, f"w_bits=4 needs even PEG group size, got {gs}"
    bm, bn, bk = min(block_m, m), fit_block(n, block_n), fit_block(k, block_k)
    assert m % bm == 0, f"rows {m} not a multiple of block {bm}"
    # groups one k-block can overlap: 1 when blocks nest inside groups
    n_span = 1 if gs % bk == 0 else min(g, -(-(bk - 1) // gs) + 1)

    has_bias = bias is not None
    has_mul = mul is not None
    requant = out_scale is not None
    if requant:
        out_dtype = jnp.int8

    operands = [jnp.asarray(w_scale, jnp.float32).reshape(1),
                act_scales.astype(jnp.float32),
                act_zps.astype(jnp.float32),
                w_colsum_g]
    in_specs = [SMEM, SMEM, SMEM,                                # s_w, s_g, z_g
                pl.BlockSpec((g, bn), lambda i, j, kk: (0, j))]  # colsum
    if has_bias:
        operands.append(bias.astype(jnp.float32).reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    if has_mul:
        operands.append(mul.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
    if requant:
        outq = jnp.stack([jnp.asarray(out_scale, jnp.float32).reshape(()),
                          jnp.asarray(0.0 if out_zp is None else out_zp,
                                      jnp.float32).reshape(())])
        operands.append(outq)
        in_specs.append(SMEM)
    bkw = bk // 2 if w_bits == 4 else bk
    operands += [a_q, w_q]
    in_specs += [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                 pl.BlockSpec((bkw, bn), lambda i, j, kk: (kk, j))]

    kernel = functools.partial(
        _int8_matmul_peg_kernel, n_k=k // bk, bk=bk, gs=gs, n_span=n_span,
        activation=activation,
        has_bias=has_bias, has_mul=has_mul, requant=requant,
        qmin=qmin, qmax=qmax, w_bits=w_bits)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[_vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
        name="int8_matmul_peg",
    )(*operands)
