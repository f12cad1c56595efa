"""Pallas TPU kernel: LayerNorm / RMSNorm fused with quantization.

The paper's Fig.-4 rewriting puts a quantizer directly after each LayerNorm
(the FFN-input path). On TPU this is a single VPU pass per token row: compute
the row statistics, normalize+affine, quantize — the normalized f32
intermediate never leaves VMEM.

Variants (x2 norms, x2 emit modes):
  * ln_fake_quant / ln_quantize    — LayerNorm (mean/var, gamma/beta)
  * rms_fake_quant / rms_quantize  — RMSNorm (no mean subtraction; the
    affine is (1 + gamma) matching repro.models.common.rms_norm)

``*_fake_quant`` returns quant->dequant f32 (simulation / QAT forward);
``*_quantize`` emits the int8 payload (deployment; feeds int8_matmul[_peg]).

Scales / zero-points are traced (G,) vectors: G == 1 is the per-tensor case,
G > 1 quantizes per contiguous embedding group (the paper's PEG scheme with
the range-based permutation already folded into gamma/beta and the adjacent
weights, so groups are contiguous lane-aligned spans).

Grid: (T / block_t,). Block: (block_t, d) — a full embedding row per token so
the reduction stays in-block. :func:`row_block` caps block_t so one f32 block
stays within 1 MiB: the body holds several f32 temporaries of the block next
to the double-buffered input and output, and all of it must fit the chip's
scoped VMEM (at d 3840 a 256-row block does not on a TPU v5e; 64 rows do).
The per-group scales live in SMEM and are broadcast to a (1, d) row with a
lane-index select, G static (no lane-repeat, which Mosaic cannot lower).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

# f32 elements of one (block_t, d) block: 1 MiB
_BLOCK_ELEMS = 1 << 18


def row_block(block_t: int, d: int) -> int:
    """block_t capped so a (rows, d) f32 block stays within 1 MiB (a power
    of two, at least 8 rows)."""
    rows = block_t
    while rows > 8 and rows * d > _BLOCK_ELEMS:
        rows //= 2
    return rows


def group_row(ref, d: int):
    """(G,) SMEM scalars -> (1, d) f32 row: group g's value on its
    contiguous span of d // G lanes."""
    g = ref.shape[0]
    row = jnp.full((1, d), ref[0], jnp.float32)
    if g > 1:
        col = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
        for i in range(1, g):
            row = jnp.where(col >= i * (d // g), ref[i], row)
    return row


def _norm_quant_kernel(g_ref, b_ref, s_ref, z_ref, x_ref, o_ref, *,
                       kind, emit, qmin, qmax, eps):
    x = x_ref[...].astype(jnp.float32)
    if kind == "ln":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps) * g_ref[...] + b_ref[...]
    else:                                   # rms: x * rsqrt(E[x^2]) * (1 + g)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * (1.0 + g_ref[...])
    d = x.shape[-1]
    s = group_row(s_ref, d)
    z = group_row(z_ref, d)
    q = jnp.clip(jnp.round(y / s) + z, qmin, qmax)
    if emit:
        o_ref[...] = q.astype(o_ref.dtype)
    else:
        o_ref[...] = ((q - z) * s).astype(o_ref.dtype)


def _call(x, gamma, beta, scale, zp, *, kind, emit, qmin, qmax, eps,
          out_dtype, block_t, interpret):
    t, d = x.shape
    bt = min(row_block(block_t, d), t)
    assert t % bt == 0
    scale = jnp.atleast_1d(jnp.asarray(scale, jnp.float32))
    zp = jnp.atleast_1d(jnp.asarray(zp, jnp.float32))
    g = scale.shape[0]
    assert d % g == 0, "group count must divide the embedding dim"
    if beta is None:
        beta = jnp.zeros((d,), jnp.float32)
    kernel = functools.partial(_norm_quant_kernel, kind=kind, emit=emit,
                               qmin=qmin, qmax=qmax, eps=eps)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, d), out_dtype),
        grid=(t // bt,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            SMEM,
            SMEM,
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        interpret=interpret,
        name=f"{kind}_{'quantize' if emit else 'fake_quant'}",
    )(gamma.astype(jnp.float32).reshape(1, d),
      beta.astype(jnp.float32).reshape(1, d), scale, zp, x)


def ln_fake_quant(x, gamma, beta, scale, zp, *, qmin: int, qmax: int,
                  eps: float = 1e-6, block_t: int = 256,
                  interpret: bool = False):
    """x: (T, d) -> LN + fake-quant, same dtype."""
    return _call(x, gamma, beta, scale, zp, kind="ln", emit=False, qmin=qmin,
                 qmax=qmax, eps=eps, out_dtype=x.dtype, block_t=block_t,
                 interpret=interpret)


def ln_quantize(x, gamma, beta, scale, zp, *, qmin: int, qmax: int,
                eps: float = 1e-6, out_dtype=jnp.int8, block_t: int = 256,
                interpret: bool = False):
    """x: (T, d) -> LN + int8 emit."""
    return _call(x, gamma, beta, scale, zp, kind="ln", emit=True, qmin=qmin,
                 qmax=qmax, eps=eps, out_dtype=out_dtype, block_t=block_t,
                 interpret=interpret)


def rms_fake_quant(x, gamma, scale, zp, *, qmin: int, qmax: int,
                   eps: float = 1e-6, block_t: int = 256,
                   interpret: bool = False):
    """x: (T, d) -> RMSNorm + fake-quant, same dtype."""
    return _call(x, gamma, None, scale, zp, kind="rms", emit=False, qmin=qmin,
                 qmax=qmax, eps=eps, out_dtype=x.dtype, block_t=block_t,
                 interpret=interpret)


def rms_quantize(x, gamma, scale, zp, *, qmin: int, qmax: int,
                 eps: float = 1e-6, out_dtype=jnp.int8, block_t: int = 256,
                 interpret: bool = False):
    """x: (T, d) -> RMSNorm + int8 emit."""
    return _call(x, gamma, None, scale, zp, kind="rms", emit=True, qmin=qmin,
                 qmax=qmax, eps=eps, out_dtype=out_dtype, block_t=block_t,
                 interpret=interpret)
