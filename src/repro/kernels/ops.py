"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend kernels run in interpret mode — the kernel body executes
in Python for correctness validation; on a TPU the same call lowers to
Mosaic and is never interpreted. ``interpret=None`` picks by backend; an
explicit ``False`` on the CPU lowers for a described (not attached) TPU.

Two conventions enforced here (and relied on by repro.core.deploy):

* **Traced scales.** Every scale / zero-point is a traced operand, never a
  ``static_argnames`` entry — serving with freshly calibrated scales (or
  per-layer scales sliced out of a lax.scan) must not recompile per call.
  Only block sizes, activation names and flags are static.

* **Batched + ragged shapes.** Wrappers accept ``(..., K)`` inputs: leading
  dims are flattened into the M/token axis and, when the flattened row count
  does not divide the block size, rows are zero-padded and the result is
  sliced back — so decode-time ``(B, 1, D)`` and ragged prefill shapes all
  hit the same kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import fused_ln_quant as _lnq
from repro.kernels import int8_attend_decode as _iad
from repro.kernels import int8_matmul as _imm
from repro.kernels import paged_attend_decode as _pad
from repro.kernels import peg_quant as _peg
from repro.kernels import ref as _ref


def _interp(flag: Optional[bool]) -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend != "cpu":
        raise RuntimeError(f"Pallas kernels target the TPU (Mosaic) or the "
                           f"CPU interpreter, not {backend!r}")
    return True if flag is None else flag


def _flatten_rows(x, block: int):
    """(..., D) -> ((M_padded, D), lead_shape, M). Pads rows to the block."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    # m <= block runs as one partial block (bm == m) of whole sublane
    # tiles (Mosaic refuses some broadcasts over fewer than 8 rows); larger
    # ragged row counts are zero-padded to a block multiple. Padding rows
    # are sliced off after.
    pad = (-m) % block if m > block else (-m) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, lead, m


def _unflatten_rows(y, lead, m):
    return y[:m].reshape(*lead, y.shape[-1])


# ---------------------------------------------------------------------------
# Per-embedding-group quantize (paper eq. 5)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "block_t",
                                             "interpret"))
def peg_fake_quant(x, scales, zps, *, qmin: int = 0, qmax: int = 255,
                   block_t: int = 256, interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _peg.peg_fake_quant(x2, scales, zps, qmin=qmin, qmax=qmax,
                              block_t=block_t, interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "block_t",
                                             "interpret"))
def peg_quantize(x, scales, zps, *, qmin: int = 0, qmax: int = 255,
                 block_t: int = 256, interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _peg.peg_quantize(x2, scales, zps, qmin=qmin, qmax=qmax,
                            block_t=block_t, interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


# ---------------------------------------------------------------------------
# int8 matmuls (paper eq. 3-5) with fused epilogue
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("activation", "qmin", "qmax",
                                             "block_m", "block_n", "block_k",
                                             "w_bits", "interpret"))
def int8_matmul(a_q, w_q, *, s_a, s_w, z_a=None, w_colsum=None, bias=None,
                mul=None, activation: str = "none", out_scale=None,
                out_zp=None, qmin: int = -128, qmax: int = 127,
                block_m: int = 256, block_n: int = 256, block_k: int = 512,
                w_bits: int = 8, interpret: Optional[bool] = None):
    """Per-tensor int8 matmul (+ fused epilogue) over (..., K) activations.

    s_a/s_w (and the optional z_a/out_scale/out_zp) are traced scalars.
    z_a requires w_colsum (N,) = colsum(w_q) for the zero-point correction.
    ``w_bits=4``: w_q is (K/2, N) pairwise-row-packed nibbles (see
    repro.kernels.nibble) and w_colsum must be supplied pre-computed from
    the unpacked int4 values — summing the packed bytes would be wrong.
    """
    if z_a is not None and w_colsum is None:
        if w_bits == 4:
            raise ValueError("w_bits=4 with z_a requires explicit w_colsum "
                             "(colsum over packed bytes is meaningless)")
        w_colsum = jnp.sum(w_q.astype(jnp.int32), axis=0)
    a2, lead, m = _flatten_rows(a_q, block_m)
    mul2 = None
    if mul is not None:
        mul2, _, _ = _flatten_rows(mul, block_m)
    out = _imm.int8_matmul(a2, w_q, s_a, s_w, z_a=z_a, w_colsum=w_colsum,
                           bias=bias, mul=mul2, activation=activation,
                           out_scale=out_scale, out_zp=out_zp, qmin=qmin,
                           qmax=qmax, block_m=block_m, block_n=block_n,
                           block_k=block_k, w_bits=w_bits,
                           interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


@functools.partial(jax.jit, static_argnames=("activation", "qmin", "qmax",
                                             "block_m", "block_n", "block_k",
                                             "w_bits", "interpret"))
def int8_matmul_peg(a_q, w_q, act_scales, act_zps, *, w_scale,
                    w_colsum=None, bias=None, mul=None,
                    activation: str = "none", out_scale=None, out_zp=None,
                    qmin: int = -128, qmax: int = 127, block_m: int = 256,
                    block_n: int = 256, block_k: int = 512, w_bits: int = 8,
                    interpret: Optional[bool] = None):
    """PEG fixed-point matmul: K re-scalings fused into the MXU k-loop.
    Computes the zero-point correction internally unless ``w_colsum`` (G, N)
    is supplied (deployment pre-packs it next to the int8 weights).
    ``w_bits=4``: w_q is (K/2, N) row-packed nibbles; w_colsum required."""
    g = act_scales.shape[0]
    if w_colsum is None:
        if w_bits == 4:
            raise ValueError("w_bits=4 requires explicit w_colsum")
        w_colsum = _ref.w_colsum_groups(w_q, g)
    a2, lead, m = _flatten_rows(a_q, block_m)
    mul2 = None
    if mul is not None:
        mul2, _, _ = _flatten_rows(mul, block_m)
    out = _imm.int8_matmul_peg(a2, w_q, act_scales, act_zps, w_scale,
                               w_colsum, bias=bias, mul=mul2,
                               activation=activation, out_scale=out_scale,
                               out_zp=out_zp, qmin=qmin, qmax=qmax,
                               block_m=block_m, block_n=block_n,
                               block_k=block_k, w_bits=w_bits,
                               interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


# ---------------------------------------------------------------------------
# int8 KV-cache decode attention (serving hot path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "logit_softcap",
                                             "sm_qmin", "sm_qmax",
                                             "smo_qmin", "smo_qmax", "chunk",
                                             "kv_bits", "interpret"))
def int8_attend_decode(q_q, q_scale, k_q, k_scale, v_q, v_scale, k_pos,
                       q_pos, *, q_zp=None, k_zp=None, v_zp=None,
                       window: Optional[int] = None,
                       logit_softcap: Optional[float] = None,
                       sm_quant=None, sm_qmin: int = 0, sm_qmax: int = 255,
                       smo_quant=None, smo_qmin: int = 0, smo_qmax: int = 255,
                       chunk: int = 256, kv_bits: int = 8,
                       interpret: Optional[bool] = None):
    """Decode attention over an int8 KV cache (see int8_attend_decode.py).

    q_q (B, KV, G, hd) int8; q_scale (B, KV, G) f32 (attention scale folded
    in); q_zp (B, KV, G) / k_zp, v_zp (B, KV) f32 shifted-grid zero-points
    (None = symmetric); k_q/v_q (B, S, KV, hd) int8; k_scale/v_scale
    (B, S, KV) f32; k_pos (B, S) int32 (-1 = empty); q_pos (B,) int32.
    ``sm_quant``/``smo_quant``: optional (2,) [scale, zp] — the traced
    softmax_in / softmax_out fake-quants (the latter selects the two-pass
    schedule). Ragged S is padded to the chunk size with empty slots.
    ``kv_bits=4``: k_q/v_q are split-half nibble-packed (B, S, KV, hd/2)
    payloads, unpacked in VMEM inside the kernel.
    Returns (B, KV, G, hd) f32.
    """
    if q_zp is None:
        q_zp = jnp.zeros_like(q_scale)
    if k_zp is None:
        k_zp = jnp.zeros(q_scale.shape[:2], jnp.float32)
    if v_zp is None:
        v_zp = jnp.zeros(q_scale.shape[:2], jnp.float32)
    s_len = k_pos.shape[1]
    c = min(chunk, s_len)
    pad = (-s_len) % c
    if pad:
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_q = jnp.pad(k_q, pad4)
        v_q = jnp.pad(v_q, pad4)
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    return _iad.int8_attend_decode(
        q_q, q_scale, q_zp, k_zp, v_zp, k_q, k_scale, v_q, v_scale, k_pos,
        q_pos,
        window=window, logit_softcap=logit_softcap, sm_quant=sm_quant,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_quant=smo_quant,
        smo_qmin=smo_qmin, smo_qmax=smo_qmax, chunk=c, kv_bits=kv_bits,
        interpret=_interp(interpret))


# ---------------------------------------------------------------------------
# Paged KV-cache decode attention (block-pool serving path)
# ---------------------------------------------------------------------------

def _lane_blocks(block_table, s_cap, block_size):
    """Slice the table to the logical blocks this layer can touch: a
    sliding-window layer's capacity (s_cap = min(max_len, window)) needs
    only the first ceil(s_cap / bs) columns, so its kernel grid never walks
    (or DMAs) blocks only global layers use."""
    nb = -(-s_cap // block_size)
    return block_table[:, :nb]


@functools.partial(jax.jit, static_argnames=("s_cap", "window",
                                             "logit_softcap", "sm_qmin",
                                             "sm_qmax", "smo_qmin",
                                             "smo_qmax", "interpret"))
def paged_attend_decode(q, k_arena, v_arena, block_table, q_pos, *,
                        s_cap: int, window: Optional[int] = None,
                        logit_softcap: Optional[float] = None,
                        sm_quant=None, sm_qmin: int = 0, sm_qmax: int = 255,
                        smo_quant=None, smo_qmin: int = 0,
                        smo_qmax: int = 255,
                        interpret: Optional[bool] = None):
    """Decode attention over a paged bf16/f32 KV cache (see
    paged_attend_decode.py). q (B, KV, G, hd) with the attention scale
    folded in; arenas (N, bs, KV, hd); block_table (B, nb) int32; q_pos
    (B,) int32 (-1 = idle lane). ``s_cap`` is the layer's logical capacity.
    Returns (B, KV, G, hd) f32.
    """
    return _pad.paged_attend_decode(
        q, k_arena, v_arena,
        _lane_blocks(block_table, s_cap, k_arena.shape[1]), q_pos,
        s_cap=s_cap, window=window, logit_softcap=logit_softcap,
        sm_quant=sm_quant, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_quant=smo_quant, smo_qmin=smo_qmin, smo_qmax=smo_qmax,
        interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("s_cap", "window",
                                             "logit_softcap", "sm_qmin",
                                             "sm_qmax", "smo_qmin",
                                             "smo_qmax", "kv_bits",
                                             "interpret"))
def paged_int8_attend_decode(q_q, q_scale, k_arena, k_scale, v_arena,
                             v_scale, block_table, q_pos, *, s_cap: int,
                             q_zp=None, k_zp=None, v_zp=None,
                             window: Optional[int] = None,
                             logit_softcap: Optional[float] = None,
                             sm_quant=None, sm_qmin: int = 0,
                             sm_qmax: int = 255, smo_quant=None,
                             smo_qmin: int = 0, smo_qmax: int = 255,
                             kv_bits: int = 8,
                             interpret: Optional[bool] = None):
    """Decode attention over a paged int8 KV cache — the paged twin of
    :func:`int8_attend_decode` (same zero-point handling; scales traced).
    k_arena/v_arena (N, bs, KV, hd) int8; k_scale/v_scale (N, bs, KV) f32.
    ``kv_bits=4``: arenas are split-half nibble-packed (N, bs, KV, hd/2).
    Returns (B, KV, G, hd) f32.
    """
    if q_zp is None:
        q_zp = jnp.zeros_like(q_scale)
    if k_zp is None:
        k_zp = jnp.zeros(q_scale.shape[:2], jnp.float32)
    if v_zp is None:
        v_zp = jnp.zeros(q_scale.shape[:2], jnp.float32)
    return _pad.paged_int8_attend_decode(
        q_q, q_scale, q_zp, k_zp, v_zp, k_arena, k_scale, v_arena, v_scale,
        _lane_blocks(block_table, s_cap, k_arena.shape[1]), q_pos,
        s_cap=s_cap, window=window, logit_softcap=logit_softcap,
        sm_quant=sm_quant, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_quant=smo_quant, smo_qmin=smo_qmin, smo_qmax=smo_qmax,
        kv_bits=kv_bits, interpret=_interp(interpret))


# ---------------------------------------------------------------------------
# Fused norm + quantize (paper Fig. 4 hot path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "eps", "block_t",
                                             "interpret"))
def ln_fake_quant(x, gamma, beta, scale, zp, *, qmin: int = 0,
                  qmax: int = 255, eps: float = 1e-6, block_t: int = 256,
                  interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _lnq.ln_fake_quant(x2, gamma, beta, scale, zp, qmin=qmin,
                             qmax=qmax, eps=eps, block_t=block_t,
                             interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "eps", "block_t",
                                             "interpret"))
def ln_quantize(x, gamma, beta, scale, zp, *, qmin: int = 0, qmax: int = 255,
                eps: float = 1e-6, block_t: int = 256,
                interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _lnq.ln_quantize(x2, gamma, beta, scale, zp, qmin=qmin, qmax=qmax,
                           eps=eps, block_t=block_t,
                           interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "eps", "block_t",
                                             "interpret"))
def rms_fake_quant(x, gamma, scale, zp, *, qmin: int = 0, qmax: int = 255,
                   eps: float = 1e-6, block_t: int = 256,
                   interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _lnq.rms_fake_quant(x2, gamma, scale, zp, qmin=qmin, qmax=qmax,
                              eps=eps, block_t=block_t,
                              interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)


@functools.partial(jax.jit, static_argnames=("qmin", "qmax", "eps", "block_t",
                                             "interpret"))
def rms_quantize(x, gamma, scale, zp, *, qmin: int = 0, qmax: int = 255,
                 eps: float = 1e-6, block_t: int = 256,
                 interpret: Optional[bool] = None):
    x2, lead, m = _flatten_rows(x, _lnq.row_block(block_t, x.shape[-1]))
    out = _lnq.rms_quantize(x2, gamma, scale, zp, qmin=qmin, qmax=qmax,
                            eps=eps, block_t=block_t,
                            interpret=_interp(interpret))
    return _unflatten_rows(out, lead, m)
