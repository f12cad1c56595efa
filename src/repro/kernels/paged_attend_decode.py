"""Pallas TPU kernels: decode attention over a BLOCK-PAGED KV cache.

The paged serving cache (models/attention.py: ``PagedKVCache`` /
``PagedQuantKVCache``) stores each layer's K/V as one shared arena of
``num_blocks`` blocks of ``block_size`` token cells — no batch axis; a
``(B, nb)`` int32 block table (-1 = unmapped) says which physical blocks
back which decode lane. These kernels are the paged twins of the dense
decode paths: same online-softmax accumulation, GQA layout, sliding-window
/ soft-capping semantics, in-kernel ``softmax_in`` / ``softmax_out``
fake-quant sites (the latter via the same two-pass S schedule), and — for
the int8 variant — the same zero-point rowsum/colsum corrections as
``int8_attend_decode``.

Two things are paged-specific:

* **Block gather via scalar prefetch.** The grid's last axis walks the
  lane's logical blocks; the block table rides in SMEM as a scalar-prefetch
  operand so each K/V BlockSpec index map picks the *physical* arena block
  ``table[b, step]`` for the DMA. Unmapped entries clip to block 0 and are
  fully masked, so only mapped blocks contribute.

* **Derived positions.** Cell validity is NOT read from stored per-cell
  positions (a freshly grown block may carry a previous owner's stale
  cells). Because a lane writes positions 0..q_pos contiguously and cell
  ``L`` of the logical view holds position ``p = q_pos - ((q_pos - L) mod
  S)`` (S = the layer's logical capacity, ``min(max_len, window)`` for
  ring layers), the kernel reconstructs every position from (L, q_pos, S)
  alone: ``valid = (L < S) & (p >= 0) [& window]``. Stale cells derive
  ``p < 0`` or ``L >= S`` and can never be read — allocation order, not
  memset, provides isolation. An idle lane (q_pos = -1) derives an
  all-invalid mask and contributes nothing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_attend_decode import (SMEM, decode_attend_step,
                                              decode_scratch, merge_parts,
                                              split_queries)
from repro.kernels.nibble import packed_len


def _paged_kernel(*refs, nb: int, bs: int, s_cap: int, hd: int,
                  window: Optional[int], logit_softcap: Optional[float],
                  quantized: bool, has_smq: bool, has_smo: bool,
                  sm_qmin: int, sm_qmax: int, smo_qmin: int, smo_qmax: int,
                  kv_bits: int = 8):
    refs = list(refs)
    tbl_ref = refs.pop(0)                   # (B, nb) scalar-prefetch
    qp_ref = refs.pop(0)                    # (B,)   scalar-prefetch
    kz_ref = vz_ref = qs_ref = qz_ref = ks_ref = vs_ref = None
    if quantized:
        kz_ref = refs.pop(0)                # (B, KV) SMEM
        vz_ref = refs.pop(0)
    smq_ref = refs.pop(0) if has_smq else None
    smo_ref = refs.pop(0) if has_smo else None
    if quantized:
        (q_ref, qs_ref, qz_ref, k_ref, ks_ref, v_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) = refs

    b = pl.program_id(0)
    kk = pl.program_id(1)
    blk = jax.lax.rem(kk, nb)               # logical block (2-pass folds)

    # derived positions: cell L of the logical view holds position
    # q_pos - ((q_pos - L) mod S) — exact for written cells, invalid
    # (p < 0 or L >= S) for everything a lane has not written.
    qp = qp_ref[b]
    cell = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    L = blk * bs + cell                                # (1, bs)
    p = qp - jnp.mod(qp - L, s_cap)
    valid = (L < s_cap) & (p >= 0) & (tbl_ref[b, blk] >= 0)
    if window is not None:
        valid &= p > qp - window
    decode_attend_step(
        step=kk, n_blocks=nb, lane=b, valid=valid, q_ref=q_ref, k_ref=k_ref,
        v_ref=v_ref, o_ref=o_ref, m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref,
        hd=hd, quantized=quantized, kv_bits=kv_bits,
        logit_softcap=logit_softcap, smq_ref=smq_ref, smo_ref=smo_ref,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_qmin=smo_qmin,
        smo_qmax=smo_qmax, qs_ref=qs_ref, qz_ref=qz_ref, kz_ref=kz_ref,
        vz_ref=vz_ref, ks_ref=ks_ref, vs_ref=vs_ref)


def _paged_call(kernel_operands, in_specs, *, b, kv, g, hd, nb, bs, s_cap,
                window, logit_softcap, quantized, sm_quant, smo_quant,
                sm_qmin, sm_qmax, smo_qmin, smo_qmax, block_table, q_pos,
                kv_bits=8, interpret=False, zero_points=()):
    has_smq = sm_quant is not None
    has_smo = smo_quant is not None
    n_steps = 2 * nb if has_smo else nb
    operands = list(zero_points)
    specs = [SMEM] * len(zero_points)
    if has_smq:
        operands.append(sm_quant.astype(jnp.float32))
        specs.append(SMEM)
    if has_smo:
        operands.append(smo_quant.astype(jnp.float32))
        specs.append(SMEM)
    operands += kernel_operands
    specs += in_specs
    parts, w = kernel_operands[0].shape[2], kernel_operands[0].shape[-1]
    kernel = functools.partial(
        _paged_kernel, nb=nb, bs=bs, s_cap=s_cap, hd=hd, window=window,
        logit_softcap=logit_softcap, quantized=quantized, has_smq=has_smq,
        has_smo=has_smo, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_qmin=smo_qmin, smo_qmax=smo_qmax, kv_bits=kv_bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_steps),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, kv, parts, g, w),
                               lambda i, kk, tbl, qp: (i, 0, 0, 0, 0)),
        scratch_shapes=decode_scratch(kv, parts, g, w))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, kv, parts, g, w), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_int8_attend_decode" if quantized
        else "paged_attend_decode",
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(q_pos, jnp.int32),
      *operands)
    return merge_parts(out, hd)


def _arena_maps(nb, has_smo):
    """K/V arena index maps: physical block = table[lane, logical step];
    the two-pass schedule re-walks K while V pins to the first block during
    the stats pass (fetched once per program there), exactly as in
    int8_attend_decode. Unmapped (-1) entries clip to block 0 — their cells
    all derive invalid, so the garbage is masked. Payload blocks are
    (1, bs, KV, hd) over the (N, bs, KV, hd) arena; scale blocks
    (1, KV, bs) over the scales handed over as (N, KV, bs)."""
    if has_smo:
        ck = lambda kk: jax.lax.rem(kk, nb)
        cv = lambda kk: jnp.maximum(kk - nb, 0)
    else:
        ck = cv = lambda kk: kk
    k_map = lambda i, kk, tbl, qp: (jnp.maximum(tbl[i, ck(kk)], 0), 0, 0, 0)
    v_map = lambda i, kk, tbl, qp: (jnp.maximum(tbl[i, cv(kk)], 0), 0, 0, 0)
    ks_map = lambda i, kk, tbl, qp: (jnp.maximum(tbl[i, ck(kk)], 0), 0, 0)
    vs_map = lambda i, kk, tbl, qp: (jnp.maximum(tbl[i, cv(kk)], 0), 0, 0)
    return k_map, v_map, ks_map, vs_map


def _q_spec(kv, parts, g, w):
    return pl.BlockSpec((1, kv, parts, g, w),
                        lambda i, kk, tbl, qp: (i, 0, 0, 0, 0))


def paged_attend_decode(q: jnp.ndarray, k_arena: jnp.ndarray,
                        v_arena: jnp.ndarray, block_table: jnp.ndarray,
                        q_pos: jnp.ndarray, *, s_cap: int,
                        window: Optional[int] = None,
                        logit_softcap: Optional[float] = None,
                        sm_quant: Optional[jnp.ndarray] = None,
                        sm_qmin: int = 0, sm_qmax: int = 255,
                        smo_quant: Optional[jnp.ndarray] = None,
                        smo_qmin: int = 0, smo_qmax: int = 255,
                        interpret: bool = False) -> jnp.ndarray:
    """One decode step over a paged bf16/f32 KV cache.

    q: (B, KV, G, hd) queries grouped per kv head, attention scale already
    folded in; k_arena/v_arena: (N, bs, KV, hd) shared arenas; block_table:
    (B, nb) int32 physical block per logical block (-1 = unmapped), where
    ``nb * bs`` covers ``s_cap`` (the layer's logical capacity =
    min(max_len, window) for ring layers); q_pos: (B,) query positions
    (-1 = idle lane -> zero contribution). Returns (B, KV, G, hd) f32.
    """
    b, kv, g, hd = q.shape
    bs = k_arena.shape[1]
    nb = block_table.shape[1]
    assert nb * bs >= s_cap, f"table covers {nb * bs} < s_cap={s_cap}"
    k_map, v_map, _, _ = _arena_maps(nb, smo_quant is not None)
    q_parts = split_queries(q.astype(jnp.float32), 8)
    operands = [q_parts, k_arena, v_arena]
    in_specs = [
        _q_spec(kv, 1, g, hd),                                     # q
        pl.BlockSpec((1, bs, kv, hd), k_map),                      # k arena
        pl.BlockSpec((1, bs, kv, hd), v_map),                      # v arena
    ]
    return _paged_call(
        operands, in_specs, b=b, kv=kv, g=g, hd=hd, nb=nb, bs=bs,
        s_cap=s_cap, window=window, logit_softcap=logit_softcap,
        quantized=False, sm_quant=sm_quant, smo_quant=smo_quant,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_qmin=smo_qmin,
        smo_qmax=smo_qmax, block_table=block_table, q_pos=q_pos,
        interpret=interpret)


def paged_int8_attend_decode(q_q: jnp.ndarray, q_scale: jnp.ndarray,
                             q_zp: jnp.ndarray, k_zp: jnp.ndarray,
                             v_zp: jnp.ndarray, k_arena: jnp.ndarray,
                             k_scale: jnp.ndarray, v_arena: jnp.ndarray,
                             v_scale: jnp.ndarray,
                             block_table: jnp.ndarray,
                             q_pos: jnp.ndarray, *, s_cap: int,
                             window: Optional[int] = None,
                             logit_softcap: Optional[float] = None,
                             sm_quant: Optional[jnp.ndarray] = None,
                             sm_qmin: int = 0, sm_qmax: int = 255,
                             smo_quant: Optional[jnp.ndarray] = None,
                             smo_qmin: int = 0, smo_qmax: int = 255,
                             kv_bits: int = 8,
                             interpret: bool = False) -> jnp.ndarray:
    """One decode step over a paged int8 KV cache (the paged twin of
    :func:`repro.kernels.int8_attend_decode.int8_attend_decode`).

    q_q: (B, KV, G, hd) int8; q_scale/q_zp: (B, KV, G) f32 (attention scale
    folded into q_scale; zero-points corrected in-kernel from rowsum/colsum
    scalars); k_zp/v_zp: (B, KV) f32 static per-head cache-grid zero-points;
    k_arena/v_arena: (N, bs, KV, hd) int8 arenas; k_scale/v_scale:
    (N, bs, KV) f32 per-head per-cell scales; block_table/q_pos as in
    :func:`paged_attend_decode`. With ``kv_bits=4`` the arenas hold
    split-half nibble-packed payloads (N, bs, KV, hd/2), unpacked in VMEM
    per block. Returns (B, KV, G, hd) f32.
    """
    b, kv, g, hd = q_q.shape
    hd_kv = packed_len(hd) if kv_bits == 4 else hd
    assert k_arena.shape[-1] == hd_kv, (
        f"arena last dim {k_arena.shape[-1]} != {hd_kv}")
    bs = k_arena.shape[1]
    nb = block_table.shape[1]
    assert nb * bs >= s_cap, f"table covers {nb * bs} < s_cap={s_cap}"
    k_map, v_map, ks_map, vs_map = _arena_maps(nb, smo_quant is not None)
    q_parts = split_queries(q_q, kv_bits)
    parts, w = q_parts.shape[2], q_parts.shape[-1]
    operands = [q_parts, q_scale.astype(jnp.float32)[..., None],
                q_zp.astype(jnp.float32)[..., None], k_arena,
                jnp.swapaxes(k_scale.astype(jnp.float32), 1, 2), v_arena,
                jnp.swapaxes(v_scale.astype(jnp.float32), 1, 2)]
    qv_map = lambda i, kk, tbl, qp: (i, 0, 0, 0)
    in_specs = [
        _q_spec(kv, parts, g, w),                                  # q_q
        pl.BlockSpec((1, kv, g, 1), qv_map),                       # q_s
        pl.BlockSpec((1, kv, g, 1), qv_map),                       # q_z
        pl.BlockSpec((1, bs, kv, hd_kv), k_map),                   # k arena
        pl.BlockSpec((1, kv, bs), ks_map),                         # k scales
        pl.BlockSpec((1, bs, kv, hd_kv), v_map),                   # v arena
        pl.BlockSpec((1, kv, bs), vs_map),                         # v scales
    ]
    return _paged_call(
        operands, in_specs, b=b, kv=kv, g=g, hd=hd, nb=nb, bs=bs,
        s_cap=s_cap, window=window, logit_softcap=logit_softcap,
        quantized=True, sm_quant=sm_quant, smo_quant=smo_quant,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_qmin=smo_qmin,
        smo_qmax=smo_qmax, block_table=block_table, q_pos=q_pos,
        kv_bits=kv_bits, interpret=interpret,
        zero_points=(k_zp.astype(jnp.float32), v_zp.astype(jnp.float32)))
