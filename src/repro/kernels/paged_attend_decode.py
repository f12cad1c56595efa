"""Pallas TPU kernels: decode attention over a BLOCK-PAGED KV cache.

The paged serving cache (models/attention.py: ``PagedKVCache`` /
``PagedQuantKVCache``) stores each layer's K/V as one shared arena of
``num_blocks`` pages of ``block_size`` token cells — no batch axis; a
``(B, nb)`` int32 block table (-1 = unmapped) says which physical pages
back which decode lane. These kernels are the paged twins of the dense
decode paths: same online-softmax accumulation, GQA layout, sliding-window
/ soft-capping semantics, in-kernel ``softmax_in`` / ``softmax_out``
fake-quant sites (the latter via the same two-pass S schedule), and — for
the int8 variant — the same zero-point rowsum/colsum corrections as
``int8_attend_decode``.

Three things are paged-specific:

* **A live-bounded walk.** Lane ``b``'s grid row walks its logical pages
  ``0 .. n_live - 1`` only, ``n_live = min(nb, ceil((q_pos + 1) / bs))``
  (:func:`live_pages`): all of them once a ring layer has wrapped, none
  for an idle lane (q_pos = -1, which writes zeros). The row has a slot
  for every compute block the table could need; a slot past the lane's
  live blocks (:func:`walk_blocks`) computes nothing, and its index maps
  repeat the last live block's pages, so the pipeline copies nothing for
  it either.

* **Multi-page compute blocks.** A step takes :func:`pages_per_block`
  pages: 128 tokens of a bf16/f32 arena (one lane row of scores per
  head), one page of an int8 arena (its per-cell scales arrive
  page-major, (N, KV, bs), and a block's scales must be one lane row per
  head). The arena is handed to the kernel once per page of a block, each
  copy with its own BlockSpec whose index map reads the block table (in
  SMEM, as a scalar-prefetch operand) for that page's *physical* arena
  page; the pipeline double-buffers every copy, so the next block's pages
  (the next lane's first block at a lane's end) are in flight while the
  current block is consumed. A block's pages past the live bound point at
  the lane's last live page (a second copy of a live page, never a page
  past the bound), and their cells derive invalid.

* **Derived positions.** Cell validity is NOT read from stored per-cell
  positions (a freshly grown page may carry a previous owner's stale
  cells). Because a lane writes positions 0..q_pos contiguously and cell
  ``L`` of the logical view holds position ``p = q_pos - ((q_pos - L) mod
  S)`` (S = the layer's logical capacity, ``min(max_len, window)`` for
  ring layers), the kernel reconstructs every position from (L, q_pos, S)
  alone: ``valid = (L < S) & (p >= 0) & (table entry >= 0) [& window]``.
  Stale cells derive ``p < 0`` or ``L >= S`` and can never be read —
  allocation order, not memset, provides isolation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_attend_decode import (SMEM, decode_attend_step,
                                              decode_scratch, merge_parts,
                                              split_queries)
from repro.kernels.nibble import packed_len

BLOCK_TOKENS = 128       # a bf16/f32 compute block: one lane row of scores


def pages_per_block(block_size: int, quantized: bool) -> int:
    """Pages per compute block of the walk: ``BLOCK_TOKENS`` tokens over a
    bf16/f32 arena, one page over an int8 arena (its page-major scales)."""
    return 1 if quantized else max(1, BLOCK_TOKENS // block_size)


def live_pages(q_pos, *, nb: int, bs: int):
    """Pages a lane at ``q_pos`` has written, ``min(nb, ceil((q_pos + 1) /
    bs))``: all ``nb`` once a ring layer wraps, 0 for an idle lane."""
    xp = jnp if isinstance(q_pos, jax.Array) else np
    return xp.minimum((q_pos + bs) // bs, nb)


def walk_blocks(q_pos, *, nb: int, bs: int, pages: int):
    """Compute blocks a lane at ``q_pos`` walks over a table of ``nb``
    pages of ``bs`` cells, ``pages`` pages a block: ``ceil(live_pages /
    pages)``. Takes a traced scalar (the kernel's walk) or host ints /
    arrays (the scheduler's counter)."""
    return (live_pages(q_pos, nb=nb, bs=bs) + pages - 1) // pages


def _walk(kk, blocks, n_walk: int, has_smo: bool):
    """(walk step, logical block) of grid step ``kk``. A lane's grid row
    holds ``n_walk`` block slots (twice with the two-pass schedule, whose
    second pass starts at slot ``n_walk``); the first ``blocks`` slots of
    each pass are live, the rest only repeat the last live block."""
    if not has_smo:
        return kk, kk
    second = kk >= n_walk
    blk = jnp.where(second, kk - n_walk, kk)
    return jnp.where(second, blocks + blk, blk), blk


def _page_maps(nb: int, bs: int, pages: int, n_walk: int, has_smo: bool):
    """Index maps of page ``i`` of the current block, for K and for V: the
    physical page ``table[lane, col]`` of logical page ``col = blk * pages
    + i``, clipped to the lane's last live page. A dead grid step (past the
    lane's live blocks) repeats its predecessor's pages, so the pipeline
    copies nothing for it; V pins to block 0 during the two-pass schedule's
    first pass, as in int8_attend_decode."""
    def page(lane, blk, i, tbl, qp):
        blocks = walk_blocks(qp[lane], nb=nb, bs=bs, pages=pages)
        last = live_pages(qp[lane], nb=nb, bs=bs) - 1
        col = jnp.minimum(jnp.minimum(blk, blocks - 1) * pages + i, last)
        return jnp.maximum(tbl[lane, jnp.maximum(col, 0)], 0)

    def k_blk(kk):
        return jax.lax.rem(kk, n_walk) if has_smo else kk

    def v_blk(kk):
        return jnp.maximum(kk - n_walk, 0) if has_smo else kk

    def maps(i, rank):
        tail = (0,) * (rank - 1)
        return (lambda b, kk, tbl, qp: (page(b, k_blk(kk), i, tbl, qp),)
                + tail,
                lambda b, kk, tbl, qp: (page(b, v_blk(kk), i, tbl, qp),)
                + tail)
    return maps


def _paged_kernel(*refs, nb: int, bs: int, pages: int, n_walk: int,
                  s_cap: int, hd: int, window: Optional[int],
                  logit_softcap: Optional[float], quantized: bool,
                  has_smq: bool, has_smo: bool, sm_qmin: int, sm_qmax: int,
                  smo_qmin: int, smo_qmax: int, kv_bits: int = 8):
    refs = list(refs)
    tbl_ref = refs.pop(0)                   # (B, nb) scalar-prefetch
    qp_ref = refs.pop(0)                    # (B,)   scalar-prefetch
    kz_ref = vz_ref = qs_ref = qz_ref = ks_ref = vs_ref = None
    if quantized:
        kz_ref = refs.pop(0)                # (B, KV) SMEM
        vz_ref = refs.pop(0)
    smq_ref = refs.pop(0) if has_smq else None
    smo_ref = refs.pop(0) if has_smo else None
    q_ref = refs.pop(0)
    if quantized:
        qs_ref, qz_ref = refs.pop(0), refs.pop(0)
    k_refs = [refs.pop(0) for _ in range(pages)]
    if quantized:
        ks_ref = refs.pop(0)
    v_refs = [refs.pop(0) for _ in range(pages)]
    if quantized:
        vs_ref = refs.pop(0)
    o_ref, m_ref, l_ref, acc_ref = refs

    b = pl.program_id(0)
    kk = pl.program_id(1)
    qp = qp_ref[b]
    blocks = walk_blocks(qp, nb=nb, bs=bs, pages=pages)
    step, blk = _walk(kk, blocks, n_walk, has_smo)

    @pl.when((blocks == 0) & (kk == 0))
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(blk < blocks)
    def _live():
        # derived positions: cell L of the logical view holds position
        # q_pos - ((q_pos - L) mod S) — exact for written cells, invalid
        # (p < 0 or L >= S) for everything a lane has not written; cells
        # of a block's pages past the live bound (copies of the last live
        # page) derive L > q_pos, so p < 0.
        c = pages * bs
        cell = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        L = blk * c + cell                                 # (1, C)
        p = qp - jnp.mod(qp - L, s_cap)
        entry = jnp.full((1, c), tbl_ref[b, blk * pages], jnp.int32)
        for i in range(1, pages):
            col = jnp.minimum(blk * pages + i, nb - 1)
            entry = jnp.where(cell // bs == i, tbl_ref[b, col], entry)
        valid = (L < s_cap) & (p >= 0) & (entry >= 0)
        if window is not None:
            valid &= p > qp - window
        decode_attend_step(
            step=step, n_blocks=blocks, lane=b, valid=valid, q_ref=q_ref,
            k_refs=k_refs, v_refs=v_refs, o_ref=o_ref, m_ref=m_ref,
            l_ref=l_ref, acc_ref=acc_ref, hd=hd, quantized=quantized,
            kv_bits=kv_bits, logit_softcap=logit_softcap, smq_ref=smq_ref,
            smo_ref=smo_ref, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
            smo_qmin=smo_qmin, smo_qmax=smo_qmax, qs_ref=qs_ref,
            qz_ref=qz_ref, kz_ref=kz_ref, vz_ref=vz_ref, ks_ref=ks_ref,
            vs_ref=vs_ref)


def _paged_call(lane_operands, lane_specs, k_arenas, v_arenas, *, b, kv, g,
                hd, nb, bs, s_cap, window, logit_softcap, quantized,
                sm_quant, smo_quant, sm_qmin, sm_qmax, smo_qmin, smo_qmax,
                block_table, q_pos, kv_bits=8, interpret=False,
                zero_points=()):
    """``lane_operands``: per-lane blocks (the queries first);
    ``k_arenas`` / ``v_arenas``: the payload arena (N, bs, KV, w), then for
    int8 its scales (N, KV, bs). The payload is handed over once per page
    of a compute block, each copy's index map gathering one page: Mosaic
    takes a BlockSpec whose minor dims span the arena's (KV, hd), but
    refuses a hand-made copy out of a slice of it unless hd is a multiple
    of 128 (h2o-danube3-4b's is 120)."""
    has_smq = sm_quant is not None
    has_smo = smo_quant is not None
    pages = pages_per_block(bs, quantized)
    n_walk = -(-nb // pages)
    maps = _page_maps(nb, bs, pages, n_walk, has_smo)
    operands = list(zero_points)
    specs = [SMEM] * len(zero_points)
    if has_smq:
        operands.append(sm_quant.astype(jnp.float32))
        specs.append(SMEM)
    if has_smo:
        operands.append(smo_quant.astype(jnp.float32))
        specs.append(SMEM)
    operands += lane_operands
    specs += lane_specs
    for which, arenas in enumerate((k_arenas, v_arenas)):
        payload, scales = arenas[0], arenas[1:]
        for i in range(pages):
            operands.append(payload)
            specs.append(pl.BlockSpec((1,) + payload.shape[1:],
                                      maps(i, payload.ndim)[which]))
        for sc in scales:               # int8: one page a block
            operands.append(sc)
            specs.append(pl.BlockSpec((1,) + sc.shape[1:],
                                      maps(0, sc.ndim)[which]))
    parts, w = lane_operands[0].shape[2], lane_operands[0].shape[-1]
    kernel = functools.partial(
        _paged_kernel, nb=nb, bs=bs, pages=pages, n_walk=n_walk,
        s_cap=s_cap, hd=hd, window=window, logit_softcap=logit_softcap,
        quantized=quantized, has_smq=has_smq, has_smo=has_smo,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_qmin=smo_qmin,
        smo_qmax=smo_qmax, kv_bits=kv_bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, 2 * n_walk if has_smo else n_walk),
        in_specs=specs,
        out_specs=_lane_spec(kv, parts, g, w),
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


def _lane_spec(*block):
    return pl.BlockSpec((1,) + block,
                        lambda i, kk, tbl, qp: (i,) + (0,) * len(block))


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
    (B, nb) int32 physical page per logical page (-1 = unmapped), where
    ``nb * bs`` covers ``s_cap`` (the layer's logical capacity =
    min(max_len, window) for ring layers); q_pos: (B,) query positions
    (-1 = idle lane -> zero contribution). Returns (B, KV, G, hd) f32.
    """
    b, kv, g, hd = q.shape
    bs = k_arena.shape[1]
    nb = block_table.shape[1]
    assert nb * bs >= s_cap, f"table covers {nb * bs} < s_cap={s_cap}"
    q_parts = split_queries(q.astype(jnp.float32), 8)
    return _paged_call(
        [q_parts], [_lane_spec(kv, 1, g, hd)], [k_arena], [v_arena], b=b,
        kv=kv, g=g, hd=hd, nb=nb, bs=bs, s_cap=s_cap, window=window,
        logit_softcap=logit_softcap, quantized=False, sm_quant=sm_quant,
        smo_quant=smo_quant, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_qmin=smo_qmin, smo_qmax=smo_qmax, block_table=block_table,
        q_pos=q_pos, interpret=interpret)


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
    per page. Returns (B, KV, G, hd) f32.
    """
    b, kv, g, hd = q_q.shape
    hd_kv = packed_len(hd) if kv_bits == 4 else hd
    assert k_arena.shape[-1] == hd_kv, (
        f"arena last dim {k_arena.shape[-1]} != {hd_kv}")
    bs = k_arena.shape[1]
    nb = block_table.shape[1]
    assert nb * bs >= s_cap, f"table covers {nb * bs} < s_cap={s_cap}"
    q_parts = split_queries(q_q, kv_bits)
    parts, w = q_parts.shape[2], q_parts.shape[-1]
    lane_operands = [q_parts, q_scale.astype(jnp.float32)[..., None],
                     q_zp.astype(jnp.float32)[..., None]]
    lane_specs = [_lane_spec(kv, parts, g, w), _lane_spec(kv, g, 1),
                  _lane_spec(kv, g, 1)]
    k_arenas = [k_arena, jnp.swapaxes(k_scale.astype(jnp.float32), 1, 2)]
    v_arenas = [v_arena, jnp.swapaxes(v_scale.astype(jnp.float32), 1, 2)]
    return _paged_call(
        lane_operands, lane_specs, k_arenas, v_arenas, b=b, kv=kv, g=g,
        hd=hd, nb=nb, bs=bs, s_cap=s_cap, window=window,
        logit_softcap=logit_softcap, quantized=True, sm_quant=sm_quant,
        smo_quant=smo_quant, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_qmin=smo_qmin, smo_qmax=smo_qmax, block_table=block_table,
        q_pos=q_pos, kv_bits=kv_bits, interpret=interpret,
        zero_points=(k_zp.astype(jnp.float32), v_zp.astype(jnp.float32)))
