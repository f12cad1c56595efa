"""Pallas TPU kernel: fused int8 decode attention over a quantized KV cache.

The serving decode step is HBM-bandwidth-bound: every new token re-reads the
whole KV cache. With the cache stored int8 (per-head, per-slot symmetric
scales — see ``repro.models.attention.QuantKVCache``) this kernel computes
the (B, 1, H, S) step as

    s[g, c]  = (q_q[g] . k_q[c]) * q_scale[g] * k_scale[c]     s8 x s8 -> s32
    s        = softcap(s);  s = fake_quant_{softmax_in}(s)     (optional)
    s        = mask(s)              causal + sliding-window from positions
    p        = online_softmax(s)    flash-style running (m, l) over S chunks
    p        = fake_quant_{softmax_out}(p)                     (optional)
    acc     += (p * v_scale) @ v_q                              dequant-on-read

so the int8 payloads and their f32 scales are the ONLY cache bytes read from
HBM — roughly half the traffic of a bf16 cache — and the q.k product runs on
the MXU in int8.

Layout: one program per (batch lane, kv-chunk); the grid's last axis walks
the S chunks so the running max / denominator / accumulator live in VMEM
scratch across chunk steps (same accumulation pattern as the int8 matmul
kernels). A program covers ALL kv heads: the cache's (KV, hd) minor dims
are blocked whole — Mosaic only tiles a block whose last two dims divide
(8, 128) or span the array — and each head is a strided (C, hd) load out of
the (C, KV, hd) chunk. Per-slot scales and key positions are handed over
with S minor ((B, KV, S) / (B, 1, S)) so one head's scales are a lane row
of the scores; per-lane scalars (query position, cache zero-points, the
softmax site grids) ride in SMEM. GQA is free: the q block for a kv head is
its (G, hd) group of query heads.

The paper's Fig.-1 attention quantization sites are applied IN-KERNEL with
traced scale / zero-point operands (no recompile per calibration), matching
the simulate path bit-for-bit:

  * ``softmax_in`` — fake-quant on the (soft-capped) logits, one VPU pass.
  * ``softmax_out`` — fake-quant on the *normalized* probabilities. This is
    impossible in one streaming pass (the denominator is only known after
    the last chunk), so when the site is calibrated the grid walks S twice:
    pass 1 accumulates the running (m, l), pass 2 recomputes the logits,
    quantizes ``exp(s - m) / l`` on the site grid and accumulates against
    V. The V block index is pinned during pass 1, so V still streams from
    HBM once; only K is read twice — ~1.5x the single-pass cache bytes.

The mask is causal-decode fixed (valid slot, k_pos <= q_pos, optional
sliding window). Non-causal configs and sites that need more than a
per-tensor scalar fall back to dequantize-then-flash
(repro.models.attention) — the simulate-path rule.

``kv_bits=4`` caches are split-half nibble-packed: each chunk unpacks into
its low / high int4 halves, and the head dim is processed as those two
parts (the queries arrive split to match), so no lane concatenate at the
unaligned half boundary is needed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nibble import packed_len, unpack_halves

NEG_INF = -1e30

SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _dot_t(a, b, acc_dtype):
    """a (M, K) . b (N, K)^T -> (M, N)."""
    # explicit either way: an enclosing jax.default_matmul_precision must
    # not reach the int8 MXU pass
    precision = (jax.lax.Precision.HIGHEST if acc_dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=acc_dtype)


def _dot(a, b):
    """a (M, K) . b (K, N) -> (M, N), f32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def split_queries(q, kv_bits: int):
    """(B, KV, G, hd) -> (B, KV, parts, G, w): one part of the full head dim
    for bf16 / int8 caches, the two split-half nibble parts (zero-padded to
    ``2 * ceil(hd/2)``) for ``kv_bits=4``."""
    b, kv, g, hd = q.shape
    if kv_bits != 4:
        return q.reshape(b, kv, 1, g, hd)
    w = packed_len(hd)
    if 2 * w != hd:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, 2 * w - hd)))
    return jnp.stack([q[..., :w], q[..., w:]], axis=2)


def merge_parts(out, hd: int):
    """Inverse of :func:`split_queries` on the (B, KV, parts, G, w) output."""
    b, kv, parts, g, w = out.shape
    if parts == 1:
        return out.reshape(b, kv, g, w)
    return jnp.concatenate([out[:, :, 0], out[:, :, 1]], axis=-1)[..., :hd]


def decode_attend_step(*, step, n_blocks, lane, valid, q_ref, k_refs,
                       v_refs, o_ref, m_ref, l_ref, acc_ref, hd: int,
                       quantized: bool, kv_bits: int,
                       logit_softcap: Optional[float], smq_ref, smo_ref,
                       sm_qmin: int, sm_qmax: int, smo_qmin: int,
                       smo_qmax: int, qs_ref=None, qz_ref=None, kz_ref=None,
                       vz_ref=None, ks_ref=None, vs_ref=None):
    """One step of online-softmax decode attention over one K/V block, for
    every kv head of one lane — shared by the dense and paged kernels.

    ``valid``: (1, C) bool mask of this block's cells. Blocks: q (1, KV,
    parts, G, w); k/v: the block's pages in order, each (1, c, KV, w), C
    cells in all; scales (1, KV, C); q scales / zps (1, KV, G, 1); kz/vz
    (B, KV) SMEM. Scratch: m/l (KV, G, 1), acc (KV * parts, G, w).
    ``step`` walks ``n_blocks`` (``2 * n_blocks`` with the two-pass
    softmax_out schedule); both may be traced."""
    kv, parts = q_ref.shape[1], q_ref.shape[2]
    has_smo = smo_ref is not None

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _parts(x):
        return list(unpack_halves(x)) if kv_bits == 4 else [x]

    def _cells(refs, h):
        """(C, w) cells of kv head h over the block's pages."""
        rows = [r[0, :, h, :] for r in refs]
        return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)

    for h in range(kv):
        qs = [q_ref[0, h, i] for i in range(parts)]             # (G, w)
        if quantized:
            ks = _parts(_cells(k_refs, h))                      # (C, w) int8
            s32 = sum(_dot_t(q, k, jnp.int32) for q, k in zip(qs, ks))
            # zero-point corrections (asymmetric q grid / static per-head k
            # grid):  sum (q - zq)(k - zk)
            #           = q.k - zq colsum(k) - zk rowsum(q) + hd zq zk
            # colsum comes off the MXU as a ones-row product (a lane-major
            # row, like the scores), rowsum off the VPU; both from ints
            # already in VMEM — no extra HBM traffic.
            ones = jnp.ones(qs[0].shape, jnp.int8)
            kcol = sum(_dot_t(ones, k, jnp.int32) for k in ks)  # (G, C)
            qrow = sum(jnp.sum(q.astype(jnp.int32), axis=-1, keepdims=True)
                       for q in qs)                              # (G, 1)
            zq = qz_ref[0, h]                                    # (G, 1)
            zk = kz_ref[lane, h]
            acc32 = (s32.astype(jnp.float32)
                     - zq * kcol.astype(jnp.float32)
                     - zk * qrow.astype(jnp.float32) + hd * zq * zk)
            s = acc32 * qs_ref[0, h] * ks_ref[0, h:h + 1, :]     # (G, C)
        else:
            s = _dot_t(qs[0].astype(jnp.float32),
                       _cells(k_refs, h).astype(jnp.float32), jnp.float32)
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if smq_ref is not None:
            sm_s = smq_ref[0]
            sm_z = smq_ref[1]
            sq = jnp.clip(jnp.round(s / sm_s) + sm_z, sm_qmin, sm_qmax)
            s = (sq - sm_z) * sm_s
        s = jnp.where(valid, s, NEG_INF)

        def _accumulate(pmat, decay, h=h):
            """acc[h] = acc[h] * decay + p @ V, with the int8 variant's
            dequant: per-slot v scales folded into p (G x C muls < C x hd)
            and the static v zero-point as a per-row correction."""
            if quantized:
                pmat = pmat * vs_ref[0, h:h + 1, :]
                zcorr = vz_ref[lane, h] * jnp.sum(pmat, axis=-1,
                                                  keepdims=True)
            for i, v in enumerate(_parts(_cells(v_refs, h))):
                pv = _dot(pmat, v.astype(jnp.float32))
                if quantized:
                    pv = pv - zcorr
                acc_ref[h * parts + i] = acc_ref[h * parts + i] * decay + pv

        @pl.when(step < n_blocks)
        def _stats_pass(h=h, s=s):
            # online max / denominator (flash accumulation); in single-pass
            # mode the numerator accumulates alongside.
            m_prev = m_ref[h]
            m_new = jnp.maximum(jnp.maximum(
                m_prev, jnp.max(s, axis=-1, keepdims=True)), NEG_INF)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if not has_smo:
                _accumulate(p, corr)

        if has_smo:
            @pl.when(step >= n_blocks)
            def _emit_pass(h=h, s=s):
                # second pass: (m, l) are final — quantize the normalized
                # probabilities on the softmax_out grid exactly like the
                # simulate path (which does NOT renormalize after fake-quant).
                p = jnp.exp(s - m_ref[h]) / jnp.maximum(l_ref[h], 1e-30)
                so_s = smo_ref[0]
                so_z = smo_ref[1]
                pq = jnp.clip(jnp.round(p / so_s) + so_z, smo_qmin, smo_qmax)
                _accumulate((pq - so_z) * so_s, 1.0)

    last = 2 * n_blocks - 1 if has_smo else n_blocks - 1

    @pl.when(step == last)
    def _done():
        for h in range(kv):
            denom = 1.0 if has_smo else jnp.maximum(l_ref[h], 1e-30)
            for i in range(parts):
                o_ref[0, h, i] = acc_ref[h * parts + i] / denom


def decode_scratch(kv: int, parts: int, g: int, w: int):
    return [pltpu.VMEM((kv, g, 1), jnp.float32),          # running max
            pltpu.VMEM((kv, g, 1), jnp.float32),          # running denom
            pltpu.VMEM((kv * parts, g, w), jnp.float32)]  # numerator


def _attend_decode_kernel(*refs, n_chunks: int, hd: int,
                          window: Optional[int],
                          logit_softcap: Optional[float], has_smq: bool,
                          has_smo: bool, sm_qmin: int, sm_qmax: int,
                          smo_qmin: int, smo_qmax: int, kv_bits: int):
    refs = list(refs)
    qp_ref, kz_ref, vz_ref = refs[:3]
    refs = refs[3:]
    smq_ref = refs.pop(0) if has_smq else None
    smo_ref = refs.pop(0) if has_smo else None
    (q_ref, qs_ref, qz_ref, k_ref, ks_ref, v_ref, vs_ref, kp_ref,
     o_ref, m_ref, l_ref, acc_ref) = refs

    lane = pl.program_id(0)
    kp = kp_ref[0]                                     # (1, C) int32
    qp = qp_ref[lane]
    valid = (kp >= 0) & (kp <= qp)
    if window is not None:
        valid &= kp > qp - window
    decode_attend_step(
        step=pl.program_id(1), n_blocks=n_chunks, lane=lane, valid=valid,
        q_ref=q_ref, k_refs=[k_ref], v_refs=[v_ref], o_ref=o_ref,
        m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref, hd=hd, quantized=True,
        kv_bits=kv_bits, logit_softcap=logit_softcap, smq_ref=smq_ref,
        smo_ref=smo_ref, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
        smo_qmin=smo_qmin, smo_qmax=smo_qmax, qs_ref=qs_ref, qz_ref=qz_ref,
        kz_ref=kz_ref, vz_ref=vz_ref, ks_ref=ks_ref, vs_ref=vs_ref)


def int8_attend_decode(q_q: jnp.ndarray, q_scale: jnp.ndarray,
                       q_zp: jnp.ndarray, k_zp: jnp.ndarray,
                       v_zp: jnp.ndarray,
                       k_q: jnp.ndarray, k_scale: jnp.ndarray,
                       v_q: jnp.ndarray, v_scale: jnp.ndarray,
                       k_pos: jnp.ndarray, q_pos: jnp.ndarray, *,
                       window: Optional[int] = None,
                       logit_softcap: Optional[float] = None,
                       sm_quant: Optional[jnp.ndarray] = None,
                       sm_qmin: int = 0, sm_qmax: int = 255,
                       smo_quant: Optional[jnp.ndarray] = None,
                       smo_qmin: int = 0, smo_qmax: int = 255,
                       chunk: int = 256, kv_bits: int = 8,
                       interpret: bool = False) -> jnp.ndarray:
    """One decode step of attention against an int8 KV cache.

    q_q: (B, KV, G, hd) int8 queries, grouped per kv head (GQA);
    q_scale: (B, KV, G) f32 per-query-head scales with the attention
    1/sqrt(hd) factor already folded in; q_zp: (B, KV, G) f32 zero-points on
    the shifted int8 grid (0 = symmetric); k_zp/v_zp: (B, KV) f32 static
    per-head zero-points of the cache grids (0 = symmetric). All three are
    corrected in-kernel with rowsum/colsum scalars computed from the int8
    payloads already in VMEM, so affine site grids dequantize exactly with
    zero extra HBM traffic and a zero-point-free per-slot payload.
    k_q/v_q: (B, S, KV, hd) int8 cache; k_scale/v_scale: (B, S, KV) f32
    per-head per-slot scales; k_pos: (B, S) absolute positions (-1 = empty
    slot); q_pos: (B,) query positions. sm_quant / smo_quant: optional (2,) f32 [scale, zero_point]
    for the in-kernel ``softmax_in`` / ``softmax_out`` fake-quant on their
    [qmin, qmax] grids (softmax_out switches to the two-pass schedule).
    ``kv_bits=4`` reads a nibble-packed cache — k_q/v_q (B, S, KV, hd//2)
    int8 with two int4 cells per byte (split-half layout) — and unpacks
    each chunk in VMEM before the MXU q.k^T; scales/zero-points keep their
    8-bit shapes. Returns (B, KV, G, hd) f32. S must be a multiple of
    ``chunk`` (the ops wrapper pads with k_pos = -1 slots); a chunk shorter
    than S must be a multiple of 128 (the scales' lane tile).
    """
    b, kv, g, hd = q_q.shape
    hd_kv = packed_len(hd) if kv_bits == 4 else hd
    assert k_q.shape[-1] == hd_kv, (k_q.shape, hd_kv)
    s_len = k_q.shape[1]
    c = min(chunk, s_len)
    assert s_len % c == 0, f"S={s_len} not a multiple of chunk={c}"
    n_chunks = s_len // c
    has_smq = sm_quant is not None
    has_smo = smo_quant is not None
    n_steps = 2 * n_chunks if has_smo else n_chunks

    q_parts = split_queries(q_q, kv_bits)
    parts, w = q_parts.shape[2], q_parts.shape[-1]
    operands = [q_pos.astype(jnp.int32), k_zp.astype(jnp.float32),
                v_zp.astype(jnp.float32)]
    in_specs = [SMEM, SMEM, SMEM]
    if has_smq:
        operands.append(sm_quant.astype(jnp.float32))
        in_specs.append(SMEM)
    if has_smo:
        operands.append(smo_quant.astype(jnp.float32))
        in_specs.append(SMEM)
    operands += [q_parts, q_scale.astype(jnp.float32)[..., None],
                 q_zp.astype(jnp.float32)[..., None], k_q,
                 jnp.swapaxes(k_scale.astype(jnp.float32), 1, 2), v_q,
                 jnp.swapaxes(v_scale.astype(jnp.float32), 1, 2),
                 k_pos.astype(jnp.int32)[:, None, :]]
    # the chunk axis folds modulo n_chunks so the two-pass schedule re-walks
    # the same S blocks for K; V pins to block 0 during the stats pass (its
    # block index then doesn't change, so the pipeline fetches it only once
    # per program there — V streams from HBM once overall, K twice)
    ck = (lambda kk: kk % n_chunks) if has_smo else (lambda kk: kk)
    cv = (lambda kk: jnp.maximum(kk - n_chunks, 0)) if has_smo \
        else (lambda kk: kk)
    in_specs += [
        pl.BlockSpec((1, kv, parts, g, w), lambda i, kk: (i, 0, 0, 0, 0)),
        pl.BlockSpec((1, kv, g, 1), lambda i, kk: (i, 0, 0, 0)),    # q_s
        pl.BlockSpec((1, kv, g, 1), lambda i, kk: (i, 0, 0, 0)),    # q_z
        pl.BlockSpec((1, c, kv, hd_kv), lambda i, kk: (i, ck(kk), 0, 0)),
        pl.BlockSpec((1, kv, c), lambda i, kk: (i, 0, ck(kk))),     # k_s
        pl.BlockSpec((1, c, kv, hd_kv), lambda i, kk: (i, cv(kk), 0, 0)),
        pl.BlockSpec((1, kv, c), lambda i, kk: (i, 0, cv(kk))),     # v_s
        pl.BlockSpec((1, 1, c), lambda i, kk: (i, 0, ck(kk))),      # k_pos
    ]

    kernel = functools.partial(
        _attend_decode_kernel, n_chunks=n_chunks, hd=hd, window=window,
        logit_softcap=logit_softcap, has_smq=has_smq, has_smo=has_smo,
        sm_qmin=sm_qmin, sm_qmax=sm_qmax, smo_qmin=smo_qmin,
        smo_qmax=smo_qmax, kv_bits=kv_bits)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, kv, parts, g, w), jnp.float32),
        grid=(b, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, parts, g, w),
                               lambda i, kk: (i, 0, 0, 0, 0)),
        scratch_shapes=decode_scratch(kv, parts, g, w),
        interpret=interpret,
        name="int8_attend_decode",
    )(*operands)
    return merge_parts(out, hd)
