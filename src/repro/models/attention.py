"""Multi-head attention: MHA / GQA / MQA, sliding-window, local+global,
logit soft-capping, RoPE, KV cache (full + ring-buffer windowed), and a
flash-style chunked path (online softmax over KV chunks via lax.scan) so long
contexts never materialize the (T, S) score matrix.

Absolute positions drive both masking and cache writes, and position -1
marks a DEAD cell — a pad token inside a left-packed prompt or an idle
decode lane: dead cells are masked out of attention (every path checks
k_pos >= 0) and their KV-cache writes are dropped (_write_slots). That
sentinel is the lane-safety contract the continuous-batching scheduler
builds on (runtime/serve_loop.py): a slot-insert prefill or a masked decode
step can never perturb co-resident lanes' caches.

Quantization sites (paper Fig. 1 naming) are threaded via QuantCtx:
  {prefix}/q, {prefix}/k, {prefix}/v       — linear outputs
  {prefix}/softmax_in, {prefix}/softmax_out
  {prefix}/ctx_out                          — self-attention output (after Wo)
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, softcap

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    causal: bool = True
    window: Optional[int] = None          # sliding-window size (None = global)
    logit_softcap: Optional[float] = None # gemma-2 style
    rope_theta: Optional[float] = 10000.0 # None = no RoPE (e.g. BERT)
    query_scale: Optional[float] = None   # default 1/sqrt(head_dim)

    @property
    def q_groups(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def scale(self) -> float:
        return (self.query_scale if self.query_scale is not None
                else 1.0 / math.sqrt(self.head_dim))


class KVCache(NamedTuple):
    """k/v: (B, S, KV, hd); pos: (B, S) absolute positions (-1 = empty).
    S = max_len for global attention, window size for sliding-window."""
    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray


class QuantKVCache(NamedTuple):
    """Int8 KV cache (deployment serving path): k_q/v_q (B, S, KV, hd) int8
    payloads with zero-point-free symmetric per-head, per-slot scales k_s/v_s
    (B, S, KV) f32; pos as in :class:`KVCache`. Symmetry keeps the zero-point
    colsum correction out of the decode kernel's S-loop; per-slot scales make
    the write a pure in-place quantize (ring-buffer slots included)."""
    k_q: jnp.ndarray
    v_q: jnp.ndarray
    k_s: jnp.ndarray
    v_s: jnp.ndarray
    pos: jnp.ndarray


class PagedKVCache(NamedTuple):
    """Block-paged bf16/f32 KV cache: k/v (N, bs, KV, hd) — one shared
    arena of N physical blocks of bs token cells, NO batch axis. Which
    blocks back which decode lane is data: the (B, max_blocks) int32 block
    table (-1 = unmapped) that travels inside the whole-model cache pytree
    (runtime.block_pool.BlockPool allocates it host-side), so lanes own
    bytes proportional to their LIVE tokens, not to max_len. ``pos``
    (N, bs) keeps the per-cell dead-cell sentinel (-1) of :class:`KVCache`;
    the read paths additionally derive validity from (logical index,
    q_pos) alone — see paged_key_positions — so a freshly grown block's
    stale cells are unreadable even before any write touches them."""
    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray


class PagedQuantKVCache(NamedTuple):
    """Paged int8 KV cache: :class:`QuantKVCache` payloads/scales laid out
    over the shared block arena of :class:`PagedKVCache` — k_q/v_q
    (N, bs, KV, hd) int8, k_s/v_s (N, bs, KV) f32, pos (N, bs)."""
    k_q: jnp.ndarray
    v_q: jnp.ndarray
    k_s: jnp.ndarray
    v_s: jnp.ndarray
    pos: jnp.ndarray


class Quant4KVCache(QuantKVCache):
    """Packed int4 KV cache: same fields and scale layout as
    :class:`QuantKVCache` but k_q/v_q hold two int4 cells per byte —
    (B, S, KV, hd/2) split-half nibble payloads (repro.kernels.nibble).
    The TYPE is the bit-width marker: every isinstance check on the int8
    base class still applies (write/reset/reads), and the decode paths
    select ``kv_bits=4`` kernels plus the int4 quantizer by this subclass.
    JAX tree ops rebuild namedtuples as ``type(x)(*children)``, so the
    marker survives jit/scan/donation."""


class PagedQuant4KVCache(PagedQuantKVCache):
    """Paged packed int4 KV cache: :class:`Quant4KVCache` payloads over the
    shared block arena — k_q/v_q (N, bs, KV, hd/2) nibble-packed int8,
    k_s/v_s (N, bs, KV) f32, pos (N, bs). Halves arena HBM per block, so a
    pool of the same byte budget holds ~2x the resident decode lanes."""


def init_kv_cache(batch: int, max_len: int, cfg: AttnConfig,
                  dtype=jnp.bfloat16) -> KVCache:
    size = min(max_len, cfg.window) if cfg.window else max_len
    return KVCache(
        k=jnp.zeros((batch, size, cfg.num_kv_heads, cfg.head_dim), dtype),
        v=jnp.zeros((batch, size, cfg.num_kv_heads, cfg.head_dim), dtype),
        pos=jnp.full((batch, size), -1, jnp.int32))


def init_quant_kv_cache(batch: int, max_len: int,
                        cfg: AttnConfig) -> QuantKVCache:
    size = min(max_len, cfg.window) if cfg.window else max_len
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return QuantKVCache(
        k_q=jnp.zeros((batch, size, kv, hd), jnp.int8),
        v_q=jnp.zeros((batch, size, kv, hd), jnp.int8),
        k_s=jnp.zeros((batch, size, kv), jnp.float32),
        v_s=jnp.zeros((batch, size, kv), jnp.float32),
        pos=jnp.full((batch, size), -1, jnp.int32))


def init_paged_kv_cache(num_blocks: int, block_size: int, cfg: AttnConfig,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return PagedKVCache(
        k=jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        v=jnp.zeros((num_blocks, block_size, kv, hd), dtype),
        pos=jnp.full((num_blocks, block_size), -1, jnp.int32))


def init_paged_quant_kv_cache(num_blocks: int, block_size: int,
                              cfg: AttnConfig) -> PagedQuantKVCache:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return PagedQuantKVCache(
        k_q=jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
        v_q=jnp.zeros((num_blocks, block_size, kv, hd), jnp.int8),
        k_s=jnp.zeros((num_blocks, block_size, kv), jnp.float32),
        v_s=jnp.zeros((num_blocks, block_size, kv), jnp.float32),
        pos=jnp.full((num_blocks, block_size), -1, jnp.int32))


def init_quant4_kv_cache(batch: int, max_len: int,
                         cfg: AttnConfig) -> Quant4KVCache:
    size = min(max_len, cfg.window) if cfg.window else max_len
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    assert hd % 2 == 0, f"int4 KV cache needs even head_dim, got {hd}"
    return Quant4KVCache(
        k_q=jnp.zeros((batch, size, kv, hd // 2), jnp.int8),
        v_q=jnp.zeros((batch, size, kv, hd // 2), jnp.int8),
        k_s=jnp.zeros((batch, size, kv), jnp.float32),
        v_s=jnp.zeros((batch, size, kv), jnp.float32),
        pos=jnp.full((batch, size), -1, jnp.int32))


def init_paged_quant4_kv_cache(num_blocks: int, block_size: int,
                               cfg: AttnConfig) -> PagedQuant4KVCache:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    assert hd % 2 == 0, f"int4 KV cache needs even head_dim, got {hd}"
    return PagedQuant4KVCache(
        k_q=jnp.zeros((num_blocks, block_size, kv, hd // 2), jnp.int8),
        v_q=jnp.zeros((num_blocks, block_size, kv, hd // 2), jnp.int8),
        k_s=jnp.zeros((num_blocks, block_size, kv), jnp.float32),
        v_s=jnp.zeros((num_blocks, block_size, kv), jnp.float32),
        pos=jnp.full((num_blocks, block_size), -1, jnp.int32))


def paged_capacity(block_table, block_size: int,
                   window: Optional[int]) -> int:
    """A layer's logical capacity S over a paged cache: the block table
    covers max_blocks*bs cells; ring (sliding-window) layers wrap at the
    window exactly like the dense sized-to-window cache."""
    cap = block_table.shape[-1] * block_size
    return min(cap, window) if window else cap


def quantize_kv(x, grid_scale=None, zero_point=None):
    """Per-head (last-two-axes: ..., KV, hd) int8 quantization.

    Without calibration each (token, kv-head) vector gets its own symmetric
    scale amax/127. With a calibrated site grid (``grid_scale`` +
    ``zero_point`` from deploy.kv_quant_for, both broadcastable over (KV,))
    the write re-uses the site's affine grid shifted onto int8 — values the
    simulate path already fake-quantized then store EXACTLY, so the int8
    cache adds no storage error on the deploy path. The zero-point is NOT
    stored per slot; it is static per head and corrected inside the decode
    kernel. Returns (q int8, scale f32 x.shape[:-1]).
    """
    xf = x.astype(jnp.float32)
    if zero_point is not None:
        s = jnp.broadcast_to(jnp.asarray(grid_scale, jnp.float32),
                             xf.shape[:-1])
        z = jnp.asarray(zero_point, jnp.float32)
        q = jnp.clip(jnp.round(xf / s[..., None]) + z[..., None],
                     -128, 127).astype(jnp.int8)
        return q, s
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = amax / 127.0
    if grid_scale is not None:
        s = jnp.maximum(s, jnp.asarray(grid_scale, jnp.float32))
    s = jnp.maximum(s, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def quantize_kv4(x, grid_scale=None, zero_point=None):
    """Per-head int4 quantization + split-half nibble pack: the 4-bit twin
    of :func:`quantize_kv`. Calibrated grids (from deploy.kv_quant_for with
    bits=4, zero-point already shifted onto the int4 grid) clip to [-8, 7];
    dynamic symmetric uses amax/7 on [-7, 7]. Returns
    (packed int8 (..., hd/2), scale f32 x.shape[:-1])."""
    from repro.kernels.nibble import pack_nibbles
    xf = x.astype(jnp.float32)
    if zero_point is not None:
        s = jnp.broadcast_to(jnp.asarray(grid_scale, jnp.float32),
                             xf.shape[:-1])
        z = jnp.asarray(zero_point, jnp.float32)
        q = jnp.clip(jnp.round(xf / s[..., None]) + z[..., None],
                     -8, 7).astype(jnp.int8)
        return pack_nibbles(q), s
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = amax / 7.0
    if grid_scale is not None:
        s = jnp.maximum(s, jnp.asarray(grid_scale, jnp.float32))
    s = jnp.maximum(s, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(xf / s[..., None]), -7, 7).astype(jnp.int8)
    return pack_nibbles(q), s


def dequantize_kv(cache: QuantKVCache, kvq=None):
    """(k, v) f32 views of a quantized cache (the fallback read path).
    ``kvq``: the deploy.KVQuant whose static zero-points the cache was
    written with (None = symmetric dynamic writes). Packed int4 caches
    unpack their nibbles first (hd = 2 * stored payload width)."""
    kq, vq = cache.k_q, cache.v_q
    if isinstance(cache, Quant4KVCache):
        from repro.kernels.nibble import unpack_nibbles
        hd = 2 * kq.shape[-1]
        kq = unpack_nibbles(kq, hd)
        vq = unpack_nibbles(vq, hd)
    kq = kq.astype(jnp.float32)
    vq = vq.astype(jnp.float32)
    if kvq is not None:
        kq = kq - jnp.asarray(kvq.k_zp, jnp.float32)[..., None]
        vq = vq - jnp.asarray(kvq.v_zp, jnp.float32)[..., None]
    return kq * cache.k_s[..., None], vq * cache.v_s[..., None]


def _mask(q_pos, k_pos, cfg: AttnConfig):
    """Boolean validity mask (..., T, S) from absolute positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    valid = kp >= 0
    if cfg.causal:
        valid &= kp <= qp
    if cfg.window is not None:
        valid &= kp > qp - cfg.window
    return valid


def _dense_attend(q, k, v, q_pos, k_pos, cfg: AttnConfig, ctx=None, prefix=""):
    """q: (B,T,H,hd), k/v: (B,S,KV,hd). Returns (B,T,H,hd)."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    KV, G = cfg.num_kv_heads, cfg.q_groups
    qg = q.reshape(B, T, KV, G, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * cfg.scale
    logits = softcap(logits, cfg.logit_softcap)
    if ctx is not None:
        logits = ctx.act(f"{prefix}/softmax_in", logits)
    valid = _mask(q_pos, k_pos, cfg)[:, None, None]     # (B,1,1,T,S)
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if ctx is not None:
        probs = ctx.act(f"{prefix}/softmax_out", probs)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(jnp.float32),
                     v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def _chunked_attend(q, k, v, q_pos, k_pos, cfg: AttnConfig,
                    kv_chunk: int = 1024):
    """Flash-style online-softmax scan over KV chunks; never materializes
    the full (T, S) score matrix. Numerically matches _dense_attend."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    KV, G = cfg.num_kv_heads, cfg.q_groups
    qg = q.reshape(B, T, KV, G, hd).astype(jnp.float32) * cfg.scale

    n_chunks = -(-S // kv_chunk)
    pad = n_chunks * kv_chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)

    ks = k.reshape(B, n_chunks, kv_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_chunks, kv_chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    ps = k_pos.reshape(B, n_chunks, kv_chunk).transpose(1, 0, 2)

    def step(carry, chunk):
        m, l, acc = carry                       # running max / denom / numer
        kc, vc, pc = chunk                      # (B,C,KV,hd), (B,C)
        s = jnp.einsum("btkgd,bckd->bkgtc", qg, kc.astype(jnp.float32))
        s = softcap(s, cfg.logit_softcap)
        valid = _mask(q_pos, pc, cfg)[:, None, None]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows: keep m finite
        m_new = jnp.maximum(m_new, -1e30)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgtc,bckd->bkgtd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (ks, vs, ps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd).astype(q.dtype)


def _banded_attend(q, k, v, q_pos, k_pos, cfg: AttnConfig,
                   block: int = 1024):
    """Sliding-window attention that COMPUTES only the band (perf variant):
    queries are processed in blocks of ``block``; each block attends only to
    the kv blocks that can intersect its window — O(T·W) flops/bytes instead
    of O(T²). Requires aligned q/k (self-attention layout, q_pos == k_pos ==
    arange) and cfg.window set.
    """
    B, T, H, hd = q.shape
    W = cfg.window
    assert W is not None
    nq = -(-T // block)
    pad = nq * block - T
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-10**9)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    Tp = nq * block
    nband = -(-W // block) + 1            # kv blocks a q block can reach
    KV = cfg.num_kv_heads

    qb = q.reshape(B, nq, block, H, hd)
    kb = k.reshape(B, nq, block, KV, hd)
    vb = v.reshape(B, nq, block, KV, hd)
    qp = q_pos.reshape(B, nq, block)
    kp = k_pos.reshape(B, nq, block)

    # band gather: for q block i, kv blocks [i-nband+1 .. i] (causal window)
    idx = jnp.arange(nq)[:, None] - (nband - 1) + jnp.arange(nband)[None, :]
    valid_blk = idx >= 0
    idx_c = jnp.clip(idx, 0, nq - 1)
    k_band = kb[:, idx_c].reshape(B, nq, nband * block, KV, hd)
    v_band = vb[:, idx_c].reshape(B, nq, nband * block, KV, hd)
    kp_band = jnp.where(valid_blk[None, :, :, None], kp[:, idx_c], -1)
    kp_band = kp_band.reshape(B, nq, nband * block)

    # fold (B, nq) into the batch dim and reuse the dense kernel per band
    q2 = qb.reshape(B * nq, block, H, hd)
    k2 = k_band.reshape(B * nq, nband * block, KV, hd)
    v2 = v_band.reshape(B * nq, nband * block, KV, hd)
    qp2 = qp.reshape(B * nq, block)
    kp2 = kp_band.reshape(B * nq, nband * block)
    out = _dense_attend(q2, k2, v2, qp2, kp2, cfg)
    return out.reshape(B, Tp, H, hd)[:, :T]


def attend(q, k, v, q_pos, k_pos, cfg: AttnConfig, *, ctx=None, prefix="",
           chunked: Optional[bool] = None, kv_chunk: int = 1024,
           banded: bool = False):
    """Dispatch dense vs chunked vs banded. Dense supports quant sites;
    chunked is the long-context path (online softmax, no (T,S)
    materialization); banded computes only the sliding-window band
    (perf variant, requires cfg.window and self-attention layout)."""
    T, S = q.shape[1], k.shape[1]
    if (banded or chunked == "banded") and cfg.window is not None \
            and T == S and T > cfg.window:
        return _banded_attend(q, k, v, q_pos, k_pos, cfg)
    if chunked is None or chunked == "banded":
        chunked = (T * S > 4096 * 4096)
    if chunked:
        return _chunked_attend(q, k, v, q_pos, k_pos, cfg, kv_chunk)
    return _dense_attend(q, k, v, q_pos, k_pos, cfg, ctx, prefix)


# ---------------------------------------------------------------------------
# Quantized-cache write / decode paths
# ---------------------------------------------------------------------------

def _write_slots(pw, S, window):
    """Cache slot index per new token from its absolute position. Dead cells
    (position < 0: prompt pads and idle decode lanes) are routed out of
    bounds so the scatter DROPS them — the lane-safety contract behind the
    slot-insert prefill and the masked decode step (a cell with pw == -1
    neither attends nor writes, so co-resident lanes pass through
    bit-identical)."""
    base = pw % S if window else pw
    return jnp.where(pw >= 0, base, S)


def _quantize_kv_writes(cache, k_new, v_new, kvq):
    """(kq, ks, vq, vs) on the cache's own grid: packed int4 for the
    Quant4 subclasses, int8 otherwise (``kvq``: calibrated clip ranges)."""
    qfn = quantize_kv4 \
        if isinstance(cache, (Quant4KVCache, PagedQuant4KVCache)) \
        else quantize_kv
    if kvq is None:
        kq, ks = qfn(k_new)
        vq, vs = qfn(v_new)
    else:
        kq, ks = qfn(k_new, kvq.k_grid, kvq.k_zp)
        vq, vs = qfn(v_new, kvq.v_grid, kvq.v_zp)
    return kq, ks, vq, vs


def _write_kv(cache, k_new, v_new, pw, slots, bidx, kvq):
    """Scatter new K/V tokens into the cache slots. Quantized caches write
    quantize in place (per-head per-slot scales, ring-buffer slots included;
    int4 subclasses nibble-pack); ``kvq`` optionally carries the calibrated
    per-head clip ranges. The result is rebuilt as ``type(cache)`` so the
    bit-width-marker subclass survives the write.
    Out-of-bounds slots (dead cells, see _write_slots) are dropped."""
    with jax.named_scope("kv_write"):
        if isinstance(cache, QuantKVCache):
            kq, ks, vq, vs = _quantize_kv_writes(cache, k_new, v_new, kvq)
            return type(cache)(
                k_q=cache.k_q.at[bidx, slots].set(kq, mode="drop"),
                v_q=cache.v_q.at[bidx, slots].set(vq, mode="drop"),
                k_s=cache.k_s.at[bidx, slots].set(ks, mode="drop"),
                v_s=cache.v_s.at[bidx, slots].set(vs, mode="drop"),
                pos=cache.pos.at[bidx, slots].set(pw, mode="drop"))
        return KVCache(
            k=cache.k.at[bidx, slots].set(k_new.astype(cache.k.dtype),
                                          mode="drop"),
            v=cache.v.at[bidx, slots].set(v_new.astype(cache.v.dtype),
                                          mode="drop"),
            pos=cache.pos.at[bidx, slots].set(pw, mode="drop"))


def _write_paged_kv(cache, k_new, v_new, pw, block_table, window, kvq):
    """Scatter new K/V tokens into the paged arena via the lane's block
    table. The logical cell is ``pw % S`` (the dense _write_slots wrap
    rule — global layers never wrap in a capacity-checked workload); its
    physical block comes from the lane's table. Dead cells (pw < 0) and
    unmapped blocks route to ``num_blocks`` so the scatter DROPS them —
    the same lane-safety contract as the dense path. Quantized arenas
    quantize in place exactly like _write_kv."""
    with jax.named_scope("kv_write"):
        num_blocks, bs = cache.pos.shape
        s_cap = paged_capacity(block_table, bs, window)
        L = jnp.mod(jnp.maximum(pw, 0), s_cap)
        phys = jnp.take_along_axis(block_table, L // bs, axis=1)  # (B, T)
        dead = (pw < 0) | (phys < 0)
        phys = jnp.where(dead, num_blocks, phys)
        cell = L % bs
        if isinstance(cache, PagedQuantKVCache):
            kq, ks, vq, vs = _quantize_kv_writes(cache, k_new, v_new, kvq)
            return type(cache)(
                k_q=cache.k_q.at[phys, cell].set(kq, mode="drop"),
                v_q=cache.v_q.at[phys, cell].set(vq, mode="drop"),
                k_s=cache.k_s.at[phys, cell].set(ks, mode="drop"),
                v_s=cache.v_s.at[phys, cell].set(vs, mode="drop"),
                pos=cache.pos.at[phys, cell].set(pw, mode="drop"))
        return PagedKVCache(
            k=cache.k.at[phys, cell].set(k_new.astype(cache.k.dtype),
                                         mode="drop"),
            v=cache.v.at[phys, cell].set(v_new.astype(cache.v.dtype),
                                         mode="drop"),
            pos=cache.pos.at[phys, cell].set(pw, mode="drop"))


def paged_key_positions(block_table, q_pos, s_cap: int, block_size: int):
    """Derived key positions (B, nb*bs) of each lane's dense block view.

    A lane writes positions 0..q_pos contiguously (left-pad dead cells are
    dropped, not stored), so logical cell L holds position
    ``p = q_pos - ((q_pos - L) mod S)`` — reconstructed validity that can
    never read a reallocated block's stale cells, because stale cells
    derive p < 0 / L >= S. Idle lanes (q_pos = -1) derive all -1.
    """
    nb = -(-s_cap // block_size)
    L = jnp.arange(nb * block_size, dtype=jnp.int32)[None, :]
    qp = jnp.asarray(q_pos, jnp.int32).reshape(-1, 1)
    p = qp - jnp.mod(qp - L, s_cap)
    mapped = jnp.repeat(block_table[:, :nb] >= 0, block_size, axis=1)
    valid = (L < s_cap) & (p >= 0) & mapped
    return jnp.where(valid, p, -1)


def paged_gather_kv(cache, block_table, window, kvq=None):
    """Dense (B, nb*bs, KV, hd) f32 view of each lane's mapped blocks (the
    fallback read path when the paged kernels cannot express a site) —
    quantized arenas dequantize on gather. Pair with paged_key_positions
    to mask unwritten/stale cells."""
    num_blocks, bs = cache.pos.shape
    s_cap = paged_capacity(block_table, bs, window)
    nb = -(-s_cap // bs)
    phys = jnp.clip(block_table[:, :nb], 0, num_blocks - 1)

    def g(arena):
        x = arena[phys]                                # (B, nb, bs, ...)
        return x.reshape(x.shape[0], nb * bs, *arena.shape[2:])

    if isinstance(cache, PagedQuantKVCache):
        kq, vq = g(cache.k_q), g(cache.v_q)
        if isinstance(cache, PagedQuant4KVCache):
            from repro.kernels.nibble import unpack_nibbles
            hd = 2 * kq.shape[-1]
            kq = unpack_nibbles(kq, hd)
            vq = unpack_nibbles(vq, hd)
        kq = kq.astype(jnp.float32)
        vq = vq.astype(jnp.float32)
        if kvq is not None:
            kq = kq - jnp.asarray(kvq.k_zp, jnp.float32)[..., None]
            vq = vq - jnp.asarray(kvq.v_zp, jnp.float32)[..., None]
        return kq * g(cache.k_s)[..., None], vq * g(cache.v_s)[..., None]
    return g(cache.k).astype(jnp.float32), g(cache.v).astype(jnp.float32)


def reset_paged_lanes(cache, lane_mask, block_table):
    """Empty every block mapped by the masked lanes: ``pos`` -> -1 on those
    blocks' cells (payload bytes stay, as in reset_kv_lanes — an empty
    position masks the cell out of every read path). Works for unstacked
    (N, bs) and stacked (n_super, N, bs) arena layouts; the block table
    itself is host-owned (runtime.block_pool) and not touched here."""
    with jax.named_scope("kv_write"):
        num_blocks = cache.pos.shape[-2]
        mask = jnp.asarray(lane_mask, bool)[:, None]
        blocks = jnp.where(mask & (block_table >= 0), block_table,
                           num_blocks).reshape(-1)
        if cache.pos.ndim == 3:       # stacked scan leaf (n_super, N, bs)
            pos = cache.pos.at[:, blocks].set(-1, mode="drop")
        else:
            pos = cache.pos.at[blocks].set(-1, mode="drop")
        return cache._replace(pos=pos)


def reset_kv_lanes(cache, lane_mask, batch_axis: int = 0):
    """Empty the masked batch lanes of a (Quant)KVCache for slot reuse:
    ``pos`` -> -1 on those lanes. Payload bytes (and int8 scales) are left in
    place — an empty position masks the slot out of every read path (dense /
    chunked / fused int8 kernel), so stale K/V from a retired request can
    never leak into the next occupant. ``lane_mask``: (B,) bool;
    ``batch_axis``: where B sits in ``pos`` (1 for stacked scan leaves)."""
    shape = [1] * cache.pos.ndim
    shape[batch_axis] = lane_mask.shape[0]
    m = jnp.reshape(lane_mask, shape)
    return cache._replace(pos=jnp.where(m, -1, cache.pos))


def _sites_active(ctx):
    if ctx is None or not ctx.act_state:
        return False
    from repro.core.calibration import Mode
    return ctx.mode in (Mode.APPLY, Mode.DEPLOY)


def _site_quant(ctx, site):
    """((scale, zp) (2,), qmin, qmax) for an in-kernel fake-quant site;
    (None, 0, 0) when the site is inactive; ``False`` when calibrated but not
    expressible by the kernel (per-channel / PEG) — the caller then falls
    back to dequantize-then-attend so the site still applies."""
    qp = ctx.act_state.get(site)
    acfg = ctx.policy.act_config(site)
    if qp is None or not acfg.enabled:
        return None, 0, 0
    if jnp.size(qp.scale) != 1 or qp.group_index is not None:
        return False
    sm = jnp.stack([jnp.reshape(jnp.asarray(qp.scale, jnp.float32), ()),
                    jnp.reshape(jnp.asarray(qp.zero_point, jnp.float32), ())])
    return sm, acfg.qmin, acfg.qmax


def _q_site_quant(ctx, prefix):
    """(scale, shifted zero-point, qmin, qmax, shift) of the calibrated
    per-tensor ``{prefix}/q`` site, or None. Re-using the site's own affine
    grid (shifted onto int8, zero-point corrected in-kernel) makes already
    fake-quantized queries enter the kernel EXACTLY — no second rounding."""
    qp = ctx.act_state.get(f"{prefix}/q")
    acfg = ctx.policy.act_config(f"{prefix}/q")
    if qp is None or not acfg.enabled or acfg.bits != 8 \
            or jnp.size(qp.scale) != 1:
        return None
    shift = 128 if acfg.qmin == 0 else 0
    return (jnp.reshape(jnp.asarray(qp.scale, jnp.float32), ()),
            jnp.reshape(jnp.asarray(qp.zero_point, jnp.float32), ()),
            acfg.qmin, acfg.qmax, shift)


def _decode_site_params(ctx, prefix):
    """The in-kernel softmax site operands shared by the dense and paged
    decode kernels: (sm_kwargs dict, q_site) — or None when a calibrated
    site is not per-tensor expressible (caller falls back)."""
    sm_quant = smo_quant = None
    sm_qmin = sm_qmax = smo_qmin = smo_qmax = 0
    q_site = None
    if _sites_active(ctx):
        sm = _site_quant(ctx, f"{prefix}/softmax_in")
        smo = _site_quant(ctx, f"{prefix}/softmax_out")
        if sm is False or smo is False:
            return None
        sm_quant, sm_qmin, sm_qmax = sm
        smo_quant, smo_qmin, smo_qmax = smo
        q_site = _q_site_quant(ctx, prefix)
    return (dict(sm_quant=sm_quant, sm_qmin=sm_qmin, sm_qmax=sm_qmax,
                 smo_quant=smo_quant, smo_qmin=smo_qmin,
                 smo_qmax=smo_qmax), q_site)


def _quantize_decode_q(qg, q_site):
    """(q_q int8, scales (B, KV, G), zero-points | None) for the decode
    kernels: the calibrated ``{prefix}/q`` site grid when available
    (already fake-quantized queries enter EXACTLY), else dynamic symmetric
    per-head quantization."""
    B, KV, G, _ = qg.shape
    if q_site is not None:
        # re-use the site's affine grid (shifted to int8): already
        # fake-quantized queries enter the kernel exactly
        s_q, z_q, qmin, qmax, shift = q_site
        q_q = (jnp.clip(jnp.round(qg / s_q) + z_q, qmin, qmax)
               - shift).astype(jnp.int8)
        return q_q, jnp.full((B, KV, G), s_q), jnp.full((B, KV, G),
                                                        z_q - shift)
    amax = jnp.max(jnp.abs(qg), axis=-1)
    qs = jnp.maximum(amax / 127.0, jnp.finfo(jnp.float32).tiny)
    q_q = jnp.clip(jnp.round(qg / qs[..., None]), -127, 127).astype(jnp.int8)
    return q_q, qs, None


def _kv_zero_points(kvq, B, KV):
    if kvq is None:
        return None, None
    return (jnp.broadcast_to(jnp.asarray(kvq.k_zp, jnp.float32), (B, KV)),
            jnp.broadcast_to(jnp.asarray(kvq.v_zp, jnp.float32), (B, KV)))


def _kv_head_parallel(dist, kernel, operands):
    """``kernel(*arrays)`` for a decode-attention kernel returning
    (B, KV, G, hd). ``operands``: [(array | None, its kv-head axis | None)].

    XLA cannot partition a Mosaic kernel, so over a tensor-parallel mesh
    (``dist``) the kernel runs inside a ``shard_map``: each device attends
    with its own kv heads (and their query groups), the rest of every
    operand replicated. Kv heads that do not split evenly over the mesh
    run replicated on every device."""
    arrays = [a for a, _ in operands]
    if dist is None or dist.tp_size == 1:
        return kernel(*arrays)
    from jax.sharding import PartitionSpec as P
    tp = dist.tp_axis
    kv = arrays[0].shape[1]
    split = kv % dist.tp_size == 0

    def spec(a, axis):
        if a is None or axis is None or not split:
            return P()
        return P(*[tp if i == axis else None for i in range(a.ndim)])
    return jax.shard_map(
        kernel, mesh=dist.mesh,
        in_specs=tuple(spec(a, axis) for a, axis in operands),
        out_specs=P(None, tp) if split else P(), check_vma=False)(*arrays)


def _quant_decode_attend(q, cache: QuantKVCache, q_pos, cfg: AttnConfig,
                         ctx, prefix, kvq=None, dist=None):
    """Decode step through the fused int8 attention kernel.

    q: (B, 1, H, hd) (already RoPE'd / site-quantized); queries enter on
    the calibrated site grid when available (exact), else dynamically
    quantized per head; the attention scale is folded into the q scales.
    ``kvq``: the deploy.KVQuant the cache was written with (its static
    per-head zero-points are corrected in-kernel). Returns (B, 1, H, hd) in
    q.dtype, or None when the kernel cannot express the site (the caller
    dequantizes and takes the flash path — the simulate-path fallback rule).
    """
    if not cfg.causal:
        return None           # kernel masks causally; _mask handles the rest
    site = _decode_site_params(ctx, prefix)
    if site is None:
        return None
    sm_kwargs, q_site = site
    from repro.kernels import ops as kops
    B, T, H, hd = q.shape
    KV, G = cfg.num_kv_heads, cfg.q_groups
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    q_q, qs, qz = _quantize_decode_q(qg, q_site)
    kz, vz = _kv_zero_points(kvq, B, KV)
    sm, smo = sm_kwargs.pop("sm_quant"), sm_kwargs.pop("smo_quant")

    def kernel(q_q, qs, k_q, k_s, v_q, v_s, k_pos, q_pos, qz, kz, vz, sm,
               smo):
        return kops.int8_attend_decode(
            q_q, qs, k_q, k_s, v_q, v_s, k_pos, q_pos, q_zp=qz, k_zp=kz,
            v_zp=vz, window=cfg.window, logit_softcap=cfg.logit_softcap,
            kv_bits=4 if isinstance(cache, Quant4KVCache) else 8,
            sm_quant=sm, smo_quant=smo, **sm_kwargs)
    out = _kv_head_parallel(dist, kernel, [
        (q_q, 1), (qs * cfg.scale, 1), (cache.k_q, 2), (cache.k_s, 2),
        (cache.v_q, 2), (cache.v_s, 2), (cache.pos, None),
        (q_pos[:, 0], None), (qz, 1), (kz, 1), (vz, 1), (sm, None),
        (smo, None)])
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _paged_quant_decode_attend(q, cache: PagedQuantKVCache, block_table,
                               q_pos, cfg: AttnConfig, ctx, prefix,
                               kvq=None, dist=None):
    """Decode step through the paged int8 attention kernel — the
    :func:`_quant_decode_attend` twin over a block-paged arena (same site
    grids, zero-point corrections and fallback rule; block gather + the
    derived-position mask happen in-kernel)."""
    if not cfg.causal:
        return None
    site = _decode_site_params(ctx, prefix)
    if site is None:
        return None
    sm_kwargs, q_site = site
    from repro.kernels import ops as kops
    B, T, H, hd = q.shape
    KV, G = cfg.num_kv_heads, cfg.q_groups
    bs = cache.pos.shape[1]
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    q_q, qs, qz = _quantize_decode_q(qg, q_site)
    kz, vz = _kv_zero_points(kvq, B, KV)
    sm, smo = sm_kwargs.pop("sm_quant"), sm_kwargs.pop("smo_quant")

    def kernel(q_q, qs, k_q, k_s, v_q, v_s, table, q_pos, qz, kz, vz, sm,
               smo):
        return kops.paged_int8_attend_decode(
            q_q, qs, k_q, k_s, v_q, v_s, table, q_pos,
            s_cap=paged_capacity(table, bs, cfg.window),
            q_zp=qz, k_zp=kz, v_zp=vz, window=cfg.window,
            logit_softcap=cfg.logit_softcap,
            kv_bits=4 if isinstance(cache, PagedQuant4KVCache) else 8,
            sm_quant=sm, smo_quant=smo, **sm_kwargs)
    out = _kv_head_parallel(dist, kernel, [
        (q_q, 1), (qs * cfg.scale, 1), (cache.k_q, 2), (cache.k_s, 2),
        (cache.v_q, 2), (cache.v_s, 2), (block_table, None),
        (q_pos[:, 0], None), (qz, 1), (kz, 1), (vz, 1), (sm, None),
        (smo, None)])
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _paged_decode_attend(q, cache: PagedKVCache, block_table, q_pos,
                         cfg: AttnConfig, ctx, prefix,
                         dist=None):
    """Decode step through the paged bf16/f32 attention kernel. Applies
    the softmax_in/softmax_out sites in-kernel when they are per-tensor
    (matching _dense_attend's placement); returns None when a site is
    calibrated per-channel/PEG — the caller gathers the lane's blocks and
    takes the dense path so the site still applies exactly."""
    if not cfg.causal:
        return None
    site = _decode_site_params(ctx, prefix)
    if site is None:
        return None
    sm_kwargs, _ = site
    from repro.kernels import ops as kops
    B, T, H, hd = q.shape
    KV, G = cfg.num_kv_heads, cfg.q_groups
    bs = cache.pos.shape[1]
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32) * cfg.scale
    sm, smo = sm_kwargs.pop("sm_quant"), sm_kwargs.pop("smo_quant")

    def kernel(qg, k, v, table, q_pos, sm, smo):
        return kops.paged_attend_decode(
            qg, k, v, table, q_pos,
            s_cap=paged_capacity(table, bs, cfg.window),
            window=cfg.window, logit_softcap=cfg.logit_softcap,
            sm_quant=sm, smo_quant=smo, **sm_kwargs)
    out = _kv_head_parallel(dist, kernel, [
        (qg, 1), (cache.k, 2), (cache.v, 2), (block_table, None),
        (q_pos[:, 0], None), (sm, None), (smo, None)])
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block with projections + cache handling
# ---------------------------------------------------------------------------

def _prev_positions(positions):
    """Per-lane position of the last token BEFORE this chunk (chunked
    prefill): one less than the lane's first live position, or -1 for lanes
    whose rows are all dead (idle lanes, and lanes starting chunk 1)."""
    live = positions >= 0
    big = jnp.where(live, positions, jnp.iinfo(jnp.int32).max)
    start = jnp.min(big, axis=1)
    return jnp.where(jnp.any(live, axis=1), start - 1, -1)


def attention_block(p, x, positions, cfg: AttnConfig, *, ctx=None,
                    prefix="attn", cache: Optional[KVCache] = None,
                    chunked: Optional[bool] = None, block_table=None,
                    append: bool = False, dist=None
                    ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """x: (B, T, D). p: dict with wq (D,H*hd), wk/wv (D,KV*hd), wo (H*hd,D).

    Training/prefill: cache=None or empty cache to fill.
    Decode: T == 1 (or small), cache holds past KV; returns updated cache.

    Paged caches (PagedKVCache / PagedQuantKVCache) additionally need
    ``block_table`` (B, max_blocks) int32 — writes scatter through it and
    decode runs the paged kernels (gather + derived-position mask
    in-kernel). Over a tensor-parallel mesh (``dist``) the decode kernels
    run per kv-head shard (:func:`_kv_head_parallel`).

    ``append=True`` is the chunked-prefill contract: the T tokens are ONE
    chunk appended at each lane's current position, so queries attend over
    the pre-write cache contents (the lane's earlier chunks) PLUS the fresh
    chunk, instead of over the fresh tokens alone. Earlier chunks are read
    back exactly as decode would read them (quantized caches dequantize on
    the calibrated grid), and the chunk's own writes keep the dead-cell
    scatter contract, so co-resident lanes pass through bit-identical per
    chunk.

    DEPLOY: ``x`` may arrive as a QTensor (int8 LN output) with packed
    projection weights — QKV and Wo then run on the int8 matmul kernel.
    """
    from repro.core import deploy as deploy_lib
    x_int8 = isinstance(x, deploy_lib.QTensor)
    B, T, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def w(name):
        from repro.models.common import resolve_weight
        wmat = resolve_weight(p[name])
        return ctx.weight(f"{prefix}/{name}", wmat) if ctx is not None else wmat

    with jax.named_scope("qkv"):
        if x_int8:
            q = deploy_lib.matmul(x, p["wq"]).reshape(B, T, H, hd)
            k = deploy_lib.matmul(x, p["wk"]).reshape(B, T, KV, hd)
            v = deploy_lib.matmul(x, p["wv"]).reshape(B, T, KV, hd)
        else:
            q = (x @ w("wq")).reshape(B, T, H, hd)
            k = (x @ w("wk")).reshape(B, T, KV, hd)
            v = (x @ w("wv")).reshape(B, T, KV, hd)
        if "q_norm" in p:   # qwen3-style per-head QK norm
            from repro.models.common import rms_norm
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        if cfg.rope_theta is not None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if ctx is not None:
            q = ctx.act(f"{prefix}/q", q)
            k = ctx.act(f"{prefix}/k", k)
            v = ctx.act(f"{prefix}/v", v)

    new_cache = None
    out = None
    positions = jnp.broadcast_to(positions, (B, T))
    if cache is not None:
        paged = isinstance(cache, (PagedKVCache, PagedQuantKVCache))
        quantized = isinstance(cache, (QuantKVCache, PagedQuantKVCache))
        # int4 caches read their clip ranges from the separate kv4 site
        # (present only when k/v were calibrated at 4 bits) — falling back
        # to dynamic per-slot int4 grids when it is absent.
        kv_site = f"{prefix}/kv4" \
            if isinstance(cache, (Quant4KVCache, PagedQuant4KVCache)) \
            else f"{prefix}/kv"
        kvq = ctx.deploy_act(kv_site) \
            if (quantized and ctx is not None) else None
        if paged:
            if block_table is None:
                raise ValueError("paged KV cache needs the block_table "
                                 "threaded from the whole-model cache")
            S = paged_capacity(block_table, cache.pos.shape[1], cfg.window)
        else:
            S = cache.pos.shape[1]
        bidx = jnp.arange(B)[:, None]
        if T > 1:
            # Prefill: attend over the fresh K/V (window enforced by mask),
            # then write the last min(T, S) tokens into the cache. In
            # append mode (chunked prefill) the pre-write cache view is
            # snapshotted first: earlier chunks join the attended keys, and
            # ring slots the chunk overwrites still show their OLD occupant
            # (position p - S), which is exactly what earlier queries in
            # the chunk may still attend within their window.
            if append:
                with jax.named_scope("attend"):
                    if paged:
                        prev = _prev_positions(positions)
                        k_past, v_past = paged_gather_kv(cache, block_table,
                                                         cfg.window, kvq)
                        kpos_past = paged_key_positions(
                            block_table, prev, S, cache.pos.shape[1])
                    elif quantized:
                        k_past, v_past = dequantize_kv(cache, kvq)
                        kpos_past = cache.pos
                    else:
                        k_past, v_past, kpos_past = (cache.k, cache.v,
                                                     cache.pos)
            keep = min(T, S)
            kw, vw, pw = k[:, -keep:], v[:, -keep:], positions[:, -keep:]
            if paged:
                new_cache = _write_paged_kv(cache, kw, vw, pw, block_table,
                                            cfg.window, kvq)
            else:
                slots = _write_slots(pw, S, cfg.window)
                new_cache = _write_kv(cache, kw, vw, pw, slots, bidx, kvq)
            if append:
                with jax.named_scope("attend"):
                    k_att = jnp.concatenate([k_past.astype(k.dtype), k],
                                            axis=1)
                    v_att = jnp.concatenate([v_past.astype(v.dtype), v],
                                            axis=1)
                    kpos_att = jnp.concatenate([kpos_past, positions],
                                               axis=1)
            else:
                k_att, v_att, kpos_att = k, v, positions
        elif paged:
            # Paged decode: write the new token through the block table,
            # attend through the paged kernel (site fallback: gather the
            # lane's blocks into a dense view + derived positions).
            new_cache = _write_paged_kv(cache, k, v, positions, block_table,
                                        cfg.window, kvq)
            with jax.named_scope("attend"):
                if quantized:
                    out = _paged_quant_decode_attend(
                        q, new_cache, block_table, positions, cfg, ctx,
                        prefix, kvq, dist=dist)
                else:
                    out = _paged_decode_attend(q, new_cache, block_table,
                                               positions, cfg, ctx, prefix,
                                               dist=dist)
                if out is None:
                    k_att, v_att = paged_gather_kv(new_cache, block_table,
                                                   cfg.window, kvq)
                    kpos_att = paged_key_positions(
                        block_table, positions[:, 0], S, cache.pos.shape[1])
        else:
            # Decode: write the new token, attend over the cache.
            slots = _write_slots(positions, S, cfg.window)
            new_cache = _write_kv(cache, k, v, positions, slots, bidx, kvq)
            if quantized:
                with jax.named_scope("attend"):
                    out = _quant_decode_attend(q, new_cache, positions, cfg,
                                               ctx, prefix, kvq, dist=dist)
                    if out is None:   # kernel can't express: dequant + flash
                        k_att, v_att = dequantize_kv(new_cache, kvq)
                        kpos_att = new_cache.pos
            else:
                k_att, v_att, kpos_att = (new_cache.k, new_cache.v,
                                          new_cache.pos)
    else:
        k_att, v_att = k, v
        kpos_att = positions

    if out is None:
        with jax.named_scope("attend"):
            out = attend(q, k_att.astype(q.dtype), v_att.astype(q.dtype),
                         jnp.broadcast_to(positions, (B, T)), kpos_att, cfg,
                         ctx=ctx, prefix=prefix, chunked=chunked)
    with jax.named_scope("out"):
        out2d = out.reshape(B, T, H * hd)
        if x_int8:
            wo_aq = ctx.deploy_act(f"{prefix}/wo_in")
            if ctx.telemetry is not None:
                ctx.telem_site(f"{prefix}/wo_in",
                               deploy_lib.site_stats(out2d, wo_aq))
            out = deploy_lib.matmul(deploy_lib.quantize_act(out2d, wo_aq),
                                    p["wo"])
        else:
            if ctx is not None:
                out2d = ctx.act_in(f"{prefix}/wo_in", out2d)
            out = out2d @ w("wo")
        if ctx is not None:
            out = ctx.act(f"{prefix}/ctx_out", out)
    return out, new_cache


def init_attention_params(key, d_model: int, cfg: AttnConfig,
                          dtype=jnp.float32, qk_norm: bool = False):
    from repro.models.common import dense_init, split_keys
    k1, k2, k3, k4 = split_keys(key, 4)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {"wq": dense_init(k1, d_model, H * hd, dtype),
         "wk": dense_init(k2, d_model, KV * hd, dtype),
         "wv": dense_init(k3, d_model, KV * hd, dtype),
         "wo": dense_init(k4, H * hd, d_model, dtype)}
    if qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p
