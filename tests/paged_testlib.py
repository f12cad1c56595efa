"""Shared operands for the paged decode kernels' oracle tests
(test_paged_kv.py: bf16/f32 and int8 arenas; test_lowbit.py: int4): lanes
at every edge of the live-bounded multi-page walk, with unmapped entries
and stale pages past each lane's live bound."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import nibble, ops, ref

# One lane at each q_pos: idle, the first cell, a page's last cell, the
# next page's first, mid-block, a compute block's last cell (128 tokens:
# 16 pages of 8), the next block's first, full capacity. The ring layer
# (window = s_cap = 200, under the table's 320 cells) holds lanes before,
# at and past its wrap.
WALKS = {"global": ([-1, 0, 7, 8, 60, 127, 128, 319], 320),
         "ring": ([-1, 150, 199, 200, 450], 200)}
STALE = 1e4              # what pages past a lane's live bound hold


def walk_operands(seed, walk, kv_bits=16, cache_dtype=jnp.float32, bs=8,
                  nb=40, KV=2, hd=16):
    """Arenas (float at ``kv_bits=16``; int8 or nibble-packed int4 payloads
    with per-cell scales) and a block table for the lanes of
    ``WALKS[walk]``: each lane's live pages map to pages of their own; past
    the live bound, every other entry is unmapped and the rest map stale
    pages (scales ``STALE``, payloads at their largest)."""
    q_pos, s_cap = WALKS[walk]
    rng = np.random.default_rng(seed)
    live = [min(-(-s_cap // bs), (p + bs) // bs) for p in q_pos]
    n_stale = 4
    N = sum(live) + n_stale
    tbl = np.full((len(q_pos), nb), -1, np.int32)
    pages = iter(rng.permutation(sum(live)) + n_stale)
    for lane, n in enumerate(live):
        tbl[lane, :n] = [next(pages) for _ in range(n)]
        tbl[lane, n + 1::2] = rng.integers(0, n_stale, len(tbl[lane,
                                                               n + 1::2]))
    shape = (N, bs, KV, hd)
    if kv_bits < 16:
        top = 2 ** (kv_bits - 1) - 1
        k_a = rng.integers(-top, top + 1, shape).astype(np.int8)
        v_a = rng.integers(-top, top + 1, shape).astype(np.int8)
        k_s = rng.uniform(.01, .05, shape[:3]).astype(np.float32)
        v_s = rng.uniform(.01, .05, shape[:3]).astype(np.float32)
        k_a[:n_stale] = v_a[:n_stale] = top
        k_s[:n_stale] = v_s[:n_stale] = STALE
        if kv_bits == 4:
            k_a, v_a = nibble.pack_nibbles(k_a), nibble.pack_nibbles(v_a)
        arenas = tuple(jnp.asarray(x) for x in (k_a, k_s, v_a, v_s))
    else:
        k_a = rng.standard_normal(shape).astype(np.float32)
        v_a = rng.standard_normal(shape).astype(np.float32)
        k_a[:n_stale] = v_a[:n_stale] = STALE
        arenas = tuple(jnp.asarray(x, cache_dtype) for x in (k_a, v_a))
    return arenas, jnp.asarray(tbl), jnp.asarray(q_pos, jnp.int32), s_cap


def check_lanes(got, want, q_pos, tol=3e-5):
    """Live lanes match the oracle; idle lanes (q_pos = -1) are zero."""
    got, want, live = np.asarray(got), np.asarray(want), np.asarray(q_pos)
    np.testing.assert_allclose(got[live >= 0], want[live >= 0], rtol=tol,
                               atol=tol)
    assert (got[live < 0] == 0).all()


def int8_walk_case(window, softcap, sites, walk, kv_bits):
    """One walk case of the paged int8 decode kernel against its oracle,
    over an int8 (``kv_bits=8``) or nibble-packed int4 arena: one page a
    compute block."""
    (k_a, k_s, v_a, v_s), tbl, q_pos, s_cap = walk_operands(
        2, walk, kv_bits=kv_bits)
    B, KV, G, hd = len(q_pos), 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q_q = jax.random.randint(ks[0], (B, KV, G, hd), -128, 128, jnp.int8)
    q_s = jax.random.uniform(ks[1], (B, KV, G), minval=.01, maxval=.05)
    q_z = jnp.round(jax.random.uniform(ks[2], (B, KV, G), minval=-20.,
                                       maxval=20.))
    k_z = jnp.round(jax.random.uniform(ks[3], (B, KV), minval=-5.,
                                       maxval=5.))
    kw = dict(s_cap=s_cap, q_zp=q_z, k_zp=k_z, v_zp=-k_z, window=window,
              logit_softcap=softcap, kv_bits=kv_bits)
    if sites:
        kw.update(sm_quant=jnp.asarray([0.02, 100.0]),
                  smo_quant=jnp.asarray([1 / 255.0, 0.0]))
    got = ops.paged_int8_attend_decode(q_q, q_s, k_a, k_s, v_a, v_s,
                                       tbl, q_pos, **kw)
    want = ref.paged_int8_attend_decode_ref(
        q_q, q_s, k_a, k_s, v_a, v_s, ops._lane_blocks(tbl, s_cap, 8),
        q_pos, **kw)
    check_lanes(got, want, q_pos, tol=2e-4)
