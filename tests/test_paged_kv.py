"""Paged KV-cache subsystem: block-pool allocator, paged decode kernels
(bf16 + int8) vs their oracles, block-table / derived-position properties,
and the pool-managed continuous scheduler.

Layers of coverage (mirroring tests/test_kv_quant.py + test_scheduler.py):

* BlockPool unit tests — prefix mapping, reservation backpressure, growth
  within reservation, free-list accounting (leak check).
* Kernel-vs-oracle for ``paged_attend_decode`` and
  ``paged_int8_attend_decode`` across window / softcap / GQA / partially
  mapped lanes / idle lanes / in-kernel softmax sites, and lanes at every
  edge of the live-bounded multi-page walk (tests/paged_testlib.py).
* Write-path + derived-position properties: stored positions equal derived
  positions on every written cell, and a reallocated block's STALE cells
  are never readable (allocation order, not memset, provides isolation).
* Stub-model scheduler properties with a constrained pool: golden tokens
  under backpressure, FIFO admission, all blocks returned.
* Real-model invariants on gemma2-2b-reduced: paged == dense greedy
  parity across schedulers (kv 16 + int8 kv 8, plus the deploy-int8
  integer path), slot-insert admission leaves other lanes' *blocks*
  bit-identical, capacity validation errors match the dense path's, and
  the jitted steps trace exactly once across paged admissions + growth.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import attention as att
from repro.models import transformer as tfm
from repro.runtime import (BlockPool, Request, blocks_for_tokens, serve,
                           serve_continuous)
from repro.runtime.steps import (make_admit_step, make_decode_step,
                                 make_prefill_step)
from paged_testlib import check_lanes, int8_walk_case, walk_operands
from serve_testlib import golden as _golden
from serve_testlib import next_arr as _next_arr
from serve_testlib import onehot as _onehot

pytestmark = pytest.mark.paged


# ---------------------------------------------------------------------------
# BlockPool allocator
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_prefix_mapping_and_free(self):
        pool = BlockPool(8, 4, batch_slots=2, max_blocks_per_lane=4)
        assert pool.reserve_and_alloc(0, n_alloc=2, n_reserve=3)
        assert list(pool.table[0, :2]) == [0, 1]
        assert pool.table[0, 2] == -1
        assert pool.blocks_in_use == 2 and pool.blocks_reserved == 3
        pool.grow(0, 3)
        assert pool.table[0, 2] == 2
        pool.grow(0, 3)                      # idempotent
        assert pool.blocks_in_use == 3
        assert pool.free_lane(0) == 3
        assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0
        assert (pool.table == -1).all()

    def test_reservation_backpressure(self):
        pool = BlockPool(4, 4, batch_slots=2, max_blocks_per_lane=4)
        assert pool.reserve_and_alloc(0, 1, 3)
        # only 1 block mapped, but the RESERVATION gates admission
        assert pool.blocks_in_use == 1
        assert not pool.can_reserve(2)
        assert pool.can_reserve(1)
        assert not pool.reserve_and_alloc(1, 1, 2)   # no state change
        assert pool.blocks_reserved == 3
        pool.free_lane(0)
        assert pool.reserve_and_alloc(1, 1, 2)

    def test_growth_beyond_reservation_raises(self):
        pool = BlockPool(8, 4, batch_slots=1, max_blocks_per_lane=8)
        pool.reserve_and_alloc(0, 1, 2)
        pool.grow(0, 2)
        with pytest.raises(RuntimeError, match="reservation"):
            pool.grow(0, 3)

    def test_double_reserve_raises(self):
        pool = BlockPool(8, 4, batch_slots=1, max_blocks_per_lane=8)
        pool.reserve_and_alloc(0, 1, 1)
        with pytest.raises(RuntimeError, match="still holds"):
            pool.reserve_and_alloc(0, 1, 1)

    def test_fragmentation_gauge(self):
        pool = BlockPool(8, 4, batch_slots=1, max_blocks_per_lane=8)
        pool.reserve_and_alloc(0, 2, 2)      # 8 cells allocated
        assert pool.fragmentation(live_tokens=6) == pytest.approx(0.25)
        assert pool.fragmentation(live_tokens=8) == 0.0

    def test_blocks_for_tokens(self):
        assert blocks_for_tokens(0, 4) == 0
        assert blocks_for_tokens(1, 4) == 1
        assert blocks_for_tokens(4, 4) == 1
        assert blocks_for_tokens(5, 4) == 2


# ---------------------------------------------------------------------------
# Paged kernels vs oracles
# ---------------------------------------------------------------------------

def _paged_operands(key, N=10, bs=8, KV=2, G=2, hd=16, s_cap=40, B=3):
    """Arenas + a block table with one deep lane, one shallow lane and one
    idle lane (tests the partially-mapped/unmapped masking)."""
    nb = -(-s_cap // bs)
    ks = jax.random.split(key, 4)
    k_arena = jax.random.normal(ks[0], (N, bs, KV, hd), jnp.float32)
    v_arena = jax.random.normal(ks[1], (N, bs, KV, hd), jnp.float32)
    tbl = np.full((B, nb), -1, np.int32)
    tbl[0, :4] = [7, 2, 9, 0]
    tbl[1, :1] = [5]
    q_pos = jnp.asarray([25, 3, -1][:B], jnp.int32)
    q = jax.random.normal(ks[2], (B, KV, G, hd), jnp.float32)
    return q, k_arena, v_arena, jnp.asarray(tbl), q_pos


class TestPagedKernelVsOracle:
    @pytest.mark.parametrize("window,softcap,walk,cache_dtype,sites", [
        pytest.param(None, None, None, None, False, id="None-None"),
        pytest.param(16, None, None, None, False, id="16-None"),
        pytest.param(None, 50.0, None, None, False, id="None-50.0"),
        pytest.param(8, 30.0, None, None, False, id="8-30.0"),
        pytest.param(None, None, "global", jnp.float32, False,
                     id="walk-f32"),
        pytest.param(100, 30.0, "global", jnp.bfloat16, False,
                     id="walk-bf16-window-softcap"),
        pytest.param(None, None, "global", jnp.bfloat16, True,
                     id="walk-bf16-sites"),
        pytest.param(None, 50.0, "global", jnp.float32, True,
                     id="walk-f32-sites"),
        pytest.param(200, None, "ring", jnp.bfloat16, False,
                     id="ring-bf16"),
        pytest.param(200, None, "ring", jnp.float32, True,
                     id="ring-f32-sites")])
    def test_bf16_matches_ref(self, window, softcap, walk, cache_dtype,
                              sites):
        """Kernel == oracle. The walk cases mix lanes at every edge of the
        live-bounded multi-page walk, with unmapped entries and stale
        pages past each lane's live bound; ``sites`` adds softmax_in and
        the two-pass softmax_out schedule."""
        kw = dict(window=window, logit_softcap=softcap)
        if sites:
            kw.update(sm_quant=jnp.asarray([0.02, 100.0]),
                      smo_quant=jnp.asarray([1.0 / 255.0, 0.0]))
        if walk is None:
            q, k_a, v_a, tbl, q_pos = _paged_operands(jax.random.PRNGKey(0))
            got = ops.paged_attend_decode(q, k_a, v_a, tbl, q_pos, s_cap=40,
                                          **kw)
            want = ref.paged_attend_decode_ref(q, k_a, v_a, tbl, q_pos,
                                               s_cap=40, **kw)
            np.testing.assert_allclose(np.asarray(got)[:2],
                                       np.asarray(want)[:2],
                                       rtol=3e-5, atol=3e-5)
            return
        (k_a, v_a), tbl, q_pos, s_cap = walk_operands(
            1, walk, cache_dtype=cache_dtype)
        q = jax.random.normal(jax.random.PRNGKey(4), (len(q_pos), 2, 2, 16))
        got = ops.paged_attend_decode(q, k_a, v_a, tbl, q_pos, s_cap=s_cap,
                                      **kw)
        want = ref.paged_attend_decode_ref(
            q, k_a, v_a, ops._lane_blocks(tbl, s_cap, 8), q_pos,
            s_cap=s_cap, **kw)
        check_lanes(got, want, q_pos)

    def test_bf16_softmax_sites_in_kernel(self):
        """softmax_in (one-pass) and softmax_out (two-pass over the lane's
        blocks) match the oracle's fake-quant placement."""
        q, k_a, v_a, tbl, q_pos = _paged_operands(jax.random.PRNGKey(1))
        smq = jnp.asarray([0.02, 100.0])
        smo = jnp.asarray([1.0 / 255.0, 0.0])
        got = ops.paged_attend_decode(q, k_a, v_a, tbl, q_pos, s_cap=40,
                                      logit_softcap=50.0, sm_quant=smq,
                                      smo_quant=smo)
        want = ref.paged_attend_decode_ref(q, k_a, v_a, tbl, q_pos,
                                           s_cap=40, logit_softcap=50.0,
                                           sm_quant=smq, smo_quant=smo)
        np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                                   rtol=3e-5, atol=3e-5)

    def test_idle_lane_and_unmapped_blocks_are_masked(self):
        """An idle lane (q_pos = -1) and unmapped table entries must not
        poison the output: the mapped lanes' results are unchanged when
        arena blocks outside their tables hold garbage."""
        q, k_a, v_a, tbl, q_pos = _paged_operands(jax.random.PRNGKey(2))
        got = ops.paged_attend_decode(q, k_a, v_a, tbl, q_pos, s_cap=40)
        poison = jnp.full_like(k_a[0], 1e9)
        mapped = set(np.asarray(tbl)[np.asarray(tbl) >= 0].tolist())
        for blk in range(k_a.shape[0]):
            if blk not in mapped:
                k_a = k_a.at[blk].set(poison)
                v_a = v_a.at[blk].set(poison)
        got2 = ops.paged_attend_decode(q, k_a, v_a, tbl, q_pos, s_cap=40)
        np.testing.assert_array_equal(np.asarray(got)[:2],
                                      np.asarray(got2)[:2])

    @pytest.mark.deploy
    @pytest.mark.parametrize("window,softcap,sites,walk", [
        pytest.param(None, None, False, None, id="None-None-False"),
        pytest.param(16, 50.0, False, None, id="16-50.0-False"),
        pytest.param(None, None, True, None, id="None-None-True"),
        pytest.param(None, None, False, "global", id="walk"),
        pytest.param(100, 30.0, True, "global", id="walk-sites"),
        pytest.param(200, None, True, "ring", id="ring-sites")])
    def test_int8_matches_ref(self, window, softcap, sites, walk):
        if walk is not None:
            int8_walk_case(window, softcap, sites, walk, kv_bits=8)
            return
        key = jax.random.PRNGKey(3)
        ks = jax.random.split(key, 8)
        N, bs, KV, G, hd, B, s_cap = 10, 8, 2, 2, 16, 3, 40
        nb = -(-s_cap // bs)
        k_a = jax.random.randint(ks[0], (N, bs, KV, hd), -127, 128, jnp.int8)
        v_a = jax.random.randint(ks[1], (N, bs, KV, hd), -127, 128, jnp.int8)
        k_s = jax.random.uniform(ks[2], (N, bs, KV), minval=.01, maxval=.05)
        v_s = jax.random.uniform(ks[3], (N, bs, KV), minval=.01, maxval=.05)
        q_q = jax.random.randint(ks[4], (B, KV, G, hd), -128, 128, jnp.int8)
        q_s = jax.random.uniform(ks[5], (B, KV, G), minval=.01, maxval=.05)
        q_z = jnp.round(jax.random.uniform(ks[6], (B, KV, G), minval=-20.,
                                           maxval=20.))
        k_z = jnp.round(jax.random.uniform(ks[7], (B, KV), minval=-5.,
                                           maxval=5.))
        v_z = -k_z
        tbl = np.full((B, nb), -1, np.int32)
        tbl[0, :4] = [7, 2, 9, 0]
        tbl[1, :1] = [5]
        q_pos = jnp.asarray([25, 3, -1], jnp.int32)
        kw = dict(s_cap=s_cap, q_zp=q_z, k_zp=k_z, v_zp=v_z, window=window,
                  logit_softcap=softcap)
        if sites:
            kw.update(sm_quant=jnp.asarray([0.02, 100.0]),
                      smo_quant=jnp.asarray([1 / 255.0, 0.0]))
        got = ops.paged_int8_attend_decode(q_q, q_s, k_a, k_s, v_a, v_s,
                                           jnp.asarray(tbl), q_pos, **kw)
        want = ref.paged_int8_attend_decode_ref(q_q, q_s, k_a, k_s, v_a,
                                                v_s, jnp.asarray(tbl),
                                                q_pos, **kw)
        np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                                   rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Write path + derived positions (block-table properties)
# ---------------------------------------------------------------------------

class TestDerivedPositions:
    @pytest.mark.parametrize("window", [None, 6])
    def test_stored_pos_equals_derived_on_written_cells(self, window):
        """After writing positions 0..p through the block table, the arena's
        stored positions on every derived-valid cell equal the derived
        positions — for global and ring (window < capacity) layers."""
        cfg = att.AttnConfig(num_heads=2, num_kv_heads=2, head_dim=4,
                             window=window)
        bs, nb, N = 4, 4, 8
        cache = att.init_paged_kv_cache(N, bs, cfg, jnp.float32)
        # poison the stored positions to prove stale cells are invisible
        cache = cache._replace(pos=jnp.full_like(cache.pos, 5))
        tbl = jnp.asarray([[3, 1, 6, 0]], jnp.int32)
        s_cap = att.paged_capacity(tbl, bs, window)
        rng = np.random.RandomState(0)
        for p in range(12):
            kv = jnp.asarray(rng.randn(1, 1, 2, 4).astype(np.float32))
            pw = jnp.asarray([[p]], jnp.int32)
            cache = att._write_paged_kv(cache, kv, kv, pw, tbl, window,
                                        None)
            derived = att.paged_key_positions(tbl, jnp.asarray([p]), s_cap,
                                              bs)
            nb_cap = -(-s_cap // bs)       # window layers touch a prefix
            stored = ref.paged_gather_ref(cache.pos, tbl[:, :nb_cap])
            valid = np.asarray(derived)[0] >= 0
            np.testing.assert_array_equal(
                np.asarray(stored)[0][valid], np.asarray(derived)[0][valid])
            # the derived-valid set is exactly the live window
            want_n = min(p + 1, s_cap)
            assert valid.sum() == want_n

    def test_dead_cells_and_unmapped_blocks_drop_writes(self):
        cfg = att.AttnConfig(num_heads=1, num_kv_heads=1, head_dim=4)
        cache = att.init_paged_kv_cache(4, 4, cfg, jnp.float32)
        before = np.asarray(cache.pos).copy()
        tbl = jnp.asarray([[2, -1]], jnp.int32)
        kv = jnp.ones((1, 2, 1, 4), jnp.float32)
        # position -1 (dead) and position 5 (block 1: unmapped) both drop
        pw = jnp.asarray([[-1, 5]], jnp.int32)
        cache = att._write_paged_kv(cache, kv, kv, pw, tbl, None, None)
        np.testing.assert_array_equal(np.asarray(cache.pos), before)
        assert float(jnp.abs(cache.k).sum()) == 0.0

    def test_reset_paged_lanes_empties_only_masked_lanes_blocks(self):
        cfg = att.AttnConfig(num_heads=1, num_kv_heads=1, head_dim=4)
        cache = att.init_paged_kv_cache(6, 4, cfg, jnp.float32)
        tbl = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        kv = jnp.ones((2, 1, 1, 4), jnp.float32)
        for p in range(6):
            cache = att._write_paged_kv(cache, kv, kv,
                                        jnp.full((2, 1), p, jnp.int32),
                                        tbl, None, None)
        cache = att.reset_paged_lanes(cache, jnp.asarray([True, False]),
                                      tbl)
        pos = np.asarray(cache.pos)
        assert (pos[[0, 1]] == -1).all()          # lane 0's blocks emptied
        assert (pos[2, :4] >= 0).sum() == 4       # lane 1 untouched
        assert (pos[3, :2] >= 0).sum() == 2


# ---------------------------------------------------------------------------
# Stub-model scheduler with a constrained pool (backpressure properties)
# ---------------------------------------------------------------------------

class PoolStub:
    def __init__(self):
        self.admit_masks = []

    def init_cache(self, batch):
        return {"kv": jnp.zeros((batch, 4), jnp.float32)}

    def admit(self, tokens, positions, admit_mask, cache):
        self.admit_masks.append(np.asarray(admit_mask).copy())
        return _onehot(_next_arr(tokens)), cache

    def decode(self, tokens, pos, cache):
        return _onehot(_next_arr(tokens)), cache


@pytest.mark.serve
class TestPoolScheduler:
    def _run(self, specs, *, slots, num_blocks, bs=4, max_blocks=8):
        reqs = [Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                        max_new_tokens=q) for i, (n, q) in enumerate(specs)]
        pool = BlockPool(num_blocks, bs, slots, max_blocks)
        m = PoolStub()
        stats = serve_continuous(m.admit, m.decode, m.init_cache, reqs,
                                 batch_slots=slots, block_pool=pool)
        return reqs, pool, stats, m

    def test_golden_under_backpressure_and_no_leak(self):
        """A pool too small to admit every request at once still serves the
        exact golden tokens FIFO, and every block returns to the free list."""
        specs = [(3, 6), (4, 5), (2, 7), (3, 2)]
        # worst case per request <= 3 blocks; pool of 4 forces waiting
        reqs, pool, stats, m = self._run(specs, slots=4, num_blocks=4)
        for r in reqs:
            assert r.done
            assert r.tokens_out == _golden(r.prompt, r.max_new_tokens)
        assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0
        assert stats.blocks_in_use <= 4
        # backpressure visible: not all four admitted in the first round
        assert m.admit_masks[0].sum() < 4

    def test_unconstrained_pool_matches_dense_schedule(self):
        """With the dense worst case of blocks, pool admission decisions
        equal the dense scheduler's (same masks, same step counts)."""
        specs = [(3, 2), (4, 6), (2, 1), (3, 4), (1, 3)]
        reqs, pool, stats, m = self._run(specs, slots=2, num_blocks=16)
        dense = [Request(rid=r.rid, prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens) for r in reqs]
        md = PoolStub()
        dstats = serve_continuous(md.admit, md.decode, md.init_cache, dense,
                                  batch_slots=2)
        for r, d in zip(reqs, dense):
            assert r.tokens_out == d.tokens_out
        assert stats.decode_steps == dstats.decode_steps
        assert stats.prefill_calls == dstats.prefill_calls
        assert [tuple(x) for x in m.admit_masks] == \
            [tuple(x) for x in md.admit_masks]

    def test_seeded_random_sweep_conserves_tokens_and_blocks(self):
        rng = np.random.RandomState(1)
        for _ in range(15):
            n = rng.randint(1, 7)
            specs = [(rng.randint(1, 6), rng.randint(0, 7))
                     for _ in range(n)]
            slots = rng.randint(1, 4)
            num_blocks = rng.randint(3, 10)
            reqs, pool, stats, _ = self._run(specs, slots=slots,
                                             num_blocks=num_blocks)
            for r in reqs:
                assert r.done
                assert r.tokens_out == _golden(
                    r.prompt, max(r.max_new_tokens, 0))
            assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0

    def test_capacity_error_matches_dense_phrasing(self):
        """A prompt+quota whose worst case exceeds the pool raises the same
        up-front 'silently dropped' error as the dense max_len check."""
        m = PoolStub()
        pool = BlockPool(2, 4, 1, 8)
        with pytest.raises(ValueError, match="silently dropped"):
            serve_continuous(
                m.admit, m.decode, m.init_cache,
                [Request(rid=0, prompt=np.asarray([1, 2, 3]),
                         max_new_tokens=8)],      # needs 3 blocks > 2
                batch_slots=1, block_pool=pool)

    def test_pool_slots_mismatch_raises(self):
        m = PoolStub()
        with pytest.raises(ValueError, match="batch_slots"):
            serve_continuous(m.admit, m.decode, m.init_cache,
                             [Request(rid=0, prompt=np.asarray([1]),
                                      max_new_tokens=1)],
                             batch_slots=2,
                             block_pool=BlockPool(4, 4, 1, 4))


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                # pragma: no cover - dev-only dependency
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @pytest.mark.serve
    class TestPoolSchedulerHypothesis:
        @settings(max_examples=40, deadline=None)
        @given(st.lists(st.tuples(st.integers(1, 5), st.integers(0, 8)),
                        min_size=1, max_size=8),
               st.integers(1, 4), st.integers(3, 12))
        def test_tokens_and_blocks_conserved(self, specs, slots, blocks):
            reqs = [Request(rid=i,
                            prompt=np.arange(1, n + 1, dtype=np.int32),
                            max_new_tokens=q)
                    for i, (n, q) in enumerate(specs)]
            pool = BlockPool(blocks, 4, slots, 8)
            m = PoolStub()
            try:
                serve_continuous(m.admit, m.decode, m.init_cache, reqs,
                                 batch_slots=slots, block_pool=pool)
            except ValueError:
                # workload exceeds pool capacity: rejected up-front is the
                # contract (never a mid-flight stall)
                assert any(
                    blocks_for_tokens(n + q - 1, 4) > blocks
                    for n, q in specs if q > 0)
                return
            for r in reqs:
                assert r.done
                assert r.tokens_out == _golden(
                    r.prompt, max(r.max_new_tokens, 0))
            assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0
else:                              # keep the skip visible in test reports
    @pytest.mark.skip(reason="hypothesis not installed "
                             "(see requirements-dev.txt)")
    def test_tokens_and_blocks_conserved():
        pass


# ---------------------------------------------------------------------------
# Real-model invariants (gemma2-2b-reduced: GQA, RMSNorm, softcap, and a
# ring-buffer sliding-window cache on the local_attn layers)
# ---------------------------------------------------------------------------

MAX_LEN = 32
BS = 8
NB_LANE = -(-MAX_LEN // BS)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("gemma2-2b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), stacked=True,
                             dtype=jnp.float32)
    return cfg, params


_STEP_CACHE = {}


def _steps(cfg, ctx_factory=None):
    key = (cfg.name, ctx_factory)
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = (
            jax.jit(make_admit_step(cfg, ctx_factory=ctx_factory)),
            jax.jit(make_decode_step(cfg, ctx_factory=ctx_factory)),
            jax.jit(make_prefill_step(cfg, ctx_factory=ctx_factory)))
    return _STEP_CACHE[key]


def _serve(cfg, params, reqs, *, scheduler, kv_bits, batch_slots,
           paged=False, num_blocks=None, ctx_factory=None):
    admit, decode, prefill = _steps(cfg, ctx_factory)
    pool = None
    if paged and scheduler == "continuous":
        pool = BlockPool(num_blocks or batch_slots * NB_LANE, BS,
                         batch_slots, NB_LANE)

    def init(b):
        if not paged:
            return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                                  kv_bits=kv_bits)
        return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                              kv_bits=kv_bits, paged=True, block_size=BS,
                              num_blocks=num_blocks,
                              mapped=scheduler == "static")

    stats = serve(prefill, admit, decode, init, params, reqs,
                  scheduler=scheduler, batch_slots=batch_slots,
                  max_len=MAX_LEN, block_pool=pool)
    return stats, pool


def _mk_reqs(seed, cfg, lens_quotas):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size, size=n)
                    .astype(np.int32),
                    max_new_tokens=q)
            for i, (n, q) in enumerate(lens_quotas)]


@pytest.mark.serve
class TestPagedServingParity:
    SPEC = [(5, 2), (9, 12), (3, 1), (7, 4), (4, 8), (6, 2)]

    @pytest.mark.parametrize("kv_bits", [16, 8])
    @pytest.mark.parametrize("scheduler", ["continuous", "static"])
    def test_paged_matches_dense_greedy(self, tiny, kv_bits, scheduler):
        """Paged == dense greedy tokens under both schedulers, with the
        continuous pool CONSTRAINED so admissions hit backpressure and
        lanes grow + free mid-flight."""
        cfg, params = tiny
        dense = _mk_reqs(3, cfg, self.SPEC)
        paged = _mk_reqs(3, cfg, self.SPEC)
        _serve(cfg, params, dense, scheduler=scheduler, kv_bits=kv_bits,
               batch_slots=2)
        nb = 5 if scheduler == "continuous" else None   # worst case = 3
        stats, pool = _serve(cfg, params, paged, scheduler=scheduler,
                             kv_bits=kv_bits, batch_slots=2, paged=True,
                             num_blocks=nb)
        for d, p in zip(dense, paged):
            assert d.tokens_out == p.tokens_out, f"rid {d.rid}"
            assert p.done
        if pool is not None:
            assert pool.blocks_in_use == 0, "block leak after retirement"
            assert stats.blocks_in_use <= 5

    def test_paged_cache_bytes_scale_with_live_tokens(self, tiny):
        """The paged stat reports ALLOCATED block bytes: with a constrained
        pool it stays well under the dense worst-case footprint."""
        cfg, params = tiny
        dense = _mk_reqs(4, cfg, self.SPEC)
        paged = _mk_reqs(4, cfg, self.SPEC)
        d_stats, _ = _serve(cfg, params, dense, scheduler="continuous",
                            kv_bits=16, batch_slots=2)
        p_stats, _ = _serve(cfg, params, paged, scheduler="continuous",
                            kv_bits=16, batch_slots=2, paged=True,
                            num_blocks=5)
        assert p_stats.blocks_in_use > 0
        assert p_stats.cache_bytes < d_stats.cache_bytes
        # exact accounting: peak bytes == peak mapped blocks x per-block
        # bytes (summed over every layer's arena) — allocated blocks, not
        # batch_slots x max_len, set the footprint
        bpb = tfm.paged_block_bytes(
            tfm.init_cache(cfg, 2, MAX_LEN, dtype=jnp.float32, paged=True,
                           block_size=BS, num_blocks=5, mapped=False))
        assert p_stats.cache_bytes == p_stats.blocks_in_use * bpb


@pytest.mark.serve
class TestPagedLaneInvariants:
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_slot_insert_preserves_other_lanes_blocks(self, tiny, kv_bits):
        """Admitting into lane 1 leaves the blocks mapped by lanes 0 and 2
        BIT-IDENTICAL across every arena leaf — the paged version of the
        dense lane-hash invariant."""
        cfg, params = tiny
        admit, decode, _ = _steps(cfg)
        B = 3
        pool = BlockPool(B * NB_LANE, BS, B, NB_LANE)
        for i in range(B):
            assert pool.reserve_and_alloc(i, NB_LANE, NB_LANE)
        cache = tfm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32,
                               kv_bits=kv_bits, paged=True, block_size=BS,
                               num_blocks=B * NB_LANE, mapped=False)
        cache["block_table"] = jnp.asarray(pool.table)
        rng = np.random.RandomState(1)
        T = 6
        toks = rng.randint(1, cfg.vocab_size, size=(B, T)).astype(np.int32)
        posm = np.tile(np.arange(T, dtype=np.int32), (B, 1))
        logits, cache = admit(params, toks, posm, np.ones((B,), bool),
                              cache)
        cur = np.asarray(jnp.argmax(logits[:, -1:], -1), np.int32)
        pos = np.full((B, 1), T, np.int32)
        for _ in range(2):
            logits, cache = decode(params, cur, pos, cache)
            cur = np.asarray(jnp.argmax(logits, -1), np.int32)
            pos = pos + 1

        # stacked leaves are (n_super, N, bs, ...), tail leaves (N, bs, ...)
        def lane_bytes(c, lane):
            blocks = pool.lane_blocks(lane)
            parts = []
            for node in list(c["scan"]):
                parts.extend(np.asarray(leaf[:, blocks]).tobytes()
                             for leaf in node)
            for node in list(c["tail"]):
                parts.extend(np.asarray(leaf[blocks]).tobytes()
                             for leaf in node)
            return b"".join(parts)

        before = {i: lane_bytes(cache, i) for i in range(B)}
        toks2 = np.zeros((B, T), np.int32)
        posm2 = np.full((B, T), -1, np.int32)
        toks2[1, 2:] = rng.randint(1, cfg.vocab_size, size=4)
        posm2[1, 2:] = np.arange(4)
        _, cache2 = admit(params, toks2, posm2,
                          np.asarray([False, True, False]), cache)
        after = {i: lane_bytes(cache2, i) for i in range(B)}
        assert after[0] == before[0]
        assert after[2] == before[2]
        assert after[1] != before[1]            # the admitted lane changed

    def test_no_recompiles_across_paged_admissions(self, tiny):
        """Jitted admit/decode trace exactly once across pool-managed
        admissions, growth and frees — block tables are data, not shape."""
        cfg, params = tiny
        traces = {"admit": 0, "decode": 0}
        base_admit = make_admit_step(cfg)
        base_decode = make_decode_step(cfg)

        def admit_fn(params, t, pm, m, c):
            traces["admit"] += 1
            return base_admit(params, t, pm, m, c)

        def decode_fn(params, t, p, c):
            traces["decode"] += 1
            return base_decode(params, t, p, c)

        admit_j = jax.jit(admit_fn)
        decode_j = jax.jit(decode_fn)
        reqs = _mk_reqs(4, cfg, [(4, 2), (6, 5), (2, 1), (5, 3), (3, 4)])
        pool = BlockPool(4, BS, 2, NB_LANE)
        stats = serve_continuous(
            lambda t, pm, m, c: admit_j(params, t, pm, m, c),
            lambda t, p, c: decode_j(params, t, p, c),
            lambda b: tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                                     paged=True, block_size=BS,
                                     num_blocks=4, mapped=False),
            reqs, batch_slots=2, block_pool=pool)
        assert stats.prefill_calls >= 3         # several admission rounds
        assert traces == {"admit": 1, "decode": 1}
        assert pool.blocks_in_use == 0

    def test_prompt_exceeding_pool_raises_like_dense(self, tiny):
        """Capacity validation: a prompt alone larger than the pool fails
        up-front with the dense path's error, not via silent drops."""
        cfg, params = tiny
        reqs = _mk_reqs(5, cfg, [(10, 30)])     # needs 39 slots > 32
        with pytest.raises(ValueError, match="silently dropped"):
            _serve(cfg, params, reqs, scheduler="continuous", kv_bits=16,
                   batch_slots=1, paged=True, num_blocks=3)

    def test_cache_reset_slots_empties_paged_lane(self, tiny):
        """cache_reset_slots on a paged model cache empties exactly the
        masked lane's mapped blocks (every layer), and the pool's free-list
        accounting shows no leak when the scheduler then frees the lane."""
        cfg, params = tiny
        _, _, prefill = _steps(cfg)
        B = 2
        cache = tfm.init_cache(cfg, B, MAX_LEN, dtype=jnp.float32,
                               paged=True, block_size=BS)
        toks = np.ones((B, 5), np.int32)
        posm = np.tile(np.arange(5, dtype=np.int32), (B, 1))
        _, cache = prefill(params, toks, cache, posm)
        cache = tfm.cache_reset_slots(cache, np.asarray([True, False]))
        tbl = np.asarray(cache["block_table"])
        for node in list(cache["scan"]) + list(cache["tail"]):
            pos = np.asarray(node.pos)
            lane0 = tbl[0][tbl[0] >= 0]
            lane1 = tbl[1][tbl[1] >= 0]
            assert (pos[..., lane0, :] == -1).all()
            assert (pos[..., lane1, :] >= 0).any()


@pytest.mark.deploy
class TestPagedDeployParity:
    """Paged == dense on the integer deployment path (packed int8 weights,
    int8 KV cache, paged int8 decode kernel)."""

    @pytest.fixture(scope="class")
    def deployed(self):
        from repro.core import Mode, QuantCtx, build_deploy, peg_policy
        from repro.core.pipeline import ptq
        cfg = get_config("gemma2-2b").reduced()
        key = jax.random.PRNGKey(0)
        params = tfm.init_params(cfg, key, stacked=True, dtype=jnp.float32)
        pol = peg_policy(4)
        flat = tfm.init_params(cfg, key, stacked=False, dtype=jnp.float32)
        calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(10),
                                               (2, 8), 0, cfg.vocab_size)}]

        def fwd(p, b, ctx):
            logits, _ = tfm.forward(cfg, p, b["tokens"], ctx=ctx)
            return logits

        qm = ptq(fwd, flat, calib, pol, collect_inputs=True)
        shared = {}
        for site, qp in qm.act_state.items():
            base = ("layer/" + site.split("/", 1)[1]
                    if site.startswith("layer") else site)
            shared.setdefault(base, qp)
        packed, acts = build_deploy(cfg, params, pol, shared)

        def ctx_factory():
            return QuantCtx(policy=pol, mode=Mode.DEPLOY, act_state=shared,
                            deploy_acts=acts)
        return cfg, packed, ctx_factory

    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_paged_matches_dense_int8(self, deployed, kv_bits):
        cfg, packed, ctx_factory = deployed
        spec = [(4, 2), (8, 6), (3, 1), (6, 4)]
        dense = _mk_reqs(5, cfg, spec)
        paged = _mk_reqs(5, cfg, spec)
        _serve(cfg, packed, dense, scheduler="continuous", kv_bits=kv_bits,
               batch_slots=2, ctx_factory=ctx_factory)
        _, pool = _serve(cfg, packed, paged, scheduler="continuous",
                         kv_bits=kv_bits, batch_slots=2, paged=True,
                         num_blocks=4, ctx_factory=ctx_factory)
        for d, p in zip(dense, paged):
            assert d.tokens_out == p.tokens_out, f"rid {d.rid}"
        assert pool.blocks_in_use == 0
