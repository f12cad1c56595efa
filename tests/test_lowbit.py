"""Sub-8-bit deploy path (int4): nibble pack/unpack round-trips (both
layouts, odd dims, ragged tails — hypothesis when installed), kernel-vs-
oracle for the 4-bit attend/matmul paths, Quant4 cache invariants (payload
halving, subclass survives jit), paged == dense serving parity at
kv-bits 4, and bit-exact 4-bit weight payloads vs the simulate-path
fake-quant grid.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import QuantizerConfig, RangeEstimator
from repro.core.deploy import pack_linear
from repro.core.range_estimation import estimate_weight_params
from repro.kernels import nibble, ops, ref
from repro.models import attention as att
from repro.models import transformer as tfm
from repro.runtime import BlockPool, Request, serve
from repro.runtime.steps import (make_admit_step, make_decode_step,
                                 make_prefill_step)
from paged_testlib import int8_walk_case

pytestmark = pytest.mark.lowbit

try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    from hypothesis import given
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Nibble layouts: pack o unpack == identity over the int4 range
# ---------------------------------------------------------------------------

class TestNibbleRoundTrip:
    @pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 63, 64])
    def test_split_half_round_trip(self, n):
        """Odd n pads a spare high nibble; unpack drops it again."""
        rng = np.random.default_rng(n)
        x = rng.integers(-8, 8, size=(3, 5, n)).astype(np.int8)
        packed = nibble.pack_nibbles(jnp.asarray(x))
        assert packed.shape == (3, 5, nibble.packed_len(n))
        assert packed.dtype == jnp.int8
        out = nibble.unpack_nibbles(packed, n)
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_split_half_inner_axis(self):
        rng = np.random.default_rng(0)
        x = rng.integers(-8, 8, size=(2, 9, 4)).astype(np.int8)
        packed = nibble.pack_nibbles(jnp.asarray(x), axis=1)
        assert packed.shape == (2, 5, 4)
        out = nibble.unpack_nibbles(packed, 9, axis=1)
        np.testing.assert_array_equal(np.asarray(out), x)

    def test_split_half_extremes(self):
        """-8 and 7 (the two's-complement corners) survive the sext."""
        x = jnp.asarray([[-8, 7, -1, 0, 1, -7]], jnp.int8)
        out = nibble.unpack_nibbles(nibble.pack_nibbles(x), 6)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    @pytest.mark.parametrize("k", [2, 6, 128])
    def test_pairwise_rows_round_trip(self, k):
        rng = np.random.default_rng(k)
        w = rng.integers(-8, 8, size=(k, 12)).astype(np.int8)
        packed = nibble.pack_rows(jnp.asarray(w))
        assert packed.shape == (k // 2, 12)
        out = nibble.unpack_rows(packed)
        np.testing.assert_array_equal(np.asarray(out), w)

    def test_pairwise_rows_odd_k_rejected(self):
        with pytest.raises(ValueError, match="even K"):
            nibble.pack_rows(jnp.zeros((5, 4), jnp.int8))

    def test_packed_bytes_halved(self):
        x = jnp.zeros((4, 64), jnp.int8)
        assert np.asarray(nibble.pack_nibbles(x)).nbytes * 2 == \
            np.asarray(x).nbytes


if HAS_HYPOTHESIS:
    hypothesis.settings.register_profile(
        "lowbit", deadline=None, max_examples=25,
        suppress_health_check=[hypothesis.HealthCheck.too_slow])
    hypothesis.settings.load_profile("lowbit")

    int4_arrays = hnp.arrays(
        np.int8, hnp.array_shapes(min_dims=1, max_dims=3, min_side=1,
                                  max_side=33),
        elements=st.integers(-8, 7))

    @given(int4_arrays, st.data())
    def test_nibble_round_trip_property(x, data):
        """pack o unpack == identity on any shape / any axis (odd lengths
        exercise the ragged-tail pad-and-drop path)."""
        axis = data.draw(st.integers(-x.ndim, x.ndim - 1))
        n = x.shape[axis]
        out = nibble.unpack_nibbles(
            nibble.pack_nibbles(jnp.asarray(x), axis=axis), n, axis=axis)
        np.testing.assert_array_equal(np.asarray(out), x)

    @given(st.integers(1, 24), st.integers(1, 16), st.integers(0, 2 ** 31))
    def test_row_pack_round_trip_property(half_k, n, seed):
        rng = np.random.default_rng(seed)
        w = rng.integers(-8, 8, size=(2 * half_k, n)).astype(np.int8)
        out = nibble.unpack_rows(nibble.pack_rows(jnp.asarray(w)))
        np.testing.assert_array_equal(np.asarray(out), w)
else:                              # keep the skip visible in test reports
    @pytest.mark.skip(reason="hypothesis not installed "
                             "(see requirements-dev.txt)")
    def test_nibble_round_trip_property():
        pass


# ---------------------------------------------------------------------------
# Kernel vs oracle at kv_bits=4 / w_bits=4 (interpret mode)
# ---------------------------------------------------------------------------

def _int4_cache_operands(seed=0, B=2, S=64, KV=2, G=4, hd=64):
    rng = np.random.default_rng(seed)
    k4 = rng.integers(-8, 8, size=(B, S, KV, hd)).astype(np.int8)
    v4 = rng.integers(-8, 8, size=(B, S, KV, hd)).astype(np.int8)
    k_pk = np.asarray(nibble.pack_nibbles(jnp.asarray(k4)))
    v_pk = np.asarray(nibble.pack_nibbles(jnp.asarray(v4)))
    q = rng.integers(-127, 128, size=(B, KV, G, hd)).astype(np.int8)
    qs = rng.uniform(0.01, 0.02, size=(B, KV, G)).astype(np.float32)
    ks = rng.uniform(0.05, 0.1, size=(B, S, KV)).astype(np.float32)
    vs = rng.uniform(0.05, 0.1, size=(B, S, KV)).astype(np.float32)
    # shifted asymmetric grid (uint4 - 8): non-trivial zero points exercise
    # the rowsum/colsum corrections on the unpacked values
    kz = np.full((B, KV), -0.5, np.float32)
    vz = np.full((B, KV), 0.5, np.float32)
    k_pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    k_pos[1, 50:] = -1                                  # ragged lane
    q_pos = np.array([S - 1, 49], np.int32)
    return q, qs, k_pk, ks, v_pk, vs, kz, vz, k_pos, q_pos, hd


@pytest.mark.deploy
class TestInt4AttendKernel:
    def test_dense_matches_ref(self):
        (q, qs, k_pk, ks, v_pk, vs, kz, vz, k_pos, q_pos,
         hd) = _int4_cache_operands()
        got = ops.int8_attend_decode(q, qs, k_pk, ks, v_pk, vs, k_pos,
                                     q_pos, k_zp=kz, v_zp=vz, kv_bits=4,
                                     chunk=32)
        want = ref.int8_attend_decode_ref(
            jnp.asarray(q), jnp.asarray(qs), jnp.asarray(k_pk),
            jnp.asarray(ks), jnp.asarray(v_pk), jnp.asarray(vs),
            jnp.asarray(k_pos), jnp.asarray(q_pos), k_zp=jnp.asarray(kz),
            v_zp=jnp.asarray(vz), kv_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_dense_two_pass_matches_ref(self):
        """softmax_out quant forces the two-pass schedule: the packed V
        unpack sits inside the second pass's p@v closure."""
        (q, qs, k_pk, ks, v_pk, vs, kz, vz, k_pos, q_pos,
         hd) = _int4_cache_operands(seed=1)
        smo = jnp.asarray([1.0 / 255, 0.0], jnp.float32)
        got = ops.int8_attend_decode(q, qs, k_pk, ks, v_pk, vs, k_pos,
                                     q_pos, k_zp=kz, v_zp=vz,
                                     smo_quant=smo, kv_bits=4, chunk=32)
        want = ref.int8_attend_decode_ref(
            jnp.asarray(q), jnp.asarray(qs), jnp.asarray(k_pk),
            jnp.asarray(ks), jnp.asarray(v_pk), jnp.asarray(vs),
            jnp.asarray(k_pos), jnp.asarray(q_pos), k_zp=jnp.asarray(kz),
            v_zp=jnp.asarray(vz), smo_quant=smo, kv_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_paged_matches_ref(self):
        (q, qs, k_pk, ks, v_pk, vs, kz, vz, k_pos, q_pos,
         hd) = _int4_cache_operands()
        B, S, KV = k_pk.shape[0], k_pk.shape[1], k_pk.shape[2]
        bs = 16
        nb = S // bs
        n_blocks = B * nb + 1
        k_arena = np.zeros((n_blocks, bs, KV, hd // 2), np.int8)
        v_arena = np.zeros((n_blocks, bs, KV, hd // 2), np.int8)
        ks_arena = np.ones((n_blocks, bs, KV), np.float32)
        vs_arena = np.ones((n_blocks, bs, KV), np.float32)
        table = np.full((B, nb), -1, np.int32)
        pb = 1
        for b in range(B):
            written = int(q_pos[b]) + 1
            for lb in range(-(-written // bs)):
                table[b, lb] = pb
                lo, hi = lb * bs, (lb + 1) * bs
                k_arena[pb] = k_pk[b, lo:hi]
                v_arena[pb] = v_pk[b, lo:hi]
                ks_arena[pb] = ks[b, lo:hi]
                vs_arena[pb] = vs[b, lo:hi]
                pb += 1
        got = ops.paged_int8_attend_decode(q, qs, k_arena, ks_arena,
                                           v_arena, vs_arena, table, q_pos,
                                           s_cap=S, k_zp=kz, v_zp=vz,
                                           kv_bits=4)
        want = ref.paged_int8_attend_decode_ref(
            jnp.asarray(q), jnp.asarray(qs), jnp.asarray(k_arena),
            jnp.asarray(ks_arena), jnp.asarray(v_arena),
            jnp.asarray(vs_arena), jnp.asarray(table), jnp.asarray(q_pos),
            s_cap=S, k_zp=jnp.asarray(kz), v_zp=jnp.asarray(vz), kv_bits=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        # lanes at every edge of the live-bounded walk, stale pages past
        # each live bound, global and wrapped ring layers, both schedules
        int8_walk_case(None, None, False, "global", kv_bits=4)
        int8_walk_case(200, 30.0, True, "ring", kv_bits=4)


@pytest.mark.deploy
class TestInt4MatmulKernel:
    M, K, N, G = 32, 128, 128, 4

    def _weights(self, seed=0):
        rng = np.random.default_rng(seed)
        a_q = rng.integers(-128, 128, size=(self.M, self.K)).astype(np.int8)
        w4 = rng.integers(-7, 8, size=(self.K, self.N)).astype(np.int8)
        w_pk = np.asarray(nibble.pack_rows(jnp.asarray(w4)))
        return a_q, w4, w_pk

    def test_matmul_matches_ref(self):
        a_q, w4, w_pk = self._weights()
        colsum = np.sum(w4.astype(np.int32), axis=0)
        got = ops.int8_matmul(a_q, w_pk, s_a=0.02, s_w=0.01, z_a=3.0,
                              w_colsum=colsum, w_bits=4)
        want = ref.int8_matmul_fused_ref(jnp.asarray(a_q), jnp.asarray(w4),
                                         0.02, 0.01, z_a=3.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_peg_matmul_matches_ref(self):
        a_q, w4, w_pk = self._weights(seed=1)
        rng = np.random.default_rng(2)
        act_s = rng.uniform(0.01, 0.02, size=(self.G,)).astype(np.float32)
        act_z = rng.uniform(-2, 2, size=(self.G,)).astype(np.float32)
        wcs = ref.w_colsum_groups(jnp.asarray(w4), self.G)
        got = ops.int8_matmul_peg(a_q, w_pk, act_s, act_z, w_scale=0.01,
                                  w_colsum=wcs, w_bits=4)
        want = ref.int8_matmul_peg_fused_ref(
            jnp.asarray(a_q), jnp.asarray(w4), jnp.asarray(act_s),
            jnp.asarray(act_z), 0.01)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    def test_auto_colsum_refused_for_packed_bytes(self):
        """Summing packed bytes would be silently wrong — the ops layer
        must demand the caller's unpacked colsum at w_bits=4."""
        a_q, w4, w_pk = self._weights()
        with pytest.raises(ValueError, match="w_colsum"):
            ops.int8_matmul(a_q, w_pk, s_a=0.02, s_w=0.01, z_a=3.0,
                            w_bits=4)


# ---------------------------------------------------------------------------
# Quant4 cache invariants
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


class TestQuant4Cache:
    def test_payload_bytes_halved(self, tiny):
        cfg, _ = tiny
        c8 = tfm.init_cache(cfg, 2, MAX_LEN, dtype=jnp.float32, kv_bits=8)
        c4 = tfm.init_cache(cfg, 2, MAX_LEN, dtype=jnp.float32, kv_bits=4)

        def payload_bytes(cache):
            return sum(n.k_q.nbytes + n.v_q.nbytes
                       for n in list(cache["scan"]) + list(cache["tail"]))
        assert 2 * payload_bytes(c4) == payload_bytes(c8)

    def test_dynamic_quantize_round_trip_error_bound(self):
        """quantize_kv4 (dynamic symmetric, [-7, 7]) reconstructs within
        half a step of the per-head grid."""
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 2, 16))
        packed, s = att.quantize_kv4(x)
        assert packed.shape == (2, 6, 2, 8)
        vals = nibble.unpack_nibbles(packed, 16).astype(jnp.float32)
        recon = vals * s[..., None]
        err = np.abs(np.asarray(recon) - np.asarray(x))
        assert (err <= np.asarray(s)[..., None] * 0.5 + 1e-6).all()

    def test_dequantize_kv_unpacks(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 8))
        packed, s = att.quantize_kv4(x)
        cache = att.Quant4KVCache(
            k_q=packed, v_q=packed, k_s=s, v_s=s,
            pos=jnp.zeros((1, 4), jnp.int32))
        k, v = att.dequantize_kv(cache)
        assert k.shape == x.shape
        np.testing.assert_allclose(np.asarray(k), np.asarray(v))

    def test_subclass_survives_jit_prefill(self, tiny):
        """The Quant4 type IS the bit-width marker — tracing through the
        jitted prefill step must hand it back intact."""
        cfg, params = tiny
        prefill = jax.jit(make_prefill_step(cfg))
        cache = tfm.init_cache(cfg, 2, MAX_LEN, dtype=jnp.float32,
                               kv_bits=4)
        toks = np.ones((2, 5), np.int32)
        posm = np.tile(np.arange(5, dtype=np.int32), (2, 1))
        _, cache = prefill(params, toks, cache, posm)
        nodes = list(cache["scan"]) + list(cache["tail"])
        assert nodes and all(isinstance(n, att.Quant4KVCache)
                             for n in nodes)


# ---------------------------------------------------------------------------
# Serving parity: paged == dense greedy tokens at kv-bits 4
# ---------------------------------------------------------------------------

def _mk_reqs(seed, cfg, lens_quotas):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size, size=n)
                    .astype(np.int32),
                    max_new_tokens=q)
            for i, (n, q) in enumerate(lens_quotas)]


def _serve(cfg, params, reqs, *, paged, ctx_factory, num_blocks=None):
    admit = jax.jit(make_admit_step(cfg, ctx_factory=ctx_factory))
    decode = jax.jit(make_decode_step(cfg, ctx_factory=ctx_factory))
    prefill = jax.jit(make_prefill_step(cfg, ctx_factory=ctx_factory))
    pool = (BlockPool(num_blocks or 2 * NB_LANE, BS, 2, NB_LANE)
            if paged else None)

    def init(b):
        if not paged:
            return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                                  kv_bits=4)
        return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                              kv_bits=4, paged=True, block_size=BS,
                              num_blocks=num_blocks, mapped=False)
    serve(prefill, admit, decode, init, params, reqs,
          scheduler="continuous", batch_slots=2, max_len=MAX_LEN,
          block_pool=pool)
    return pool


@pytest.mark.deploy
@pytest.mark.serve
@pytest.mark.paged
class TestPagedDenseParityKv4:
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

    def test_paged_matches_dense_kv4(self, deployed):
        """int4 quantization is deterministic per write, so the packed
        paged and dense caches still agree token-for-token (the same
        exactness contract the int8 path asserts)."""
        cfg, packed, ctx_factory = deployed
        spec = [(4, 2), (8, 6), (3, 1), (6, 4)]
        dense = _mk_reqs(5, cfg, spec)
        paged = _mk_reqs(5, cfg, spec)
        _serve(cfg, packed, dense, paged=False, ctx_factory=ctx_factory)
        pool = _serve(cfg, packed, paged, paged=True, num_blocks=4,
                      ctx_factory=ctx_factory)
        for d, p in zip(dense, paged):
            assert d.tokens_out == p.tokens_out, f"rid {d.rid}"
            assert p.done
        assert pool.blocks_in_use == 0, "block leak after retirement"


# ---------------------------------------------------------------------------
# 4-bit weight payloads: bit-exact vs the simulate-path fake-quant grid
# ---------------------------------------------------------------------------

W4 = QuantizerConfig(bits=4, symmetric=True, estimator=RangeEstimator.MSE)


@pytest.mark.deploy
class TestWeightQ4Payload:
    def test_payload_round_trips_bit_exactly(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        payload = pack_linear(w, W4, num_groups=4)
        assert payload is not None and "q4" in payload
        assert payload["q4"].shape == (32, 48)
        # the exact grid the simulate path fake-quantizes on
        qp = estimate_weight_params(w, W4)
        s = jnp.maximum(qp.scale.astype(jnp.float32),
                        jnp.finfo(jnp.float32).tiny)
        wq = jnp.clip(jnp.round(w / s), W4.qmin, W4.qmax).astype(jnp.int8)
        np.testing.assert_array_equal(
            np.asarray(nibble.unpack_rows(payload["q4"])), np.asarray(wq))
        np.testing.assert_allclose(float(payload["s"]), float(s))
        np.testing.assert_array_equal(
            np.asarray(payload["colsum"]),
            np.asarray(ref.w_colsum_groups(wq, 4)))

    def test_stacked_layout_packs_per_layer(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 8))
        payload = pack_linear(w, W4, num_groups=2)
        assert payload is not None
        assert payload["q4"].shape == (3, 8, 8)
        for layer in range(3):
            single = pack_linear(w[layer], W4, num_groups=2)
            np.testing.assert_array_equal(np.asarray(payload["q4"][layer]),
                                          np.asarray(single["q4"]))

    @pytest.mark.parametrize("k,groups", [(15, 1), (18, 6)])
    def test_inexpressible_sites_fall_back(self, k, groups):
        """Odd K (no whole bytes) or odd PEG group size (group boundary
        would straddle a byte) must decline to pack — the site then keeps
        fake-quant APPLY behavior, exactly as before this path existed."""
        w = jax.random.normal(jax.random.PRNGKey(2), (k, 8))
        assert pack_linear(w, W4, num_groups=groups) is None

    def test_unsupported_bits_fall_back(self):
        w = jax.random.normal(jax.random.PRNGKey(3), (16, 8))
        cfg3 = QuantizerConfig(bits=3, symmetric=True)
        assert pack_linear(w, cfg3, num_groups=1) is None
