"""Compile the serving path's Pallas kernels for a described TPU v5e.

The CPU test suite runs every kernel in the Pallas interpreter, which checks
none of Mosaic's limits: block shapes that do not tile (8, 128), rank-1
scalar blocks, lane repeats, scoped-VMEM overflow. These tests lower each
kernel with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology (no chip attached: nothing runs) at the published widths of
h2o-danube3-4b — d_model 3840, 32 query / 8 kv heads of 120, d_ff 10240 —
plus the 4-kv-head and int4-cache variants, at decode (8 rows) and prefill
row counts. A refusal here is what the chip's compiler would raise.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and only the test worker
that runs this file should.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, HD, F = 3840, 120, 10240          # h2o-danube3-4b
DECODE, PREFILL = 8, 1024            # 8 lanes; 8 lanes x 128-token chunks
S_LEN, CHUNK = 1024, 256             # dense cache length (max-len)
BS, NB = 16, 64                      # paged: block size, blocks per lane
N_BLOCKS = DECODE * NB
i8, i32, f32, bf16 = jnp.int8, jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


# a kernel's custom call: its HLO name and its op_name metadata
KERNEL_CALL = re.compile(r'%([\w-]+?)(?:\.\d+)? = [^\n]*custom_call_target='
                         r'"tpu_custom_call"[^\n]*op_name="([^"]*)"')


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # every kernel carries its name (pallas_call name=), the one its HLO
    # op is named by and the benchmark's trace readers match
    calls = KERNEL_CALL.findall(text)
    assert calls
    for hlo, path in calls:
        assert path.endswith(f"/{hlo}/pallas_call"), (hlo, path)


# ---------------------------------------------------------------------------
# int8 matmuls: the attention projections and the FFN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [DECODE, PREFILL])
@pytest.mark.parametrize("k,n", [(D, D), (D, 960), (D, 480), (F, D)],
                         ids=["wq_wo", "wk_wv", "wk_wv_kv4", "w_out"])
def test_int8_matmul(one_chip, rows, k, n):
    def fn(a, w, colsum):
        return ops.int8_matmul(a, w, s_a=0.1, s_w=0.01, z_a=3.0,
                               w_colsum=colsum, interpret=False)
    _compile(one_chip, fn, ((rows, k), i8), ((k, n), i8), ((n,), i32))


@pytest.mark.parametrize("rows", [2, DECODE, PREFILL])
@pytest.mark.parametrize("epilogue", ["plain", "silu_mul_requant"])
def test_int8_matmul_peg(one_chip, rows, epilogue):
    """w_up (plain f32 out) and w_gate (silu * up, re-quantized), G=4: the
    960-wide groups cross the 384-wide k-blocks."""
    def fn(a, w, s, z, colsum, up):
        kw = {}
        if epilogue != "plain":
            kw = dict(activation="silu", mul=up, out_scale=0.05, out_zp=0.0)
        return ops.int8_matmul_peg(a, w, s, z, w_scale=0.01,
                                   w_colsum=colsum, interpret=False, **kw)
    _compile(one_chip, fn, ((rows, D), i8), ((D, F), i8), ((4,), f32),
             ((4,), f32), ((4, F), i32), ((rows, F), f32))


# ---------------------------------------------------------------------------
# fused norm + quantize, PEG quantize
# ---------------------------------------------------------------------------

def _rms_quantize(sharding, rows, groups, dtype):
    def fn(x, g, s, z):
        return ops.rms_quantize(x, g, s, z, qmin=-128, qmax=127,
                                interpret=False)
    _compile(sharding, fn, ((rows, D), dtype), ((D,), bf16),
             ((groups,), f32), ((groups,), f32))


def _peg_quantize(sharding, rows, groups, dtype):
    def fn(x, s, z):
        return ops.peg_quantize(x, s, z, qmin=-128, qmax=127,
                                interpret=False)
    _compile(sharding, fn, ((rows, D), dtype), ((groups,), f32),
             ((groups,), f32))


@pytest.mark.parametrize("rows", [DECODE, 2048])
@pytest.mark.parametrize("groups", [1, 4])
def test_rms_quantize(one_chip, rows, groups):
    _rms_quantize(one_chip, rows, groups, bf16)


@pytest.mark.parametrize("rows", [DECODE, 2048])
@pytest.mark.parametrize("groups", [1, 4])
def test_rms_quantize_f32(one_chip, rows, groups):
    """The integer path's residual stream is f32 (models/transformer.py
    ``_embed``): its norms quantize f32 rows."""
    _rms_quantize(one_chip, rows, groups, f32)


@pytest.mark.parametrize("rows", [DECODE, PREFILL])
@pytest.mark.parametrize("groups", [1, 4])
def test_peg_quantize(one_chip, rows, groups):
    _peg_quantize(one_chip, rows, groups, bf16)


@pytest.mark.parametrize("rows", [DECODE, PREFILL])
@pytest.mark.parametrize("groups", [1, 4])
def test_peg_quantize_f32(one_chip, rows, groups):
    """The attention output reaches the Wo input quantizer in f32."""
    _peg_quantize(one_chip, rows, groups, f32)


# ---------------------------------------------------------------------------
# decode attention: dense int8 cache, paged bf16 and int8 caches
# ---------------------------------------------------------------------------

def _site_kwargs(two_pass, sm):
    return dict(sm_quant=sm, smo_quant=sm) if two_pass else {}


@pytest.mark.parametrize("kv,hd,kv_bits,two_pass", [
    (8, HD, 8, False), (8, HD, 8, True), (8, 128, 8, False),
    (8, HD, 4, False), (4, HD, 8, False), (4, HD, 4, False)])
def test_int8_attend_decode(one_chip, kv, hd, kv_bits, two_pass):
    g = 32 // kv
    w = hd // 2 if kv_bits == 4 else hd

    def fn(q, qs, k, ks, v, vs, kp, qp, zp, sm):
        return ops.int8_attend_decode(
            q, qs, k, ks, v, vs, kp, qp, q_zp=qs, k_zp=zp, v_zp=zp,
            window=4096, chunk=CHUNK, kv_bits=kv_bits, interpret=False,
            **_site_kwargs(two_pass, sm))
    _compile(one_chip, fn, ((DECODE, kv, g, hd), i8), ((DECODE, kv, g), f32),
             ((DECODE, S_LEN, kv, w), i8), ((DECODE, S_LEN, kv), f32),
             ((DECODE, S_LEN, kv, w), i8), ((DECODE, S_LEN, kv), f32),
             ((DECODE, S_LEN), i32), ((DECODE,), i32), ((DECODE, kv), f32),
             ((2,), f32))


@pytest.mark.parametrize("kv,cache_dtype,lanes,nb", [
    pytest.param(8, bf16, DECODE, NB, id="bf16-8"),
    pytest.param(8, f32, DECODE, NB, id="f32-8"),
    pytest.param(4, bf16, DECODE, NB, id="bf16-4"),
    pytest.param(4, f32, DECODE, NB, id="f32-4"),
    # the served cell danube3-4b-bf16.chat-16: 16 lanes, 152 pages of 16
    # each, a bf16 arena of 2,432 pages
    pytest.param(8, bf16, 16, 152, id="chat-16")])
def test_paged_attend_decode(one_chip, kv, cache_dtype, lanes, nb):
    g = 32 // kv

    def fn(q, k, v, tbl, qp):
        return ops.paged_attend_decode(q, k, v, tbl, qp, s_cap=nb * BS,
                                       window=4096, interpret=False)
    _compile(one_chip, fn, ((lanes, kv, g, HD), f32),
             ((lanes * nb, BS, kv, HD), cache_dtype),
             ((lanes * nb, BS, kv, HD), cache_dtype), ((lanes, nb), i32),
             ((lanes,), i32))


@pytest.mark.parametrize("kv,kv_bits,two_pass", [
    (8, 8, False), (8, 8, True), (8, 4, False), (4, 8, False),
    (4, 4, True)])
def test_paged_int8_attend_decode(one_chip, kv, kv_bits, two_pass):
    g = 32 // kv
    w = HD // 2 if kv_bits == 4 else HD

    def fn(q, qs, k, ks, v, vs, tbl, qp, zp, sm):
        return ops.paged_int8_attend_decode(
            q, qs, k, ks, v, vs, tbl, qp, s_cap=S_LEN, q_zp=qs, k_zp=zp,
            v_zp=zp, window=4096, kv_bits=kv_bits, interpret=False,
            **_site_kwargs(two_pass, sm))
    _compile(one_chip, fn, ((DECODE, kv, g, HD), i8), ((DECODE, kv, g), f32),
             ((N_BLOCKS, BS, kv, w), i8), ((N_BLOCKS, BS, kv), f32),
             ((N_BLOCKS, BS, kv, w), i8), ((N_BLOCKS, BS, kv), f32),
             ((DECODE, NB), i32), ((DECODE,), i32), ((DECODE, kv), f32),
             ((2,), f32))
