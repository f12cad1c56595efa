"""Integer-path deployment (``Mode.DEPLOY``): run quantized serving through
the Pallas kernels instead of simulating quantization in f32.

The PTQ pipeline (pipeline.py) produces *fake-quant* parameters: scales and
zero-points that the ``Mode.APPLY`` context uses to round-trip f32 tensors
through the integer grid while the matmuls stay full-precision. This module
turns that artifact into a *deployable* fixed-point program (paper eq. 3-5):

  * weights are pre-quantized ONCE into packed int8 payloads — ``{"q": int8
    (K, N), "s": f32 (), "colsum": int32 (G, N)}`` — cached **in the param
    pytree**, so a lax.scan over stacked layers slices per-layer packed
    weights exactly like it slices f32 weights (scales are traced leaves:
    no recompile per layer / per calibration);
  * activations flow between matmuls as :class:`QTensor` int8 payloads; the
    FFN chain  LN -> quant -> W_in matmul -> GELU -> requant -> W_out matmul
    executes as  ``ln/rms_quantize`` -> ``int8_matmul_peg`` (fused epilogue:
    bias + activation + re-quantize) -> ``int8_matmul`` with the f32
    intermediates never leaving VMEM;
  * the paper's range-based PEG permutation is folded into the packed weight
    rows and the (tiny) norm affine at pack time, so groups are contiguous
    lane-aligned spans at runtime.

Sub-8-bit weight payloads (paper Tables 5-7): a 4-bit policy packs two int4
rows per int8 byte (``{"q4": int8 (K/2, N), "s", "colsum"}`` — see
repro.kernels.nibble) and the matmul kernels unpack to int8 in VMEM, so the
MXU path is unchanged while HBM weight reads halve.

Models dispatch on ``is_packed(weight)`` / ``isinstance(x, QTensor)``; sites
whose calibration is missing or whose grouping the kernels cannot express
(non-uniform groups, non-4/8-bit, odd-K 4-bit, per-channel hidden scales)
simply stay on the fake-quant path — deployment degrades gracefully site by
site.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant_config import (Granularity, QuantizationPolicy,
                                     QuantizerConfig)
from repro.core.quantizer import QuantParams
from repro.core.range_estimation import estimate_weight_params
from repro.kernels import nibble
from repro.kernels import ops
from repro.kernels import ref as kref

# int8 payload grid: asymmetric uint8 parameters are shifted by -128 so every
# integer tensor in HBM is int8 (the standard uint8 -> int8 re-centering:
# q8 = q - 128, z8 = z - 128 leaves s * (q - z) unchanged).
_SHIFT = 128


class QTensor(NamedTuple):
    """An int8 activation payload between kernels.

    q: (..., K) int8, already in the layout its consumer weight expects
       (PEG sites: permuted/group-sorted); scales/zps: (G,) f32 on the
       shifted int8 grid. G == 1 is the per-tensor case.
    """
    q: jnp.ndarray
    scales: jnp.ndarray
    zps: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape


@dataclasses.dataclass(frozen=True)
class ActQuant:
    """Deploy-side quantizer for one matmul-input site (host-side constants +
    traced scale arrays; lives on the ctx, not in the param pytree)."""
    scales: jnp.ndarray            # (G,) f32
    zps: jnp.ndarray               # (G,) f32, shifted int8 grid
    qmin: int                      # shifted grid bounds
    qmax: int
    perm: Optional[jnp.ndarray]    # (d,) PEG permutation or None

    @property
    def per_tensor(self) -> bool:
        return int(self.scales.shape[0]) == 1 and self.perm is None


class KVQuant(NamedTuple):
    """Calibrated per-head grids for the int8 KV cache: the ``{prefix}/k`` /
    ``{prefix}/v`` sites' quantization step and zero-point (shifted onto the
    int8 grid; all f32, (KV,)). Registered in ``deploy_acts`` under the
    ``{prefix}/attn/kv`` site by :func:`build_deploy`.

    The cache write (repro.models.attention.quantize_kv) re-uses the site's
    own affine grid, so values the simulate path already snapped to that
    grid round-trip the int8 cache EXACTLY — deployment parity is limited by
    the attention arithmetic, not by cache storage. The zero-point is
    per-head STATIC (it lives here, not in the cache): the decode kernel
    folds it into per-program scalar corrections, keeping the per-slot
    payload zero-point-free and the S-loop free of zero-point gathers."""
    k_grid: jnp.ndarray
    v_grid: jnp.ndarray
    k_zp: jnp.ndarray
    v_zp: jnp.ndarray


def kv_quant_for(act_state, policy: QuantizationPolicy, attn_prefix: str,
                 num_kv_heads: int, bits: int = 8) -> Optional[KVQuant]:
    """Per-head k/v grids from the calibrated ``{prefix}/k``/``{prefix}/v``
    sites (paper Fig. 1): per-tensor scales broadcast over heads. Returns
    None for anything else — per-channel/PEG scales span (or permute) the
    head_dim axis, not the (KV, hd) head layout, and only the per-tensor
    grid gives the exact round-trip this packing exists for. The cache then
    quantizes purely dynamically per slot (or stays bf16, per the fallback
    rule).

    ``bits=4`` derives the same grids re-estimated on the int4 range: the
    calibrated site must itself be 4-bit for the exact-round-trip property,
    so a 4-bit request against an 8-bit calibration returns None and the
    cache quantizes dynamically on the [-7, 7] grid instead. Asymmetric
    grids shift by 2^(bits-1) (uint4 -> int4 re-centering, like _SHIFT)."""
    grids = []
    for name in ("k", "v"):
        site = f"{attn_prefix}/{name}"
        qp = act_state.get(site)
        if qp is None:
            return None
        cfg = policy.act_config(site)
        if not cfg.enabled or cfg.bits != bits or qp.group_index is not None \
                or jnp.size(qp.scale) != 1:
            return None
        scale = jnp.asarray(qp.scale, jnp.float32).reshape(())
        shift = 2 ** (bits - 1) if cfg.qmin == 0 else 0
        zp = jnp.asarray(qp.zero_point, jnp.float32).reshape(()) - shift
        grids.append((jnp.full((num_kv_heads,), scale),
                      jnp.full((num_kv_heads,), zp)))
    return KVQuant(k_grid=grids[0][0], v_grid=grids[1][0],
                   k_zp=grids[0][1], v_zp=grids[1][1])


def is_packed(w) -> bool:
    """True for a packed deployment weight: int8 (``q``) or nibble-packed
    int4 (``q4``) payload (vs f32 array / legacy {"q", "s"} storage, which
    lacks the colsum payload)."""
    return isinstance(w, dict) and ("q" in w or "q4" in w) and "colsum" in w


# ---------------------------------------------------------------------------
# Building the deployment artifact
# ---------------------------------------------------------------------------

def act_quant_for(qp: QuantParams, cfg: QuantizerConfig) -> Optional[ActQuant]:
    """Convert fake-quant activation params into a deployable ActQuant.
    Returns None when the kernels cannot express the site."""
    if cfg.bits != 8:
        return None
    shift = _SHIFT if cfg.qmin == 0 else 0
    qmin, qmax = cfg.qmin - shift, cfg.qmax - shift
    scale = jnp.atleast_1d(jnp.asarray(qp.scale, jnp.float32))
    zp = jnp.atleast_1d(jnp.asarray(qp.zero_point, jnp.float32)) - shift
    if qp.group_index is None:
        if scale.shape[0] != 1:          # per-channel/embedding: not packed
            return None
        return ActQuant(scales=scale, zps=zp, qmin=qmin, qmax=qmax, perm=None)
    gi = np.asarray(qp.group_index)
    counts = np.bincount(gi, minlength=scale.shape[0])
    if counts.min() != counts.max():     # kernel needs uniform groups
        return None
    perm = np.argsort(gi, kind="stable")
    perm_arr = None if np.array_equal(perm, np.arange(gi.shape[0])) \
        else jnp.asarray(perm)
    return ActQuant(scales=scale, zps=zp, qmin=qmin, qmax=qmax, perm=perm_arr)


def pack_linear(w, wcfg: QuantizerConfig, num_groups: int,
                perm: Optional[jnp.ndarray] = None) -> Optional[dict]:
    """Quantize one weight matrix (K, N) — or a stacked (L, K, N) — into the
    packed int + scale + per-group-colsum payload. Rows are permuted first
    when the consuming activation site uses the PEG permutation.

    8-bit configs emit ``{"q": int8 (K, N), ...}``; 4-bit configs emit
    ``{"q4": int8 (K/2, N), ...}`` with two int4 rows per byte
    (repro.kernels.nibble.pack_rows) — the colsum is always computed from
    the UNPACKED values, and the quantization grid is exactly the
    simulate-path fake-quant grid, so the payload round-trips bit-exactly.
    4-bit gating: K and the PEG group size must be even (else fall back)."""
    if not wcfg.enabled or wcfg.bits not in (4, 8) or not wcfg.symmetric \
            or wcfg.granularity != Granularity.PER_TENSOR:
        return None
    from repro.models.common import resolve_weight
    w = resolve_weight(w)
    k_dim = w.shape[-2]
    if wcfg.bits == 4 and (k_dim % 2 or (k_dim // num_groups) % 2):
        return None
    return _pack(w, perm, wcfg=wcfg, num_groups=num_groups)


@functools.partial(jax.jit, static_argnames=("wcfg", "num_groups"))
def _pack(w, perm, *, wcfg: QuantizerConfig, num_groups: int) -> dict:
    """Jitted, so the f32 round trip never exists as eager temporaries."""
    def _pack_one(w2):
        w2 = w2.astype(jnp.float32)
        if perm is not None:
            w2 = jnp.take(w2, perm, axis=0)
        qp = estimate_weight_params(w2, wcfg)
        s = jnp.maximum(qp.scale.astype(jnp.float32),
                        jnp.finfo(jnp.float32).tiny)
        wq = jnp.clip(jnp.round(w2 / s), wcfg.qmin,
                      wcfg.qmax).astype(jnp.int8)
        colsum = kref.w_colsum_groups(wq, num_groups)
        if wcfg.bits == 4:
            return {"q4": nibble.pack_rows(wq), "s": s, "colsum": colsum}
        return {"q": wq, "s": s, "colsum": colsum}

    if w.ndim == 3:
        # stacked scan layout: one layer at a time, so the f32 temporaries
        # are one layer's, not the stack's
        return jax.lax.map(_pack_one, w)
    return _pack_one(w)


DEPLOY_LINEARS = {"attn": ("wq", "wk", "wv", "wo"),
                  "ffn": ("w_gate", "w_up", "w_out", "w_in")}


def count_packed(params) -> Tuple[int, int]:
    """(packed, total) per-layer linears of a transformer param pytree over
    :data:`DEPLOY_LINEARS` — stacked scan leaves count once per layer."""
    packed = total = 0
    blocks = list(params.get("layers", [])) + list(params.get("scan", [])) \
        + list(params.get("tail", []))
    for bp in blocks:
        for part, names in DEPLOY_LINEARS.items():
            sub = bp.get(part)
            if not isinstance(sub, dict):
                continue
            for name in names:
                if name not in sub:
                    continue
                w = sub[name]
                leaf = jax.tree.leaves(w)[0]
                per = leaf.shape[0] if leaf.ndim == 3 else 1
                total += per
                packed += per if is_packed(w) else 0
    return packed, total


def _site(act_state, policy, name) -> Optional[ActQuant]:
    qp = act_state.get(name)
    if qp is None:
        return None
    return act_quant_for(qp, policy.act_config(name))


def _pack_ffn(bp: dict, prefix: str, policy: QuantizationPolicy,
              acts: Dict[str, ActQuant]) -> Optional[dict]:
    """Pack one block's FFN weights if every needed site deploys."""
    ffn = bp.get("ffn")
    if not isinstance(ffn, dict):
        return None
    in_aq = acts.get(f"{prefix}/ffn_in")
    hid_aq = acts.get(f"{prefix}/ffn/hidden")
    if in_aq is None or hid_aq is None or not hid_aq.per_tensor:
        return None
    g_in = int(in_aq.scales.shape[0])
    packed = dict(ffn)
    if "w_gate" in ffn:                  # GLU
        names = [("w_gate", g_in, in_aq.perm), ("w_up", g_in, in_aq.perm),
                 ("w_out", 1, None)]
    elif "w_in" in ffn:
        names = [("w_in", g_in, in_aq.perm), ("w_out", 1, None)]
    else:
        return None
    for name, g, perm in names:
        wcfg = policy.weight_config(f"{prefix}/ffn/{name}")
        pk = pack_linear(ffn[name], wcfg, g, perm)
        if pk is None:
            return None
        packed[name] = pk
    return packed


def _pack_attn(bp: dict, prefix: str, policy: QuantizationPolicy,
               acts: Dict[str, ActQuant]) -> Optional[dict]:
    attn = bp.get("attn")
    if not isinstance(attn, dict):
        return None
    in_aq = acts.get(f"{prefix}/attn_in")
    wo_aq = acts.get(f"{prefix}/attn/wo_in")
    if in_aq is None or wo_aq is None or not in_aq.per_tensor \
            or not wo_aq.per_tensor:
        return None
    packed = dict(attn)
    for name in ("wq", "wk", "wv", "wo"):
        wcfg = policy.weight_config(f"{prefix}/attn/{name}")
        pk = pack_linear(attn[name], wcfg, 1, None)
        if pk is None:
            return None
        packed[name] = pk
    return packed


def build_deploy(cfg, params, policy: QuantizationPolicy, act_state
                 ) -> Tuple[dict, Dict[str, ActQuant]]:
    """Pre-quantize every deployable linear in a transformer param pytree.

    Returns (packed_params, deploy_acts). ``packed_params`` replaces FFN /
    attention projection weights with packed payloads wherever the policy,
    the calibrated ``act_state`` and the kernel layout constraints allow;
    everything else is left untouched (those sites keep fake-quant APPLY
    behavior). ``deploy_acts`` maps input-site names to :class:`ActQuant`,
    plus ``{prefix}/attn/kv`` -> :class:`KVQuant` clip ranges for the int8
    KV cache. Works on both the stacked-scan and the unrolled param layouts.
    """
    acts: Dict[str, ActQuant] = {}
    for name, qp in act_state.items():
        aq = _site(act_state, policy, name)
        if aq is not None:
            acts[name] = aq

    def pack_block(bp, prefix):
        new = dict(bp)
        ffn = _pack_ffn(bp, prefix, policy, acts)
        if ffn is not None:
            new["ffn"] = ffn
        attn = _pack_attn(bp, prefix, policy, acts)
        if attn is not None:
            new["attn"] = attn
        if isinstance(bp.get("attn"), dict):
            # int8 KV cache clip ranges (independent of projection packing)
            kv = kv_quant_for(act_state, policy, f"{prefix}/attn",
                              cfg.num_kv_heads)
            if kv is not None:
                acts[f"{prefix}/attn/kv"] = kv
            # int4 grids under a separate site key: only present when the
            # k/v sites were themselves calibrated at 4 bits
            kv4 = kv_quant_for(act_state, policy, f"{prefix}/attn",
                               cfg.num_kv_heads, bits=4)
            if kv4 is not None:
                acts[f"{prefix}/attn/kv4"] = kv4
        return new

    packed = dict(params)
    if "scan" in params:
        packed["scan"] = [pack_block(bp, "layer") for bp in params["scan"]]
        packed["tail"] = [pack_block(bp, "tail") for bp in params["tail"]]
    if "layers" in params:
        packed["layers"] = [pack_block(bp, f"layer{i}")
                            for i, bp in enumerate(params["layers"])]
    return packed, acts


# ---------------------------------------------------------------------------
# Runtime entry points (called from repro.models)
# ---------------------------------------------------------------------------

def norm_quantize(norm_kind: str, p_norm: dict, x, aq: ActQuant) -> QTensor:
    """Fused norm + int8 emit for a matmul input: one VPU pass, the
    normalized f32 row never leaves VMEM. The PEG permutation (if any) is
    applied to the input and folded into the norm affine."""
    g = p_norm["g"]
    if aq.perm is not None:
        x = jnp.take(x, aq.perm, axis=-1)
        g = jnp.take(g, aq.perm, axis=0)
    if norm_kind == "layernorm":
        b = p_norm["b"]
        if aq.perm is not None:
            b = jnp.take(b, aq.perm, axis=0)
        q = ops.ln_quantize(x, g, b, aq.scales, aq.zps,
                            qmin=aq.qmin, qmax=aq.qmax)
    else:
        q = ops.rms_quantize(x, g, aq.scales, aq.zps,
                             qmin=aq.qmin, qmax=aq.qmax)
    return QTensor(q=q, scales=aq.scales, zps=aq.zps)


def quantize_act(x, aq: ActQuant) -> QTensor:
    """Plain fused quantize (no norm) — e.g. the Wo input after attention."""
    if aq.perm is not None:
        x = jnp.take(x, aq.perm, axis=-1)
    q = ops.peg_quantize(x, aq.scales, aq.zps, qmin=aq.qmin, qmax=aq.qmax)
    return QTensor(q=q, scales=aq.scales, zps=aq.zps)


def site_stats(x, aq: ActQuant) -> jnp.ndarray:
    """Quant-health vector ``[n_clipped, n_total, amax, cal_range]`` for a
    deploy-fused quantize site, computed from the f32 input the kernel is
    about to consume (mirrors quantizer.telemetry_stats on the shifted int8
    grid). Used only under ``--quant-telemetry``; the fused kernels
    themselves stay untouched."""
    xf = x.astype(jnp.float32)
    if aq.perm is not None:
        xf = jnp.take(xf, aq.perm, axis=-1)
    g = int(aq.scales.shape[0])
    if g > 1:                            # PEG: fold dims into (…, G, d/G)
        d = xf.shape[-1]
        xf = xf.reshape(xf.shape[:-1] + (g, d // g))
        s = aq.scales.reshape(g, 1)
        z = aq.zps.reshape(g, 1)
    else:
        s, z = aq.scales[0], aq.zps[0]
    s = jnp.maximum(s, jnp.finfo(jnp.float32).tiny)
    t = jnp.round(xf / s) + z
    clipped = jnp.sum((t < aq.qmin) | (t > aq.qmax))
    cal_range = jnp.max(jnp.maximum(jnp.abs(s * (aq.qmin - z)),
                                    jnp.abs(s * (aq.qmax - z))))
    return jnp.stack([clipped.astype(jnp.float32), jnp.float32(xf.size),
                      jnp.max(jnp.abs(xf)), cal_range.astype(jnp.float32)])


def qtensor_stats(qt: QTensor, aq: ActQuant) -> jnp.ndarray:
    """Saturation-only quant-health vector for a kernel-internal requant
    site (e.g. the FFN hidden emitted by the fused epilogue): the f32
    pre-quant values never leave VMEM, so ``n_clipped`` counts payload
    values sitting ON the grid edges and ``amax`` is the dequantized
    magnitude — capped at the grid edge, so ``amax_ratio`` tops out at ~1
    (docs/observability.md spells out the caveat)."""
    q = qt.q.astype(jnp.int32)
    sat = jnp.sum((q <= aq.qmin) | (q >= aq.qmax))
    # requant epilogues are per-tensor (enforced at pack time), so a scalar
    # grid suffices for the dequantized magnitude
    s = jnp.maximum(aq.scales[0], jnp.finfo(jnp.float32).tiny)
    z = aq.zps[0]
    deq_amax = jnp.max(jnp.abs((q.astype(jnp.float32) - z) * s))
    cal_range = jnp.maximum(jnp.abs(s * (aq.qmin - z)),
                            jnp.abs(s * (aq.qmax - z)))
    return jnp.stack([sat.astype(jnp.float32), jnp.float32(q.size),
                      deq_amax, cal_range.astype(jnp.float32)])


def matmul(x: QTensor, packed: dict, *, bias=None, mul=None,
           activation: str = "none", out_aq: Optional[ActQuant] = None):
    """Integer matmul against a packed weight, with the fused epilogue.

    G == 1 inputs take the per-tensor kernel (paper eq. 3), grouped inputs
    the PEG kernel (eq. 4->5). With ``out_aq`` the epilogue re-quantizes and
    the result is a :class:`QTensor`; otherwise f32.
    """
    kw = dict(bias=bias, mul=mul, activation=activation)
    if out_aq is not None:
        kw.update(out_scale=out_aq.scales[0], out_zp=out_aq.zps[0],
                  qmin=out_aq.qmin, qmax=out_aq.qmax)
    if "q4" in packed:                   # row-packed int4 payload
        w_q = packed["q4"]
        kw["w_bits"] = 4
    else:
        w_q = packed["q"]
    g = int(x.scales.shape[0])
    if g == 1:
        out = ops.int8_matmul(x.q, w_q, s_a=x.scales[0],
                              s_w=packed["s"], z_a=x.zps[0],
                              w_colsum=packed["colsum"][0], **kw)
    else:
        out = ops.int8_matmul_peg(x.q, w_q, x.scales, x.zps,
                                  w_scale=packed["s"],
                                  w_colsum=packed["colsum"], **kw)
    if out_aq is not None:
        return QTensor(q=out, scales=out_aq.scales, zps=out_aq.zps)
    return out
