"""Unified decoder-only transformer covering the assigned LM families:
dense (GQA/MQA, sliding-window, local+global alternating, soft-capping),
MoE, hybrid RG-LRU (Griffin), and attention-free RWKV6 — with the paper's
quantization sites threaded throughout.

Two execution layouts share the same block functions:
  * stacked + lax.scan over "super-blocks" (one repeat of cfg.block_pattern)
    — the production path; compiles O(1) HLO in depth.
  * unrolled Python loop — for smoke tests, calibration and per-layer
    quantization experiments (sites get per-layer names ``layer{i}/...``).

Quantization sites per block (paper Fig. 1 / Table 2 naming):
  {L}/residual_attn     — residual sum after self-attention
  {L}/ffn_in            — FFN input (LN output)
  {L}/ffn_out           — FFN output (before residual add)
  {L}/residual_ffn      — THE paper bottleneck: residual sum after FFN
plus the attention-internal sites from attention.py and:
  embed/sum, head/logits
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ffn as ffn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.attention import (AttnConfig, KVCache, PagedKVCache,
                                    PagedQuant4KVCache, PagedQuantKVCache,
                                    Quant4KVCache, QuantKVCache,
                                    attention_block, init_attention_params,
                                    init_kv_cache, init_paged_kv_cache,
                                    init_paged_quant4_kv_cache,
                                    init_paged_quant_kv_cache,
                                    init_quant4_kv_cache,
                                    init_quant_kv_cache, reset_kv_lanes,
                                    reset_paged_lanes)
from repro.models.common import (cross_entropy, embed_init, layer_norm,
                                 rms_norm, softcap, split_keys)


# ---------------------------------------------------------------------------
# Distribution context (kept minimal; rules live in repro/parallel)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Any
    tp_axis: str = "model"
    fsdp_axis: Any = "data"                  # str or tuple (pod FSDP)
    dp_axes: Tuple[str, ...] = ("data",)     # ("pod","data") multi-pod
    onehot_embed: bool = False               # perf: vocab-sharded einsum
    quantized_gathers: bool = False          # perf: int8 FSDP weight gathers

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["g"], p["b"])
    return rms_norm(x, p["g"])


def _init_norm(cfg: ModelConfig, dtype):
    if cfg.norm == "layernorm":
        return {"g": jnp.ones((cfg.d_model,), dtype),
                "b": jnp.zeros((cfg.d_model,), dtype)}
    return {"g": jnp.zeros((cfg.d_model,), dtype)}   # rms: 1 + g


# ---------------------------------------------------------------------------
# Attention config per block kind
# ---------------------------------------------------------------------------

def attn_cfg_for(cfg: ModelConfig, kind: str) -> AttnConfig:
    window = cfg.window
    if kind == "local_attn":
        window = cfg.local_window
    return AttnConfig(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                      head_dim=cfg.hd, causal=True, window=window,
                      logit_softcap=cfg.attn_logit_softcap,
                      rope_theta=cfg.rope_theta)


# ---------------------------------------------------------------------------
# FFN dispatch (dense / GLU / MoE, optionally expert-parallel)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg: ModelConfig, p, x, *, ctx, prefix, dist: Optional[DistContext]):
    if cfg.moe is not None:
        B, T, D = x.shape
        if dist is not None and dist.tp_size > 1:
            return _moe_sharded(cfg, p, x, dist)
        out = moe_lib.moe_apply(p, x.reshape(B * T, D), cfg.moe, ctx=ctx,
                                prefix=prefix)
        return out.reshape(B, T, D)
    if cfg.ffn_type == "glu":
        return ffn_lib.glu_mlp(p, x, activation=cfg.act, ctx=ctx, prefix=prefix)
    return ffn_lib.mlp(p, x, activation=cfg.act, ctx=ctx, prefix=prefix)


def _moe_sharded(cfg: ModelConfig, p, x, dist: DistContext):
    """Expert-parallel MoE via shard_map (DESIGN.md §4): FLATTENED tokens
    data-sharded, experts model-sharded, FSDP re-gather of expert weights
    inside. Token count not divisible by the dp group -> tokens replicate
    (each shard computes its experts over all tokens)."""
    from jax.sharding import PartitionSpec as P
    import numpy as np
    mesh = dist.mesh
    tp, fsdp, dp = dist.tp_axis, dist.fsdp_axis, dist.dp_axes
    ep_size = mesh.shape[tp]
    mcfg = cfg.moe
    B, T, D = x.shape
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    tok_spec = P(dp, None) if (B * T) % dp_size == 0 else P(None, None)

    # E >= tp: expert parallelism (E/tp experts per shard). E < tp (grok-1:
    # 8 experts, 16 shards): hybrid — every shard holds ALL experts with a
    # d_ff slice (TP inside experts); the end psum reduces partial-F sums.
    expert_parallel = mcfg.num_experts % ep_size == 0

    def _gather(w, axis):
        if not dist.quantized_gathers:
            return jax.lax.all_gather(w, fsdp, axis=axis, tiled=True)
        # perf variant: int8 wire format for the per-layer FSDP weight
        # gathers (the paper's symmetric per-tensor weight quantization
        # applied to the collective payload) — 2x fewer ICI/DCN bytes.
        amax = jnp.max(jnp.abs(w))
        s_w = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / s_w),
                     -127, 127).astype(jnp.int8)
        q_full = jax.lax.all_gather(q, fsdp, axis=axis, tiled=True)
        s_full = jax.lax.all_gather(s_w[None], fsdp, axis=0)
        # every shard contributed its own scale; payload dequantizes with
        # the max (scales are near-identical for homogeneous shards; exact
        # per-shard dequant would segment the axis — done on real HW)
        return q_full.astype(w.dtype) * jnp.max(s_full).astype(w.dtype)

    def body(router, wg, wu, wo, xt):
        router = _gather(router, 0)
        wg = _gather(wg, 1)
        wu = _gather(wu, 1)
        wo = _gather(wo, 2)
        return moe_lib.moe_apply_sharded(
            {"router": router, "w_gate": wg, "w_up": wu, "w_out": wo},
            xt, mcfg, ep_axis=tp, ep_size=ep_size,
            expert_parallel=expert_parallel)

    if expert_parallel:
        w_specs = (P(tp, fsdp, None), P(tp, fsdp, None), P(tp, None, fsdp))
    else:
        w_specs = (P(None, fsdp, tp), P(None, fsdp, tp), P(None, tp, fsdp))

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(fsdp, None),) + w_specs + (tok_spec,),
        out_specs=tok_spec,
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_out"], x.reshape(B * T, D))
    return out.reshape(B, T, D)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _ffn_packed(p) -> bool:
    from repro.core import deploy
    ffn = p.get("ffn")
    return isinstance(ffn, dict) and deploy.is_packed(
        ffn.get("w_in", ffn.get("w_gate")))


def _attn_packed(p) -> bool:
    from repro.core import deploy
    attn = p.get("attn")
    return isinstance(attn, dict) and deploy.is_packed(attn.get("wq"))


def _ffn_input(cfg: ModelConfig, p, x, ctx, prefix):
    """LN2 + the ffn_in quantizer. In DEPLOY mode with packed FFN weights the
    two fuse into one norm+int8-emit kernel pass returning a QTensor."""
    if ctx is not None:
        aq = ctx.deploy_act(f"{prefix}/ffn_in")
        if aq is not None and _ffn_packed(p):
            from repro.core import deploy
            if ctx.telemetry is not None:
                ctx.telem_site(f"{prefix}/ffn_in",
                               deploy.site_stats(_norm(cfg, p["ln2"], x), aq))
            return deploy.norm_quantize(cfg.norm, p["ln2"], x, aq)
    h = _norm(cfg, p["ln2"], x)
    if ctx is not None:
        h = ctx.act(f"{prefix}/ffn_in", h)
    return h


def _attn_input(cfg: ModelConfig, p, x, ctx, prefix):
    """LN1 + the attn_in input quantizer (fused in DEPLOY, see _ffn_input)."""
    if ctx is not None:
        aq = ctx.deploy_act(f"{prefix}/attn_in")
        if aq is not None and _attn_packed(p):
            from repro.core import deploy
            if ctx.telemetry is not None:
                ctx.telem_site(f"{prefix}/attn_in",
                               deploy.site_stats(_norm(cfg, p["ln1"], x), aq))
            return deploy.norm_quantize(cfg.norm, p["ln1"], x, aq)
    h = _norm(cfg, p["ln1"], x)
    if ctx is not None:
        h = ctx.act_in(f"{prefix}/attn_in", h)
    return h


def block_apply(cfg: ModelConfig, kind: str, p, x, positions, *, ctx=None,
                prefix="layer", cache=None, dist=None, chunked=None,
                block_table=None, append=False):
    """One transformer block of the given kind. Returns (x, new_cache)."""
    if append and kind not in ("attn", "local_attn"):
        raise ValueError(
            f"chunked (append) prefill supports attention blocks only, got "
            f"{kind!r} (recurrent state cannot replay earlier chunks)")
    if kind in ("attn", "local_attn"):
        # named scopes (norm, attn/{qkv,attend,kv_write,out}, ffn) tag the
        # device ops with the model code that issued them in a profile
        acfg = attn_cfg_for(cfg, kind)
        with jax.named_scope("norm"):
            h = _attn_input(cfg, p, x, ctx, prefix)
        with jax.named_scope("attn"):
            attn_out, new_cache = attention_block(
                p["attn"], h, positions, acfg, ctx=ctx,
                prefix=f"{prefix}/attn", cache=cache, chunked=chunked,
                block_table=block_table, append=append, dist=dist)
        if cfg.post_norm:
            with jax.named_scope("norm"):
                attn_out = _norm(cfg, p["post_ln1"], attn_out)
        # the residual stream keeps its dtype (f32 on the integer path,
        # whose kernels emit f32; see _embed)
        x = x + attn_out.astype(x.dtype)
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_attn", x)
        with jax.named_scope("norm"):
            h = _ffn_input(cfg, p, x, ctx, prefix)
        with jax.named_scope("ffn"):
            ffn_out = _ffn_apply(cfg, p.get("moe", p.get("ffn")), h,
                                 ctx=ctx, prefix=f"{prefix}/ffn", dist=dist)
        if cfg.post_norm:
            with jax.named_scope("norm"):
                ffn_out = _norm(cfg, p["post_ln2"], ffn_out)
        if ctx is not None:
            ffn_out = ctx.act(f"{prefix}/ffn_out", ffn_out)
        x = x + ffn_out.astype(x.dtype)
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_ffn", x)
        return x, new_cache

    if kind == "rec":
        h = _norm(cfg, p["ln1"], x)
        rec_out, new_state = rglru_lib.recurrent_block(
            p["rec"], h, state=cache, ctx=ctx, prefix=f"{prefix}/rec")
        x = x + rec_out
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_attn", x)
        h = _ffn_input(cfg, p, x, ctx, prefix)
        ffn_out = _ffn_apply(cfg, p["ffn"], h, ctx=ctx, prefix=f"{prefix}/ffn",
                             dist=dist)
        if ctx is not None:
            ffn_out = ctx.act(f"{prefix}/ffn_out", ffn_out)
        x = x + ffn_out.astype(x.dtype)
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_ffn", x)
        return x, new_state

    if kind == "rwkv":
        h = _norm(cfg, p["ln1"], x)
        tm_out, st = rwkv_lib.time_mix(p["tmix"], h, cfg.rwkv_head_size,
                                       state=cache, ctx=ctx,
                                       prefix=f"{prefix}/tmix")
        x = x + tm_out
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_attn", x)
        h = _norm(cfg, p["ln2"], x)
        cm_out, st = rwkv_lib.channel_mix(p["cmix"], h, state=st, ctx=ctx,
                                          prefix=f"{prefix}/cmix")
        x = x + cm_out
        if ctx is not None:
            x = ctx.act(f"{prefix}/residual_ffn", x)
        return x, st

    raise ValueError(f"unknown block kind {kind!r}")


def init_block_params(cfg: ModelConfig, kind: str, key, dtype):
    ks = split_keys(key, 4)
    p: Dict[str, Any] = {"ln1": _init_norm(cfg, dtype),
                         "ln2": _init_norm(cfg, dtype)}
    if kind in ("attn", "local_attn"):
        p["attn"] = init_attention_params(ks[0], cfg.d_model,
                                          attn_cfg_for(cfg, kind), dtype,
                                          qk_norm=cfg.qk_norm)
        if cfg.moe is not None:
            p["moe"] = moe_lib.init_moe_params(ks[1], cfg.d_model, cfg.moe,
                                               dtype)
        elif cfg.ffn_type == "glu":
            p["ffn"] = ffn_lib.init_glu_params(ks[1], cfg.d_model, cfg.d_ff,
                                               dtype)
        else:
            p["ffn"] = ffn_lib.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                               dtype)
        if cfg.post_norm:
            p["post_ln1"] = _init_norm(cfg, dtype)
            p["post_ln2"] = _init_norm(cfg, dtype)
    elif kind == "rec":
        p["rec"] = rglru_lib.init_recurrent_params(
            ks[0], cfg.d_model, cfg.d_rnn or cfg.d_model, dtype)
        p["ffn"] = (ffn_lib.init_glu_params(ks[1], cfg.d_model, cfg.d_ff, dtype)
                    if cfg.ffn_type == "glu" else
                    ffn_lib.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff, dtype))
    elif kind == "rwkv":
        tm = rwkv_lib.init_rwkv_params(ks[0], cfg.d_model, cfg.d_ff,
                                       cfg.rwkv_head_size, dtype)
        p["tmix"] = {k: v for k, v in tm.items()
                     if not k.startswith(("w_c", "mu_c"))}
        p["cmix"] = {k: v for k, v in tm.items()
                     if k.startswith(("w_c", "mu_c"))}
    else:
        raise ValueError(kind)
    return p


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16, kv_bits: int = 16,
                     paged_blocks: Optional[Tuple[int, int]] = None):
    if kind in ("attn", "local_attn"):
        acfg = attn_cfg_for(cfg, kind)
        if paged_blocks is not None:
            num_blocks, block_size = paged_blocks
            if kv_bits == 4:
                return init_paged_quant4_kv_cache(num_blocks, block_size,
                                                  acfg)
            if kv_bits == 8:
                return init_paged_quant_kv_cache(num_blocks, block_size,
                                                 acfg)
            return init_paged_kv_cache(num_blocks, block_size, acfg, dtype)
        if kv_bits == 4:
            return init_quant4_kv_cache(batch, max_len, acfg)
        if kv_bits == 8:
            return init_quant_kv_cache(batch, max_len, acfg)
        return init_kv_cache(batch, max_len, acfg, dtype)
    if paged_blocks is not None:
        raise ValueError(
            f"paged KV cache supports attention layers only, got {kind!r} "
            "(recurrent state has no block layout)")
    if kind == "rec":
        return rglru_lib.init_rglru_state(batch, cfg.d_rnn or cfg.d_model)
    if kind == "rwkv":
        return rwkv_lib.init_rwkv_state(batch, cfg.d_model, cfg.rwkv_head_size)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, *, stacked: bool = True,
                dtype=jnp.bfloat16):
    """stacked=True: per-pattern-position params stacked over repeats (scan
    layout). stacked=False: params["layers"] is a flat per-layer list."""
    plan = cfg.layer_plan
    n_pat = len(cfg.block_pattern)
    n_tail = len(cfg.tail_pattern)
    n_super = (len(plan) - n_tail) // n_pat
    keys = split_keys(key, len(plan) + 3)

    params: Dict[str, Any] = {
        "embed": embed_init(keys[-1], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": _init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[-2], cfg.vocab_size, cfg.d_model,
                                       dtype).T

    if stacked:
        # vmap over the layer keys builds each stack directly (no per-layer
        # list + stack, i.e. no second full copy); the values are those of
        # the per-layer inits.
        scan_groups = []
        for j, kind in enumerate(cfg.block_pattern):
            ks = jnp.stack([keys[s * n_pat + j] for s in range(n_super)])
            scan_groups.append(jax.vmap(functools.partial(
                init_block_params, cfg, kind, dtype=dtype))(ks))
        params["scan"] = scan_groups
        params["tail"] = [init_block_params(cfg, kind,
                                            keys[n_super * n_pat + i], dtype)
                          for i, kind in enumerate(cfg.tail_pattern)]
    else:
        params["layers"] = [init_block_params(cfg, kind, keys[i], dtype)
                            for i, kind in enumerate(plan)]
    return params


class _LayerSlices:
    """``params["layers"]`` of a stacked pytree: layer ``i`` is sliced out
    of its scan stack when it is indexed, so a loop over the layers holds
    one layer's copy at a time."""

    def __init__(self, cfg: ModelConfig, params):
        self._cfg, self._params = cfg, params

    def __len__(self):
        return self._cfg.num_layers

    def __getitem__(self, i):
        n_pat = len(self._cfg.block_pattern)
        n_scan = self._cfg.n_super * n_pat
        if i >= n_scan:
            return self._params["tail"][i - n_scan]
        return jax.tree.map(lambda x: x[i // n_pat],
                            self._params["scan"][i % n_pat])


def unrolled_view(cfg: ModelConfig, params):
    """The unrolled layout (per-layer site names ``layer{i}/...``) over
    stacked params without copying them: for calibrating the params that
    are served instead of a second, unstacked set."""
    view = {k: v for k, v in params.items() if k not in ("scan", "tail")}
    view["layers"] = _LayerSlices(cfg, params)
    return view


def attn_write_spans(cfg: ModelConfig, max_len: int) -> List[int]:
    """Per-attention-layer token write SPANS: how many distinct cache
    cells the layer can ever occupy per lane — ``min(max_len, window)``
    for ring (sliding-window) layers, ``max_len`` for global ones."""
    spans = []
    for kind in cfg.layer_plan:
        if kind not in ("attn", "local_attn"):
            continue
        w = attn_cfg_for(cfg, kind).window
        spans.append(min(max_len, w) if w else max_len)
    return spans


def paged_lane_blocks(cfg: ModelConfig, max_len: int,
                      block_size: int) -> int:
    """Per-lane worst-case block-table width for a paged cache of this
    arch: ``ceil(max(write spans) / block_size)``. For an all-window
    model this is ``ceil(S_w / block_size)`` — window layers stop
    inflating the table, the default pool size, and reservations. Mixed
    local/global models keep the global layers' ``ceil(max_len /
    block_size)`` (one shared table must cover every layer's span)."""
    spans = attn_write_spans(cfg, max_len)
    if not spans:
        raise ValueError(f"{cfg.name}: no attention layers to page")
    return -(-max(spans) // block_size)


def attn_write_caps(cfg: ModelConfig, max_len: int,
                    block_size: int) -> List[int]:
    """Distinct per-layer paged write capacities in TOKENS — exactly the
    ``s_cap`` each layer's write path wraps at
    (``min(table_width * block_size, window)``, see
    attention.paged_capacity). The scheduler uses these as its
    copy-on-write barrier: a write at position ``p`` lands in table
    column ``(p % cap) // block_size`` for some cap in this list, and any
    such column inside a lane's shared prefix must be COWed first. The
    MINIMUM cap is also the donation rule (a lane that ever wrote at or
    past it has wrapped a ring layer, so its prompt blocks are not
    generation-0 and must not be donated), and the MAXIMUM cap is the
    ring clamp for reservations (an all-window lane never needs more than
    ``ceil(max_cap / block_size)`` blocks however long it decodes)."""
    width = paged_lane_blocks(cfg, max_len, block_size)
    caps = set()
    for kind in cfg.layer_plan:
        if kind not in ("attn", "local_attn"):
            continue
        w = attn_cfg_for(cfg, kind).window
        caps.add(min(width * block_size, w) if w else width * block_size)
    return sorted(caps)


def paged_ring_tokens(cfg: ModelConfig, max_len: int,
                      block_size: int) -> Optional[int]:
    """Ring clamp for per-lane reservations: when EVERY attention layer
    is a sliding-window ring smaller than ``max_len``, a lane's paged
    writes all wrap in place past ``max(window)`` tokens, so reservations
    and growth never need more than ``ceil(max(window) / block_size)``
    blocks however long the request decodes. Returns None for models with
    any global (or window >= max_len) layer — there a long request
    genuinely needs ``max_len`` cells and clamping would silently drop
    context."""
    windows = []
    for kind in cfg.layer_plan:
        if kind not in ("attn", "local_attn"):
            continue
        w = attn_cfg_for(cfg, kind).window
        if not w or w >= max_len:
            return None
        windows.append(w)
    return max(windows) if windows else None


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               stacked: bool = True, dtype=jnp.bfloat16, kv_bits: int = 16,
               paged: bool = False, block_size: int = 16,
               num_blocks: Optional[int] = None,
               mapped: Optional[bool] = None):
    """kv_bits=8 stores attention caches as int8 QuantKVCache (deployment
    serving path); kv_bits=4 as nibble-packed Quant4KVCache (two int4 cells
    per byte — half the cache HBM of int8); 16 keeps the bf16/f32 KVCache.

    ``paged=True`` switches every attention layer to the block-paged
    layout: one shared arena of ``num_blocks`` blocks of ``block_size``
    token cells per layer (default: the worst case,
    ``batch * paged_lane_blocks(...)`` — ``ceil(max_len / block_size)``
    per lane unless EVERY attention layer is sliding-window, in which
    case the ring bound ``ceil(min(max_len, S_w) / block_size)`` sizes
    the table and the default pool instead) plus a single
    ``"block_table"`` (batch, max_blocks_per_lane) entry in the returned
    pytree. ``mapped`` (default: True iff ``num_blocks`` was left at the
    worst case) pre-maps the identity table — lane i owns blocks
    [i*nb, (i+1)*nb) — which makes the paged cache a drop-in dense
    equivalent (the static scheduler path); pool-managed serving starts
    unmapped and lets runtime.block_pool.BlockPool own the table.
    """
    plan = cfg.layer_plan
    n_pat = len(cfg.block_pattern)
    n_tail = len(cfg.tail_pattern)
    n_super = (len(plan) - n_tail) // n_pat
    paged_blocks = None
    table = None
    if paged:
        nb_lane = paged_lane_blocks(cfg, max_len, block_size)
        if mapped is None:
            mapped = num_blocks is None
        if num_blocks is None:
            num_blocks = batch * nb_lane
        paged_blocks = (num_blocks, block_size)
        if mapped:
            if num_blocks < batch * nb_lane:
                raise ValueError(
                    f"mapped paged cache needs num_blocks >= "
                    f"batch*{nb_lane} = {batch * nb_lane}, got {num_blocks}")
            table = jnp.arange(batch * nb_lane,
                               dtype=jnp.int32).reshape(batch, nb_lane)
        else:
            table = jnp.full((batch, nb_lane), -1, jnp.int32)

    def blk(kind):
        return init_block_cache(cfg, kind, batch, max_len, dtype, kv_bits,
                                paged_blocks)

    if stacked:
        groups = []
        for kind in cfg.block_pattern:
            per = [blk(kind) for _ in range(n_super)]
            groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        tail = [blk(kind) for kind in cfg.tail_pattern]
        cache = {"scan": groups, "tail": tail}
    else:
        cache = {"layers": [blk(kind) for kind in plan]}
    if paged:
        cache["block_table"] = table
    return cache


def paged_block_bytes(cache) -> int:
    """HBM bytes per physical block, summed over every paged arena in the
    cache pytree (stacked leaves count all their layers) — multiply by the
    pool's blocks_in_use for the live paged footprint."""
    total = 0
    for node in _cache_nodes(cache):
        if isinstance(node, (PagedKVCache, PagedQuantKVCache)):
            n = node.pos.shape[-2]
            total += sum(leaf.size * leaf.dtype.itemsize for leaf in node) // n
    return total


def _cache_nodes(cache):
    return (cache.get("layers") or
            list(cache.get("scan", [])) + list(cache.get("tail", [])))


def cache_reset_slots(cache, lane_mask):
    """Empty the masked batch lanes of a whole-model cache pytree for slot
    reuse (continuous batching): every attention cache's ``pos`` becomes -1
    on those lanes, so the next occupant starts from an empty lane while the
    other lanes are untouched. Works for both cache layouts (stacked scan
    leaves carry batch on axis 1) and every cache type (KVCache /
    QuantKVCache — the int8 per-head per-slot scale layout is preserved;
    stale payload bytes are unreadable once pos == -1 — and the paged
    variants, where the masked lanes' *mapped blocks* are emptied through
    the cache's block table).

    Recurrent state (rglru / rwkv6) has no per-slot validity sentinel, so
    those caches are not supported by the continuous scheduler.
    """
    lane_mask = jnp.asarray(lane_mask, bool)
    table = cache.get("block_table")

    def _reset(c, axis):
        if isinstance(c, (PagedKVCache, PagedQuantKVCache)):
            return reset_paged_lanes(c, lane_mask, table)
        if isinstance(c, (KVCache, QuantKVCache)):
            return reset_kv_lanes(c, lane_mask, batch_axis=axis)
        raise ValueError(
            "cache_reset_slots: continuous batching supports attention "
            f"caches only, got {type(c).__name__} (recurrent state has no "
            "per-slot validity to reset)")

    if "layers" in cache:
        out = {"layers": [_reset(c, 0) for c in cache["layers"]]}
    else:
        out = {"scan": [_reset(c, 1) for c in cache["scan"]],
               "tail": [_reset(c, 0) for c in cache["tail"]]}
    if table is not None:
        out["block_table"] = table
    return out


def cache_copy_block(cache, src, dst):
    """Copy physical block ``src``'s payload (K/V, scales, positions) into
    block ``dst`` across EVERY paged arena of a whole-model cache pytree —
    the device half of the scheduler's copy-on-write: the pool swaps a
    shared table entry for a fresh private block, this clones the shared
    payload so the lane's subsequent writes land in its own copy.

    ``src`` / ``dst`` are traced int32 scalars (block ids are data, so one
    jitted trace serves every COW). Stacked scan leaves carry the block
    axis at position 1 (after n_super), tail/flat leaves at position 0.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def _copy(c, axis):
        if not isinstance(c, (PagedKVCache, PagedQuantKVCache)):
            raise ValueError(
                "cache_copy_block: paged caches only, got "
                f"{type(c).__name__}")
        if axis == 1:
            return jax.tree.map(
                lambda x: jax.lax.dynamic_update_index_in_dim(
                    x, jax.lax.dynamic_index_in_dim(x, src, axis=1,
                                                    keepdims=False),
                    dst, axis=1), c)
        return jax.tree.map(
            lambda x: jax.lax.dynamic_update_index_in_dim(
                x, jax.lax.dynamic_index_in_dim(x, src, axis=0,
                                                keepdims=False),
                dst, axis=0), c)

    if "layers" in cache:
        out = {"layers": [_copy(c, 0) for c in cache["layers"]]}
    else:
        out = {"scan": [_copy(c, 1) for c in cache["scan"]],
               "tail": [_copy(c, 0) for c in cache["tail"]]}
    if "block_table" in cache:
        out["block_table"] = cache["block_table"]
    return out


def cache_gather_blocks(cache, ids):
    """Gather the payload rows of physical blocks ``ids`` from every paged
    arena of a whole-model cache pytree — the device half of the
    scheduler's swap-out: the result (block axis shrunk to ``len(ids)``,
    ``block_table`` omitted) is device_get into a host spill buffer while
    the pool frees the blocks for other lanes.

    ``ids`` is a traced int32 vector of FIXED length (max_blocks_per_lane;
    one jitted trace serves every preemption): live block ids first,
    padded with ``num_blocks`` — an out-of-range POSITIVE id. The gather
    clips it to the last block (garbage rows in the padded tail), and the
    matching scatter in :func:`cache_scatter_blocks` DROPS those writes,
    so the padding round-trips harmlessly. Stacked scan leaves carry the
    block axis at position 1 (after n_super), tail/flat leaves at 0.
    """
    ids = jnp.asarray(ids, jnp.int32)

    def _gather(c, axis):
        if not isinstance(c, (PagedKVCache, PagedQuantKVCache)):
            raise ValueError(
                "cache_gather_blocks: paged caches only, got "
                f"{type(c).__name__}")
        return jax.tree.map(
            lambda x: jnp.take(x, ids, axis=axis, mode="clip"), c)

    if "layers" in cache:
        return {"layers": [_gather(c, 0) for c in cache["layers"]]}
    return {"scan": [_gather(c, 1) for c in cache["scan"]],
            "tail": [_gather(c, 0) for c in cache["tail"]]}


def cache_scatter_blocks(cache, ids, payload):
    """Scatter a :func:`cache_gather_blocks` ``payload`` back into physical
    blocks ``ids`` across every paged arena — the device half of the
    scheduler's swap-in on resume. ``ids`` are the lane's NEWLY allocated
    block ids (same fixed length and live-prefix layout as the gather;
    the ``num_blocks`` padding is out of range, so those rows are
    scatter-dropped). The re-uploaded payload is bit-identical to what
    the preempted lane held, so resume emits the same greedy tokens."""
    ids = jnp.asarray(ids, jnp.int32)

    def _scatter(c, p, axis):
        if not isinstance(c, (PagedKVCache, PagedQuantKVCache)):
            raise ValueError(
                "cache_scatter_blocks: paged caches only, got "
                f"{type(c).__name__}")
        if axis == 1:
            return jax.tree.map(
                lambda x, v: x.at[:, ids].set(v, mode="drop"), c, p)
        return jax.tree.map(
            lambda x, v: x.at[ids].set(v, mode="drop"), c, p)

    if "layers" in cache:
        out = {"layers": [_scatter(c, p, 0) for c, p in
                          zip(cache["layers"], payload["layers"])]}
    else:
        out = {"scan": [_scatter(c, p, 1) for c, p in
                        zip(cache["scan"], payload["scan"])],
               "tail": [_scatter(c, p, 0) for c, p in
                        zip(cache["tail"], payload["tail"])]}
    if "block_table" in cache:
        out["block_table"] = cache["block_table"]
    return out


def cache_extract_lane(cache, lane):
    """Slice one batch lane out of a DENSE whole-model cache pytree — the
    device half of the engine's decomposed ``prefill``: a request is
    prefilled into a scratch cache and its lane (batch axis kept, size 1)
    becomes the transferable ``lane_payload`` that :func:`cache_insert_lane`
    lands in any decode slot. ``lane`` is a traced int32 scalar, so one
    jitted trace serves every prefill. Paged caches have no per-lane
    batch axis — extract their lane payloads with
    :func:`cache_gather_blocks` over the lane's mapped block ids instead."""
    lane = jnp.asarray(lane, jnp.int32)

    def _extract(c, axis):
        if not isinstance(c, (KVCache, QuantKVCache)):
            raise ValueError(
                "cache_extract_lane: dense attention caches only, got "
                f"{type(c).__name__}")
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, lane, 1, axis=axis), c)

    if "block_table" in cache:
        raise ValueError("cache_extract_lane: paged caches carry no batch "
                         "axis — use cache_gather_blocks on the lane's "
                         "mapped block ids")
    if "layers" in cache:
        return {"layers": [_extract(c, 0) for c in cache["layers"]]}
    return {"scan": [_extract(c, 1) for c in cache["scan"]],
            "tail": [_extract(c, 0) for c in cache["tail"]]}


def cache_insert_lane(cache, lane, payload):
    """Write a :func:`cache_extract_lane` payload into batch lane ``lane``
    of a DENSE whole-model cache pytree — the device half of the engine's
    ``insert``. The payload covers the lane's every cell (prompt KV plus
    the -1 dead-cell padding), so the write is a full lane overwrite: the
    slot's previous occupant needs no separate reset, and every other
    lane's bytes pass through bit-identical (the lane bit-isolation
    contract the engine conformance suite asserts)."""
    lane = jnp.asarray(lane, jnp.int32)

    def _insert(c, p, axis):
        if not isinstance(c, (KVCache, QuantKVCache)):
            raise ValueError(
                "cache_insert_lane: dense attention caches only, got "
                f"{type(c).__name__}")
        return jax.tree.map(
            lambda x, v: jax.lax.dynamic_update_slice_in_dim(
                x, v, lane, axis=axis), c, p)

    if "block_table" in cache:
        raise ValueError("cache_insert_lane: paged caches carry no batch "
                         "axis — use cache_scatter_blocks on the lane's "
                         "mapped block ids")
    if "layers" in cache:
        return {"layers": [_insert(c, p, 0) for c, p in
                           zip(cache["layers"], payload["layers"])]}
    return {"scan": [_insert(c, p, 1) for c, p in
                     zip(cache["scan"], payload["scan"])],
            "tail": [_insert(c, p, 0) for c, p in
                     zip(cache["tail"], payload["tail"])]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _constrain(x, dist: Optional[DistContext], spec):
    """Divisibility-aware sharding constraint: any dim that does not divide
    its assigned axis group is replicated instead."""
    if dist is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    fixed = []
    for dim, axis in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if axis is None:
            fixed.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        size = 1
        for a in names:
            size *= dist.mesh.shape[a]
        fixed.append(axis if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dist.mesh, PartitionSpec(*fixed)))


def _embed(cfg: ModelConfig, params, tokens, embeds, ctx, dist=None):
    from repro.core.calibration import Mode
    from repro.models.common import resolve_weight
    table = resolve_weight(params["embed"])
    if dist is not None and dist.onehot_embed and tokens.size <= 4096:
        # decode-path perf variant: a one-hot einsum keeps the vocab axis
        # SHARDED through the lookup (partial rows + one tiny psum over tp)
        # instead of all-gathering the whole embedding table per step.
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=table.dtype)
        x = jnp.einsum("btv,vd->btd", oh, table)
    else:
        x = jnp.take(table, tokens, axis=0)
    if dist is not None:
        # keep the gathered activations batch-sharded (avoids the SPMD
        # "involuntary full rematerialization" reshard on the vocab gather)
        from jax.sharding import PartitionSpec as P
        x = _constrain(x, dist, P(dist.dp_axes, None, None))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if embeds is not None:
        # modality frontend stub: precomputed patch/frame embeddings are
        # prepended to the token embeddings (assignment: frontend is a stub).
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    if ctx is not None and ctx.mode == Mode.DEPLOY:
        # the integer path keeps its residual stream in f32: the int8
        # kernels emit f32, and bf16 cannot hold every value of an 8-bit
        # grid (code x scale), so bf16 roundings between sites would move
        # values across the next site's requantization ties
        x = x.astype(jnp.float32)
    if ctx is not None:
        x = ctx.act("embed/sum", x)
    return x


def _head(cfg: ModelConfig, params, x, ctx, dist=None):
    from repro.models.common import resolve_weight
    h = _norm(cfg, params["final_norm"], x)
    w = resolve_weight(params["embed"]).T if cfg.tie_embeddings \
        else resolve_weight(params["lm_head"])
    if ctx is not None:
        w = ctx.weight("head/w", w)
    logits = h @ w.astype(h.dtype)
    if dist is not None:
        # logits stay vocab-sharded on the TP axis end-to-end (the CE
        # logsumexp reduces with one small all-reduce instead of gathering
        # the (B, T, V) tensor)
        from jax.sharding import PartitionSpec as P
        logits = _constrain(logits, dist,
                            P(dist.dp_axes, None, dist.tp_axis))
    logits = softcap(logits, cfg.final_logit_softcap)
    if ctx is not None:
        logits = ctx.act("head/logits", logits)
    return logits


def forward(cfg: ModelConfig, params, tokens, *, embeds=None, ctx=None,
            dist: Optional[DistContext] = None, cache=None, positions=None,
            remat: bool = False, chunked=None, append: bool = False):
    """Returns (logits, new_cache). tokens: (B, T) int32.

    positions: (B, T) absolute positions (defaults to arange).
    cache: pytree from init_cache (stacked or unrolled layout must match
    params layout).
    append: chunked-prefill mode — the tokens are one chunk appended at
    each lane's current cache position; attention reads the cache (earlier
    chunks) in addition to the fresh tokens (see models.attention).

    Under ``Mode.DEPLOY`` the float matmuls between the int8 kernels
    (attention scores and values, the head) run at ``highest`` precision:
    at the default precision a TPU rounds their f32 operands to bf16, and
    those roundings move values across the next 8-bit site's ties.
    """
    from repro.core.calibration import Mode
    kw = dict(embeds=embeds, ctx=ctx, dist=dist, cache=cache,
              positions=positions, remat=remat, chunked=chunked,
              append=append)
    if ctx is not None and ctx.mode == Mode.DEPLOY:
        with jax.default_matmul_precision("highest"):
            return _forward(cfg, params, tokens, **kw)
    return _forward(cfg, params, tokens, **kw)


def _forward(cfg: ModelConfig, params, tokens, *, embeds, ctx, dist, cache,
             positions, remat, chunked, append):
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens, embeds, ctx, dist=dist)
    T_full = x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T_full, dtype=jnp.int32),
                                     (B, T_full))
    # the paged caches' (B, max_blocks) block table is shared by every
    # layer: thread it alongside the per-layer cache leaves and hand it
    # back unchanged (allocation is host-side, runtime.block_pool)
    block_table = cache.get("block_table") if cache is not None else None

    if "layers" in params:                      # unrolled path
        new_layer_caches = []
        for i, kind in enumerate(cfg.layer_plan):
            c = cache["layers"][i] if cache is not None else None

            def _blk(p, x, c, kind=kind, i=i):
                return block_apply(cfg, kind, p, x, positions, ctx=ctx,
                                   prefix=f"layer{i}", cache=c, dist=dist,
                                   chunked=chunked, block_table=block_table,
                                   append=append)
            if remat:
                _blk = jax.checkpoint(
                    _blk, policy=jax.checkpoint_policies.nothing_saveable)
            x, nc = _blk(params["layers"][i], x, c)
            new_layer_caches.append(nc)
        new_cache = None
        if cache is not None:
            new_cache = {"layers": new_layer_caches}
            if block_table is not None:
                new_cache["block_table"] = block_table
        with jax.named_scope("head"):
            logits = _head(cfg, params, x, ctx, dist=dist)
        return logits, new_cache

    # stacked scan path
    n_pat = len(cfg.block_pattern)

    def superblock(x, slices):
        p_slices, c_slices = slices
        new_cs = []
        for j, kind in enumerate(cfg.block_pattern):
            c = c_slices[j] if c_slices is not None else None
            x, nc = block_apply(cfg, kind, p_slices[j], x, positions,
                                ctx=ctx, prefix="layer", cache=c, dist=dist,
                                chunked=chunked, block_table=block_table,
                                append=append)
            new_cs.append(nc)
        return x, (new_cs if c_slices is not None else None)

    body = superblock
    if remat:
        body = jax.checkpoint(
            superblock,
            policy=jax.checkpoint_policies.nothing_saveable)

    scan_caches = cache["scan"] if cache is not None else None
    # Quant-health telemetry entries created INSIDE the scan body (prefix
    # "layer") would leak tracers through the ctx dict; pop them in the body
    # and return them as scan ys instead — they come back stacked (L, 4)
    # per site, which is exactly the per-layer resolution we want.
    telem = ctx.telemetry if ctx is not None else None

    def scan_fn(x, xs):
        p_slices = xs[0]
        c_slices = xs[1] if cache is not None else None
        before = set(telem) if telem is not None else None
        x, new_c = body(x, (p_slices, c_slices))
        tel_ys = {}
        if telem is not None:
            tel_ys = {k: telem.pop(k) for k in sorted(set(telem) - before)}
        return x, (new_c, tel_ys)

    # lax.scan needs xs leaves with a leading axis; pack params (+caches).
    # The "layers" scope also names the scan's own ops: the per-layer
    # slicing of the stacked params/caches and the stacking of the new ones.
    with jax.named_scope("layers"):
        if cache is not None:
            x, (new_scan_caches, tel_stacked) = jax.lax.scan(
                lambda carry, xs_: scan_fn(carry, xs_),
                x, (params["scan"], scan_caches))
        else:
            x, (_, tel_stacked) = jax.lax.scan(
                lambda carry, p: scan_fn(carry, (p,)), x, params["scan"])
            new_scan_caches = None
    if telem is not None:
        telem.update(tel_stacked)

    new_tail_caches = []
    for i, kind in enumerate(cfg.tail_pattern):
        c = cache["tail"][i] if cache is not None else None
        p_tail = params["tail"][i]
        x, nc = block_apply(cfg, kind, p_tail, x, positions, ctx=ctx,
                            prefix="tail", cache=c, dist=dist,
                            chunked=chunked, block_table=block_table,
                            append=append)
        new_tail_caches.append(nc)

    new_cache = None
    if cache is not None:
        new_cache = {"scan": new_scan_caches, "tail": new_tail_caches}
        if block_table is not None:
            new_cache["block_table"] = block_table
    with jax.named_scope("head"):
        logits = _head(cfg, params, x, ctx, dist=dist)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------

def train_loss(cfg: ModelConfig, params, batch, *, ctx=None, dist=None,
               remat: bool = True, chunked=None):
    """Next-token CE. batch: {tokens (B,T), labels (B,T) [, embeds]}."""
    logits, _ = forward(cfg, params, batch["tokens"],
                        embeds=batch.get("embeds"), ctx=ctx, dist=dist,
                        remat=remat, chunked=chunked)
    n_front = logits.shape[1] - batch["labels"].shape[1]
    if n_front > 0:
        logits = logits[:, n_front:]
    return cross_entropy(logits[:, :-1], batch["labels"][:, 1:])


def prefill(cfg: ModelConfig, params, tokens, cache, *, positions=None,
            ctx=None, embeds=None, dist=None, chunked=None,
            append: bool = False):
    """Fill the cache from a prompt; returns (last_logits, cache).

    positions: optional (B, T) absolute positions. Left-packed ragged
    prompts pass their pads as position -1 (dead cells: masked out of
    attention, cache writes dropped) and real tokens as 0..len-1, so a
    padded request produces the same logits/cache lane as serving it alone.
    A lane whose positions are ALL -1 writes nothing — the slot-insert
    admission path of the continuous scheduler relies on this.

    append=True appends the tokens as ONE chunk at each lane's current
    cache position (chunked prefill): attention covers the cache contents
    plus the fresh chunk, so a prompt split into chunks fills the cache —
    and emits its last-token logits — exactly like a monolithic prefill.
    """
    logits, cache = forward(cfg, params, tokens, embeds=embeds, ctx=ctx,
                            dist=dist, cache=cache, positions=positions,
                            chunked=chunked, append=append)
    return logits[:, -1:], cache


def decode_step(cfg: ModelConfig, params, tokens, pos, cache, *, ctx=None,
                dist=None):
    """One decode step. tokens/pos: (B, 1). Returns (logits, cache)."""
    logits, cache = forward(cfg, params, tokens, positions=pos, cache=cache,
                            ctx=ctx, dist=dist)
    return logits, cache
