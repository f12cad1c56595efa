"""Divisibility-aware sharding rules (DESIGN.md §4).

Axes: ``pod`` (cross-pod DP), ``data`` (in-pod DP + FSDP for params/optimizer
state), ``model`` (TP for heads/FFN-hidden/vocab, EP for experts, SP for
long-context caches).

Every rule degrades gracefully: a dimension is sharded on an axis only if it
divides evenly, otherwise that dim is replicated (e.g. granite's single KV
head -> the 128-wide head_dim shards instead; gemma2's d_model=2304 is not
divisible by 16 -> the FSDP dim falls back to replication for those leaves).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.models.transformer import DistContext


def make_abstract_mesh(shape: Tuple[int, ...],
                       axes: Tuple[str, ...]) -> AbstractMesh:
    """Device-free mesh for sharding-rule logic."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_dist(mesh: Mesh) -> DistContext:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # FSDP spans the whole data-parallel group: on the multi-pod mesh the
    # parameters/optimizer state shard over (pod, data) = 32 ways, which is
    # what makes 235B/314B training fit 16 GB/chip (DESIGN.md §4).
    fsdp = dp if len(dp) > 1 else "data"
    return DistContext(mesh=mesh, tp_axis="model", fsdp_axis=fsdp,
                       dp_axes=dp)


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _fits(dim: int, mesh: Mesh, axis) -> bool:
    if axis is None:
        return True
    return dim % _axis_size(mesh, axis) == 0


def _spec(mesh, shape, assignment) -> P:
    """assignment: tuple of axis-name/tuple-or-None per dim; drop
    non-divisible or already-used axes."""
    cleaned = []
    used = set()
    for dim, axis in zip(shape, assignment):
        names = axis if isinstance(axis, tuple) else (axis,)
        if axis is not None and not (set(names) & used) and \
                _fits(dim, mesh, axis):
            cleaned.append(axis)
            used.update(names)
        else:
            cleaned.append(None)
    while cleaned and cleaned[-1] is None:
        cleaned.pop()
    return P(*cleaned)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# name-pattern -> per-dim axis assignment for the TRAILING dims (the leading
# scan/stack dim, when present, is never sharded). "tp"/"fsdp" resolve to
# model/data.
_PARAM_RULES = [
    # attention projections
    (r".*attn.*wq$", ("fsdp", "tp")),
    (r".*attn.*wk$", ("fsdp", "tp")),
    (r".*attn.*wv$", ("fsdp", "tp")),
    (r".*attn.*wo$", ("tp", "fsdp")),
    (r".*(xattn).*w[qkv]$", ("fsdp", "tp")),
    (r".*(xattn).*wo$", ("tp", "fsdp")),
    # dense FFN
    (r".*ffn.*(w_gate|w_up|w_in)$", ("fsdp", "tp")),
    (r".*ffn.*w_out$", ("tp", "fsdp")),
    # MoE experts (params keyed "moe"): (E, D, F) / (E, F, D)
    (r".*moe.*(w_gate|w_up)$", ("tp", "fsdp", None)),
    (r".*moe.*w_out$", ("tp", None, "fsdp")),
    (r".*moe.*router$", ("fsdp", None)),
    # RG-LRU
    (r".*rec.*(w_rnn_in|w_gate_in)$", ("fsdp", "tp")),
    (r".*rec.*w_out$", ("tp", "fsdp")),
    (r".*rec.*(w_a|w_x)$", ("fsdp", "tp")),
    (r".*rec.*conv_w$", (None, "tp")),
    (r".*rec.*(b_a|b_x|conv_b|lam)$", ("tp",)),
    # RWKV
    (r".*tmix.*(w_r|w_k|w_v|w_g)$", ("fsdp", "tp")),
    (r".*tmix.*w_o$", ("tp", "fsdp")),
    (r".*tmix.*w_lora_a$", ("fsdp", None)),
    (r".*tmix.*w_lora_b$", (None, "tp")),
    (r".*cmix.*(w_ck|w_cr)$", ("fsdp", "tp")),
    (r".*cmix.*w_cv$", ("tp", "fsdp")),
    # embeddings / heads
    (r".*(embed|tok_embed)$", ("tp", "fsdp")),
    (r".*(enc_pos|dec_pos|pos_embed)$", (None, "fsdp")),
    (r".*lm_head$", ("fsdp", "tp")),
    (r".*(w_pool|w_cls)$", ("fsdp", None)),
]


def _resolve(axis: Optional[str], dist: DistContext) -> Optional[str]:
    if axis == "tp":
        return dist.tp_axis
    if axis == "fsdp":
        return dist.fsdp_axis
    return axis


def param_spec_for(path: str, shape: Tuple[int, ...], dist: DistContext,
                   *, has_scan_dim: bool) -> P:
    mesh = dist.mesh
    # MoE expert tensors whose E dim does not divide TP (grok-1): fall back
    # to TP on the d_ff dim (hybrid mode in transformer._moe_sharded)
    if re.search(r"moe.*(w_gate|w_up|w_out)$", path) and len(shape) >= 3:
        e_dim = shape[-3]
        if e_dim % mesh.shape[dist.tp_axis] != 0:
            if path.endswith("w_out"):     # (E, F, D): F on tp, D on fsdp
                assign = (None, dist.tp_axis, dist.fsdp_axis)
            else:                          # (E, D, F): D on fsdp, F on tp
                assign = (None, dist.fsdp_axis, dist.tp_axis)
            lead = len(shape) - 3
            return _spec(mesh, shape, (None,) * lead + assign)
    for pattern, assignment in _PARAM_RULES:
        if re.fullmatch(pattern, path):
            assign = tuple(_resolve(a, dist) for a in assignment)
            ndim = len(shape)
            lead = ndim - len(assign)
            if lead < 0:          # rule for more dims than leaf has: replicate
                return P()
            full = (None,) * lead + assign
            return _spec(mesh, shape, full)
    # default: replicate small leaves; fsdp-shard anything big on its largest
    # divisible dim
    if int(np.prod(shape)) >= 1 << 20:
        best = max(range(len(shape)), key=lambda i: shape[i])
        assign = [None] * len(shape)
        if _fits(shape[best], mesh, dist.fsdp_axis):
            assign[best] = dist.fsdp_axis
        return _spec(mesh, shape, tuple(assign))
    return P()


def _leaf_path(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def make_param_specs(params, dist: DistContext):
    """Pytree of PartitionSpec matching ``params``."""
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        p = _leaf_path(path)
        has_scan = "scan" in p
        specs.append(param_spec_for(p, leaf.shape, dist,
                                    has_scan_dim=has_scan))
    return jax.tree_util.tree_unflatten(flat[1], specs)


def make_param_shardings(params, dist: DistContext):
    specs = make_param_specs(params, dist)
    return jax.tree.map(lambda s: NamedSharding(dist.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation / batch / cache rules
# ---------------------------------------------------------------------------

def batch_spec(dist: DistContext) -> P:
    return P(dist.dp_axes)


def make_batch_shardings(batch, dist: DistContext):
    def spec(leaf):
        # shard leading (batch) dim over dp if divisible, else replicate
        bs = leaf.shape[0] if leaf.ndim else 1
        dp = int(np.prod([dist.mesh.shape[a] for a in dist.dp_axes]))
        s = P(dist.dp_axes, *([None] * (leaf.ndim - 1))) if bs % dp == 0 \
            else P()
        return NamedSharding(dist.mesh, s)
    return jax.tree.map(spec, batch)


def cache_spec_for(shape: Tuple[int, ...], dist: DistContext,
                   *, has_scan_dim: bool) -> P:
    """KV cache / recurrent state leaves.

    Layout (with scan dim): (L, B, S, KV, hd) or (L, B, ...state dims).
    Shard B over dp when divisible; otherwise shard the sequence dim over
    ``data`` (sequence parallelism for batch-1 long-context decode).

    Model-axis placement: by default the SEQUENCE dim shards over ``model``
    for 5-dim KV caches — decode attention then reduces tiny softmax
    partials over tp instead of all-gathering the cache every layer (the
    §Perf 'kvseq' finding: ~100 GiB/step of all-gather on internlm2
    decode_32k with head-sharded caches). Head/feature dims are the
    fallback when S does not divide.
    """
    mesh = dist.mesh
    dp = int(np.prod([mesh.shape[a] for a in dist.dp_axes]))
    lead = 1 if has_scan_dim else 0
    ndim = len(shape)
    assign = [None] * ndim
    bdim = lead
    if ndim <= bdim:
        return P()
    batch_shardable = shape[bdim] % dp == 0
    if batch_shardable:
        assign[bdim] = dist.dp_axes
    if ndim > bdim + 1:
        sdim = bdim + 1
        if not batch_shardable and shape[sdim] % _axis_size(mesh, dist.fsdp_axis) == 0:
            assign[sdim] = dist.fsdp_axis          # SP over 'data'
    # sequence-dim tp sharding for (L, B, S, KV, hd) KV caches
    tp_used = False
    if getattr(dist, "kv_seq_shard", True) and ndim - lead == 4:
        sdim = bdim + 1
        if assign[sdim] is None and shape[sdim] % mesh.shape[dist.tp_axis] == 0 \
                and shape[sdim] >= mesh.shape[dist.tp_axis]:
            assign[sdim] = dist.tp_axis
            tp_used = True
    # heads / feature dims on model axis: prefer KV-head dim, then features
    if not tp_used:
        for d in range(ndim - 2, ndim):
            if d > bdim and assign[d] is None and \
                    shape[d] % mesh.shape[dist.tp_axis] == 0 and \
                    dist.tp_axis not in [a for a in assign if a]:
                # avoid sharding tiny dims (e.g. kv=1, hd=64 < tp)
                if shape[d] >= mesh.shape[dist.tp_axis]:
                    assign[d] = dist.tp_axis
                    break
    cleaned = []
    used = set()
    for dim, axis in zip(shape, assign):
        if axis is None:
            cleaned.append(None)
        elif isinstance(axis, tuple):
            cleaned.append(axis)
            used.update(axis)
        elif axis not in used:
            cleaned.append(axis)
            used.add(axis)
        else:
            cleaned.append(None)
    while cleaned and cleaned[-1] is None:
        cleaned.pop()
    return P(*cleaned)


def make_cache_shardings(cache, dist: DistContext):
    flat = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat[0]:
        p = _leaf_path(path)
        has_scan = "scan" in p or "self_kv" in p or "cross" in p
        out.append(NamedSharding(dist.mesh,
                                 cache_spec_for(leaf.shape, dist,
                                                has_scan_dim=has_scan)))
    return jax.tree_util.tree_unflatten(flat[1], out)


def make_opt_shardings(opt_state, param_shardings, dist: DistContext):
    """Adam moments mirror the parameter shardings; step counter replicated."""
    from repro.optim.adam import AdamState
    return AdamState(
        step=NamedSharding(dist.mesh, P()),
        mu=param_shardings, nu=jax.tree.map(lambda s: s, param_shardings))


def constrain(x, dist: Optional[DistContext], spec: P):
    if dist is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dist.mesh, spec))
