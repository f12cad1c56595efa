"""Per-embedding-group (PEG) quantization — the paper's novel scheme (§4).

Given per-embedding-dimension calibrated dynamic ranges r_j = max_j - min_j,
we build K evenly-sized groups. With ``use_permutation`` (the "+P" rows of
Table 5) groups follow ``argsort(r)`` so all outlier dims land in the same
group; without it, groups are contiguous chunks of the natural dim order.

TPU adaptation (DESIGN.md §3):
  * group boundaries are aligned to LANE=128 multiples so a group never
    straddles an MXU tile / VREG lane boundary;
  * the permutation is *folded into adjacent weights* (LayerNorm affine, W_in
    rows, W_out columns — permutation-equivariance, paper Fig. 4) so the
    runtime layout is already group-sorted and no gather is executed;
  * `split_linear_for_per_tensor_hw` implements the paper's Fig.-4 rewriting
    for targets with only per-tensor support, used as an equivalence oracle.

TP awareness: when the embedding axis is sharded ``model``-ways, group count
is chosen per shard (K_total = K_per_shard * tp) and the permutation is
restricted to permute *within* shards, so no cross-device data movement is
introduced by quantization.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np
import jax.numpy as jnp

from repro.core.quant_config import QuantizerConfig

LANE = 128  # TPU VREG lane width / MXU tile edge


class PEGSpec(NamedTuple):
    """Static grouping decision for one activation site (host-side)."""
    permutation: np.ndarray        # (d,) dim order: position -> original dim
    inverse_permutation: np.ndarray
    group_index: np.ndarray        # (d,) group id *in permuted layout*
    num_groups: int
    group_sizes: np.ndarray        # (K,)


def _even_group_sizes(d: int, k: int, lane_align: bool) -> np.ndarray:
    """K near-even group sizes summing to d: equal multiples of LANE where
    d allows, else equal sizes where k divides d. Equal sizes come first:
    the deploy kernels take uniform groups (d_model 3840 in 4 groups is 4 x
    960, not the lane-aligned 1024, 1024, 896, 896)."""
    if lane_align and d % (LANE * k) == 0:
        return np.full(k, d // k, dtype=np.int64)
    if d % k and lane_align and d % LANE == 0 and (d // LANE) >= k:
        units = d // LANE
        base = units // k
        rem = units % k
        sizes = np.full(k, base, dtype=np.int64)
        sizes[:rem] += 1
        return sizes * LANE
    base = d // k
    rem = d % k
    sizes = np.full(k, base, dtype=np.int64)
    sizes[:rem] += 1
    return sizes


def build_groups(ranges: np.ndarray, num_groups: int, *,
                 use_permutation: bool = True,
                 lane_align: bool = True,
                 tp_shards: int = 1) -> PEGSpec:
    """Build the PEG spec from calibrated per-dim dynamic ranges.

    ranges: (d,) non-negative per-embedding-dim dynamic range (max - min).
    tp_shards: if >1, dims are partitioned into `tp_shards` contiguous shards
      and the permutation only reorders within each shard; num_groups must be
      divisible by tp_shards (K_per_shard groups each).
    """
    ranges = np.asarray(ranges, dtype=np.float64)
    d = ranges.shape[0]
    if num_groups < 1 or num_groups > d:
        raise ValueError(f"num_groups={num_groups} out of range for d={d}")
    if d % tp_shards != 0:
        raise ValueError(f"d={d} not divisible by tp_shards={tp_shards}")
    if num_groups % tp_shards != 0:
        raise ValueError(f"num_groups={num_groups} not divisible by "
                         f"tp_shards={tp_shards}")

    if tp_shards > 1:
        per = d // tp_shards
        k_per = num_groups // tp_shards
        perms, gidx, sizes = [], [], []
        for s in range(tp_shards):
            sub = build_groups(ranges[s * per:(s + 1) * per], k_per,
                               use_permutation=use_permutation,
                               lane_align=lane_align, tp_shards=1)
            perms.append(sub.permutation + s * per)
            gidx.append(sub.group_index + s * k_per)
            sizes.append(sub.group_sizes)
        perm = np.concatenate(perms)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(d)
        return PEGSpec(permutation=perm, inverse_permutation=inv,
                       group_index=np.concatenate(gidx),
                       num_groups=num_groups,
                       group_sizes=np.concatenate(sizes))

    if use_permutation:
        # Deterministic range-based permutation (paper §4): ascending range,
        # stable, so the largest-range (outlier) dims share the last group.
        perm = np.argsort(ranges, kind="stable")
    else:
        perm = np.arange(d)
    sizes = _even_group_sizes(d, num_groups, lane_align)
    group_index = np.repeat(np.arange(num_groups), sizes)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(d)
    return PEGSpec(permutation=perm.astype(np.int64),
                   inverse_permutation=inv.astype(np.int64),
                   group_index=group_index.astype(np.int64),
                   num_groups=num_groups,
                   group_sizes=sizes)


def group_index_natural_layout(spec: PEGSpec) -> np.ndarray:
    """Group id per *original* (un-permuted) dim — for runtime fake-quant when
    the permutation is NOT folded into the weights."""
    return spec.group_index[spec.inverse_permutation]


def overhead_params(d: int, num_groups: int) -> int:
    """Extra parameters per attention layer (paper §4): permutation indices +
    (scale, zero-point) per group for FFN input, output and sum."""
    return d + 2 * 3 * num_groups


# ---------------------------------------------------------------------------
# Folding the permutation into weights (TPU adaptation; paper Fig. 4).
# ---------------------------------------------------------------------------

def fold_permutation_into_ffn(perm: np.ndarray, ln_gamma, ln_beta,
                              w_in, b_in, w_out, b_out):
    """Rewrite (LN -> W_in -> act -> W_out -> +residual) so activations flow in
    permuted (group-sorted) layout with zero runtime gathers.

    Uses permutation-equivariance of LayerNorm and linears:
      LN params are permuted; W_in rows (input dim) are permuted; W_out
      columns (output dim) are permuted so the FFN *output* is produced
      directly in permuted layout, matching the permuted residual stream.
    The caller must also permute the upstream residual producer and the
    downstream consumer (next LN), i.e. apply this layer-wide.
    """
    p = np.asarray(perm)
    return (ln_gamma[..., p], ln_beta[..., p],
            w_in[p, :], b_in,
            w_out[:, p], None if b_out is None else b_out[..., p])


def split_linear_for_per_tensor_hw(spec: PEGSpec, w_in, w_out):
    """Paper Fig. 4: decompose W_in / W_out into K slices along the grouped
    embedding axis so PEG can be simulated with per-tensor quantized matmuls:
      y = sum_k  W_in[g_k, :]^T x[g_k]         (elementwise-summed partials)
      out[g_k] = (x W_out)[:, g_k]             (concatenated partials)
    Returns ([W_in_k], [W_out_k]) lists in permuted layout.
    """
    p = spec.permutation
    w_in_p = w_in[p, :]
    w_out_p = w_out[:, p]
    bounds = np.concatenate([[0], np.cumsum(spec.group_sizes)])
    ins = [w_in_p[bounds[k]:bounds[k + 1], :] for k in range(spec.num_groups)]
    outs = [w_out_p[:, bounds[k]:bounds[k + 1]] for k in range(spec.num_groups)]
    return ins, outs


def apply_permutation(x: jnp.ndarray, perm: np.ndarray, axis: int = -1):
    """Runtime gather fallback (used only in tests / non-folded mode)."""
    return jnp.take(x, jnp.asarray(perm), axis=axis)
