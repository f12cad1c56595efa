"""PEG-int8 gradient compression for cross-pod data parallelism.

Beyond-paper application of the paper's core machinery: gradients, like
transformer activations, have per-channel dynamic-range structure, so we
quantize each gradient tensor to int8 with per-group scales (the PEG scheme
applied along the last axis) before the inter-pod exchange, with an error-
feedback accumulator (Seide et al. 2014 style) so the quantization noise is
compensated on the next step instead of biasing the update.

Exchange pattern under ``shard_map`` over the ``pod`` axis:
    q, s   = peg_quantize(g + err)           # int8 payload + f32 group scales
    qs, ss = all_gather(q), all_gather(s)    # int8 on the wire (DCN)
    g_avg  = mean_k dequant(qs[k], ss[k])
    err'   = (g + err) - dequant(q, s)       # local error feedback

For P pods this moves P*X int8 bytes per device versus ~2*X bf16 bytes for a
ring all-reduce — a 4x wire-byte saving at P=2 and still >2x at P=4 when the
pod axis is small (inter-pod DCN is the scarce resource, per DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _group_scales(x: jnp.ndarray, group_size: int) -> jnp.ndarray:
    flat = x.reshape(-1)
    pad = (-flat.size) % group_size
    flat = jnp.pad(flat, (0, pad))
    g = flat.reshape(-1, group_size)
    return jnp.max(jnp.abs(g), axis=1) / 127.0


def quantize_grad(g: jnp.ndarray, group_size: int = 256
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization with per-group scales along flattened g."""
    scales = jnp.maximum(_group_scales(g, group_size),
                         jnp.finfo(jnp.float32).tiny)
    flat = g.reshape(-1)
    pad = (-flat.size) % group_size
    flat = jnp.pad(flat, (0, pad)).reshape(-1, group_size)
    q = jnp.clip(jnp.round(flat / scales[:, None]), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_grad(q: jnp.ndarray, scales: jnp.ndarray, shape, dtype
                    ) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape).astype(dtype)


def compressed_psum(g: jnp.ndarray, err: jnp.ndarray, axis_name: str,
                    group_size: int = 256):
    """Inside shard_map: int8 all-gather + local dequant-mean over axis_name.

    Returns (averaged_grad, new_error_feedback). Must be called with
    identically-shaped g on every member of the axis.
    """
    g_comp = (g + err).astype(jnp.float32)
    q, s = quantize_grad(g_comp, group_size)
    qs = jax.lax.all_gather(q, axis_name)        # (P, G, group) int8 on wire
    ss = jax.lax.all_gather(s, axis_name)        # (P, G) f32 (tiny)
    deq = jax.vmap(lambda qq, sc: dequantize_grad(qq, sc, g.shape, jnp.float32)
                   )(qs, ss)
    g_avg = jnp.mean(deq, axis=0).astype(g.dtype)
    new_err = g_comp - dequantize_grad(q, s, g.shape, jnp.float32)
    return g_avg, new_err.astype(jnp.float32)


def make_crosspod_allreduce(mesh, grad_specs, *, group_size: int = 256,
                            compressed: bool = True):
    """Build f(grads, err) -> (avg_grads, err') reducing over the 'pod' axis.

    ``grad_specs`` is a pytree of PartitionSpec matching the grads tree; the
    specs must not use the 'pod' axis (each pod holds a full replica of its
    intra-pod-sharded gradient, so reducing over 'pod' is exactly the
    cross-pod data-parallel all-reduce).

    Error-feedback buffers are PER-POD state: leaves carry a leading pod dim
    (see init_error_feedback) sharded P('pod', ...). The averaged gradients
    are mathematically replicated across pods (every pod gathers the same
    int8 payloads and reduces locally) — the VMA checker cannot infer this
    through the quantized gather, hence check_vma=False.
    """
    if "pod" not in mesh.axis_names:
        def identity(grads, err):
            return jax.tree.map(lambda g: g, grads), err
        return identity

    from jax.sharding import PartitionSpec
    err_specs = jax.tree.map(lambda s: PartitionSpec("pod", *s), grad_specs,
                             is_leaf=lambda x: isinstance(x, PartitionSpec))

    def local_fn(grads, err):
        def reduce_leaf(g, e):
            e = e[0]                                # squeeze local pod dim
            if not compressed:
                return jnp.mean(jax.lax.all_gather(g, "pod"), axis=0), \
                    e[None]
            avg, new_e = compressed_psum(g, e, "pod", group_size)
            return avg, new_e[None]
        pairs = jax.tree.map(reduce_leaf, grads, err)
        avg = jax.tree.map(lambda t: t[0], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
        new_err = jax.tree.map(lambda t: t[1], pairs,
                               is_leaf=lambda x: isinstance(x, tuple))
        return avg, new_err

    def allreduce(grads, err):
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(grad_specs, err_specs),
            out_specs=(grad_specs, err_specs),
            check_vma=False,
        )(grads, err)

    return allreduce


def init_error_feedback(grads, n_pod: int = 1):
    """Per-pod error-feedback buffers: leaves (n_pod, *grad_shape) f32,
    to be sharded P('pod', ...) on multi-pod meshes."""
    return jax.tree.map(
        lambda g: jnp.zeros((n_pod,) + g.shape, jnp.float32), grads)
