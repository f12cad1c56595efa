"""Plain reference of the configurations' architecture: a Llama-style
decoder (RMSNorm, rotary positions on the half-split layout, grouped-query
attention, SwiGLU feed-forward, tied or untied head) in ``jax.numpy`` and
float32 at ``highest`` matmul precision, with no kernels, cache or
batching. It reads the benchmark's plain weights (bench/model.py) and
imports nothing of the program.

``bits`` gives the control: every linear (projections, feed-forward, head)
computed on a symmetric grid of that many bits, weights per output channel
and inputs per token, both by their absolute maximum.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import head_dim


def _fake_quant(x, bits: int, axis: int):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _linear(x, w, bits: Optional[Tuple[int, int]]):
    """x (T, K) @ w (K, N) in f32; with ``bits`` = (weight, input) bits
    the operands are first put on their symmetric grids."""
    w = w.astype(jnp.float32)
    if bits is not None:
        w = _fake_quant(w, bits[0], axis=0)
        x = _fake_quant(x, bits[1], axis=-1)
    return x @ w


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, H, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: dict, bits, x, lw):
    T = x.shape[0]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], head_dim(m)
    eps = m["rms_norm_eps"]
    pos = jnp.arange(T)
    h = _rms_norm(x, lw["attn_norm"], eps)
    q = _rope(_linear(h, lw["wq"], bits).reshape(T, H, hd), pos,
              m["rope_theta"])
    k = _rope(_linear(h, lw["wk"], bits).reshape(T, KV, hd), pos,
              m["rope_theta"])
    v = _linear(h, lw["wv"], bits).reshape(T, KV, hd)
    # query head j reads key/value head j // (H / KV)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    mask = pos[None, :] <= pos[:, None]
    if m.get("window"):
        mask &= pos[None, :] > pos[:, None] - m["window"]
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    x = x + _linear(o.reshape(T, H * hd), lw["wo"], bits)
    h = _rms_norm(x, lw["ffn_norm"], eps)
    g = _linear(h, lw["w_gate"], bits)
    u = _linear(h, lw["w_up"], bits)
    return x + _linear(jax.nn.silu(g) * u, lw["w_down"], bits)


@functools.partial(jax.jit, static_argnames=("mk", "bits"))
def _forward(weights, tokens, *, mk, bits):
    m = dict(mk)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        x, _ = jax.lax.scan(lambda c, lw: (_layer(m, bits, c, lw), None),
                            x, weights["layers"])
        h = _rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
        head = (weights["embed"].T if m["tie_embeddings"]
                else weights["head"])
        return _linear(h, head, bits)


def logits(m: dict, weights, tokens, bits: Optional[Tuple[int, int]] = None):
    """(T, V) float32 logits of one sequence ``tokens`` (T,)."""
    mk = tuple(sorted((k, v) for k, v in m.items()
                      if not isinstance(v, (dict, list))))
    return _forward(weights, jnp.asarray(tokens, jnp.int32), mk=mk,
                    bits=bits)
