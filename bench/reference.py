"""Plain reference of a configuration's architecture: its ``forward`` in
bench/archs/, ``jax.numpy`` in float32 at ``highest`` matmul precision,
with no kernels, cache or batching. It reads the benchmark's plain weights
(bench/model.py) and imports nothing of the program.

``bits`` gives the control: every linear (projections, feed-forward, head)
computed on a symmetric grid of that many bits, weights per output channel
and inputs per token, both by their absolute maximum (``linear``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from bench.model import arch


def _fake_quant(x, bits: int, axis: int):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def linear(x, w, bits: Optional[Tuple[int, int]]):
    """x (T, K) @ w (K, N) in f32; with ``bits`` = (weight, input) bits
    the operands are first put on their symmetric grids."""
    w = w.astype(jnp.float32)
    if bits is not None:
        w = _fake_quant(w, bits[0], axis=0)
        x = _fake_quant(x, bits[1], axis=-1)
    return x @ w


def logits(m: dict, weights, tokens, bits: Optional[Tuple[int, int]] = None):
    """(T, V) float32 logits of one sequence ``tokens`` (T,)."""
    mk = tuple(sorted((k, v) for k, v in m.items()
                      if not isinstance(v, (dict, list))))
    return arch(m).forward(weights, jnp.asarray(tokens, jnp.int32), mk=mk,
                           bits=bits)
