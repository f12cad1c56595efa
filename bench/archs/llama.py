"""A Llama-style decoder: RMSNorm, rotary positions on the half-split
layout, grouped-query attention with one window (or none) for every
layer, SwiGLU feed-forward, tied or untied head.

The weights are drawn in a plain layout (``make_plain``), handed to the
program re-keyed into its stacked parameter pytree (``to_program``), and
read back by the reference through ``plain_view``, so the reference takes
nothing the program made. The reference (``forward``) is ``jax.numpy`` in
float32 at ``highest`` matmul precision, with no kernels, cache or
batching, and imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import linear


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def weight_shapes(m: dict) -> dict:
    """Shapes of the plain weights: ``layers`` leaves are stacked over the
    layer axis. Norm weights multiply the normalized row (published form)."""
    d, f, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hd = head_dim(m)
    qd, kvd = m["num_heads"] * hd, m["num_kv_heads"] * hd
    shapes = {
        "embed": (v, d),
        "final_norm": (d,),
        "layers": {"attn_norm": (L, d), "wq": (L, d, qd), "wk": (L, d, kvd),
                   "wv": (L, d, kvd), "wo": (L, qd, d), "ffn_norm": (L, d),
                   "w_gate": (L, d, f), "w_up": (L, d, f),
                   "w_down": (L, f, d)},
    }
    if not m["tie_embeddings"]:
        shapes["head"] = (d, v)
    return shapes


def make_plain(m: dict, key, dtype):
    """Random weights: linears N(0, 1/fan_in), embedding and head
    N(0, 0.02^2), norm weights 1."""
    shapes = weight_shapes(m)
    flat = [("embed", shapes["embed"])]
    if "head" in shapes:
        flat.append(("head", shapes["head"]))
    flat += [(n, s) for n, s in shapes["layers"].items()
             if not n.endswith("norm")]
    keys = jax.random.split(key, len(flat))
    out = {"layers": {}}
    for (name, shape), k in zip(flat, keys):
        std = 0.02 if name in ("embed", "head") else 1.0 / math.sqrt(shape[-2])
        w = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        if name in ("embed", "head"):
            out[name] = w
        else:
            out["layers"][name] = w
    out["final_norm"] = jnp.ones(shapes["final_norm"], dtype)
    for n in ("attn_norm", "ffn_norm"):
        out["layers"][n] = jnp.ones(shapes["layers"][n], dtype)
    return out


def plain_view(m: dict, params: dict) -> dict:
    """The plain layout of program-layout weights (inverse of
    ``to_program``): norm weights ``1 + g``, the rest the same arrays."""
    blk = params["scan"][0]
    out = {"embed": params["embed"],
           "final_norm": 1 + params["final_norm"]["g"],
           "layers": {"attn_norm": 1 + blk["ln1"]["g"],
                      "ffn_norm": 1 + blk["ln2"]["g"],
                      "w_down": blk["ffn"]["w_out"],
                      **{k: blk["attn"][k] for k in ("wq", "wk", "wv", "wo")},
                      **{k: blk["ffn"][k] for k in ("w_gate", "w_up")}}}
    if not m["tie_embeddings"]:
        out["head"] = params["lm_head"]
    return out


def to_program(m: dict, plain: dict) -> dict:
    """The plain weights re-keyed into the program's stacked pytree
    (``repro.models.transformer.init_params(stacked=True)`` layout). Its
    RMS norms scale by ``1 + g``, so ``g = w - 1``; every other leaf is the
    same array."""
    lay = plain["layers"]
    block = {
        "ln1": {"g": lay["attn_norm"] - 1},
        "ln2": {"g": lay["ffn_norm"] - 1},
        "attn": {"wq": lay["wq"], "wk": lay["wk"], "wv": lay["wv"],
                 "wo": lay["wo"]},
        "ffn": {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
                "w_out": lay["w_down"]},
    }
    params = {"embed": plain["embed"],
              "final_norm": {"g": plain["final_norm"] - 1},
              "scan": [block], "tail": []}
    if not m["tie_embeddings"]:
        params["lm_head"] = plain["head"]
    return params


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    m = conf["model"]
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=head_dim(m),
        d_ff=m["d_ff"], vocab_size=m["vocab_size"], window=m.get("window"),
        rope_theta=m["rope_theta"], norm="rmsnorm", act="silu",
        ffn_type="glu", tie_embeddings=m["tie_embeddings"],
        source=conf["source"])


def linears(m: dict):
    """The int8 linears of one layer on the W8A8 path, in call order:
    (name, K, N, PEG-grouped input, output bytes per element, extra f32
    input read per row). Projections and the down projection take
    per-tensor inputs (``int8_matmul``); gate and up take PEG groups
    (``int8_matmul_peg``). Up emits f32, gate reads it (``mul``) and emits
    requantized int8; the others emit f32."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    qd, kvd = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return [("wq", d, qd, False, 4, 0), ("wk", d, kvd, False, 4, 0),
            ("wv", d, kvd, False, 4, 0), ("wo", qd, d, False, 4, 0),
            ("w_up", d, f, True, 4, 0), ("w_gate", d, f, True, 1, f),
            ("w_down", f, d, False, 4, 0)]


def layers(m: dict) -> list:
    """Every layer alike: all heads, the one window, and the seven
    linears every token passes through."""
    layer = {"heads": m["num_heads"], "kv_heads": m["num_kv_heads"],
             "head_dim": head_dim(m), "window": m.get("window"),
             "linears": linears(m)}
    return [layer] * m["num_layers"]


def packed_weight_bytes(m: dict, groups: int) -> int:
    """HBM bytes of the parameters the deploy packing leaves: per layer,
    each linear's int8 payload, f32 scale and int32 per-group column sums,
    and the two bf16 norm vectors; the bf16 embedding, final norm and,
    untied, the bf16 head."""
    L, d, v = m["num_layers"], m["d_model"], m["vocab_size"]
    per_layer = sum(k * n + 4 + (groups if peg else 1) * n * 4
                    for _, k, n, peg, _, _ in linears(m)) + 2 * d * 2
    total = L * per_layer + v * d * 2 + d * 2
    if not m["tie_embeddings"]:
        total += d * v * 2
    return total


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
    q = _rope(linear(h, lw["wq"], bits).reshape(T, H, hd), pos,
              m["rope_theta"])
    k = _rope(linear(h, lw["wk"], bits).reshape(T, KV, hd), pos,
              m["rope_theta"])
    v = linear(h, lw["wv"], bits).reshape(T, KV, hd)
    # query head j reads key/value head j // (H / KV)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    mask = pos[None, :] <= pos[:, None]
    if m.get("window"):
        mask &= pos[None, :] > pos[:, None] - m["window"]
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    x = x + linear(o.reshape(T, H * hd), lw["wo"], bits)
    h = _rms_norm(x, lw["ffn_norm"], eps)
    g = linear(h, lw["w_gate"], bits)
    u = linear(h, lw["w_up"], bits)
    return x + linear(jax.nn.silu(g) * u, lw["w_down"], bits)


@functools.partial(jax.jit, static_argnames=("mk", "bits"))
def forward(weights, tokens, *, mk, bits):
    m = dict(mk)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        x, _ = jax.lax.scan(lambda c, lw: (_layer(m, bits, c, lw), None),
                            x, weights["layers"])
        h = _rms_norm(x, weights["final_norm"], m["rms_norm_eps"])
        head = (weights["embed"].T if m["tie_embeddings"]
                else weights["head"])
        return linear(h, head, bits)
