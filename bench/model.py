"""Model side of the yardstick: a configuration's file, the weights the
benchmark makes from the seed, and their hand-over to the program.

The weights are the benchmark's (``weights``): drawn in a plain layout,
handed to the program re-keyed into its stacked parameter pytree
(``to_program``), and read back by the reference (bench/reference.py)
through ``plain_view``, so the reference takes nothing the program made.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp


def load_config(path) -> dict:
    with open(path) as f:
        return json.load(f)


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also one past 32 bits: the low
    word seeds the key, the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


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


def _make_plain(m: dict, key, dtype):
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


def weights(m: dict, seed: int, shardings=None, dtype=jnp.bfloat16):
    """The weights of ``seed`` in the program's layout (``to_program``),
    made on the device in one jitted call. The reference reads the same
    call's output through ``plain_view``, so both see the same values."""
    fn = jax.jit(lambda k: to_program(m, _make_plain(m, k, dtype)),
                 out_shardings=shardings)
    return fn(seed_key(seed))


def weight_shardings(m: dict, dist):
    """Shardings of the program-layout weights on the serving mesh."""
    from repro.parallel import make_param_shardings
    shapes = jax.eval_shape(lambda k: to_program(
        m, _make_plain(m, k, jnp.bfloat16)), jax.random.PRNGKey(0))
    return make_param_shardings(shapes, dist)


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


def to_program(cfg_model: dict, plain: dict) -> dict:
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
    if not cfg_model["tie_embeddings"]:
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
