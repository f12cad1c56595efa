"""Model side of the yardstick: a configuration's file, the weights the
benchmark makes from the seed, and the architecture module that lays
them out.

A configuration's model block names its architecture (``"architecture"``),
a module of bench/archs/ found by that name (``arch``). The weights are
the benchmark's (``weights``): drawn in the architecture's plain layout,
handed to the program in its parameter pytree, and read back by the
reference (bench/reference.py) through the architecture's ``plain_view``,
so the reference takes nothing the program made.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp

ARCHS = Path(__file__).resolve().parent / "archs"


def load_config(path) -> dict:
    with open(path) as f:
        return json.load(f)


def arch(m: dict):
    """The architecture module that the model block ``m`` names:
    ``bench.archs.<m["architecture"]>``. There is no default."""
    name = m.get("architecture")
    if name is not None:
        try:
            return importlib.import_module(f"bench.archs.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"bench.archs.{name}":
                raise
    known = sorted(p.stem for p in ARCHS.glob("*.py")
                   if p.stem != "__init__")
    what = ("no architecture" if name is None
            else f"an unknown architecture {name!r}")
    raise ValueError(f"the model block names {what}; known: {known}")


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also one past 32 bits: the low
    word seeds the key, the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def weights(m: dict, seed: int, shardings=None, dtype=jnp.bfloat16):
    """The weights of ``seed`` in the program's layout, made on the device
    in one jitted call. The reference reads the same call's output through
    the architecture's ``plain_view``, so both see the same values."""
    a = arch(m)
    fn = jax.jit(lambda k: a.to_program(m, a.make_plain(m, k, dtype)),
                 out_shardings=shardings)
    return fn(seed_key(seed))


def weight_shardings(m: dict, dist):
    """Shardings of the program-layout weights on the serving mesh."""
    from repro.parallel import make_param_shardings
    a = arch(m)
    shapes = jax.eval_shape(lambda k: a.to_program(
        m, a.make_plain(m, k, jnp.bfloat16)), jax.random.PRNGKey(0))
    return make_param_shardings(shapes, dist)
