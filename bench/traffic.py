"""One generator for every traffic mix: a mix is a JSON file of parameters
under bench/traffic/, read here.

Every seed serves the same work: each block of ``block`` requests holds
the same (prompt, output) length pairs, taken at evenly spaced quantiles
of the mix's distributions and paired by a fixed permutation, and the
blocks follow one another in a fixed order that no seed changes. A window
of a slow cell covers a fraction of a block, so an order drawn from the
seed would change the work in it. The run's seed draws the token ids.
The list is ``queue`` requests long, more than any window serves, so a
closed loop never runs dry.
"""
from __future__ import annotations

import json
import math
from statistics import NormalDist

import numpy as np


def load_mix(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of the spec's
    lognormal, rounded and clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def length_pairs(mix: dict) -> np.ndarray:
    """(block, 2) prompt and output lengths of one block, in a fixed
    order that does not depend on the seed."""
    n = mix["block"]
    prompts = _quantiles(mix["prompt"], n)
    outputs = _quantiles(mix["output"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return np.stack([prompts, outputs[pairing]], axis=1)


def lengths(mix: dict) -> np.ndarray:
    """(queue, 2) prompt and output lengths of every run: block ``b`` is
    ``length_pairs`` in the order of a permutation drawn from ``b``."""
    pairs = length_pairs(mix)
    n_blocks = -(-mix["queue"] // len(pairs))
    blocks = [pairs[np.random.default_rng([1, b]).permutation(len(pairs))]
              for b in range(n_blocks)]
    return np.concatenate(blocks)[:mix["queue"]]


def generate(mix: dict, vocab: int, seed: int):
    """[(prompt ids, output length)] of the run: ``queue`` requests of
    ``lengths``, token ids uniform over [``low_id``, vocab) from the
    seed."""
    if mix["kind"] != "closed_loop" or mix.get("shared_prefix", 0):
        raise ValueError("the generator makes closed-loop traffic without "
                         "shared prefixes")
    rng = np.random.default_rng(seed)
    return [(rng.integers(mix["low_id"], vocab, size=int(p)).astype(np.int32),
             int(o)) for p, o in lengths(mix)]
