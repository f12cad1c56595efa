"""Architectures found by name (bench/archs/): the Llama-style module gives
the weights, reference logits and counts that the harness computed before
they moved there, bit for bit; an unknown name fails with the known ones;
a layer's window bounds the keys counted in that layer alone; and a module
registered under a name no file of bench/ knows serves a cell end to end
(CPU, toy widths)."""
import hashlib
import io
import sys
import types
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import costs, harness, model as bmodel, reference
from bench.archs import llama
from bench.metrics import attend_roofline, matmul_roofline, step_mfu
from bench.tests import tiny

# Golden values, computed with the harness before the architecture moved
# out of bench/model.py, bench/reference.py and bench/costs.py (CPU).
WEIGHTS = {
    3: "08ca204e89b3fb1880ff4973d013b78a14d80d536ac57c4dad3e0b60f400184e",
    2 ** 33 + 5:
        "09524cb455620474574649204df30f8dee75d0a3eeea1e24639387909da589c6",
}
LOGITS = {
    (None, None):
        "50b8dec8020df3c93fa3157de6acdd4f3a32e71e170f8ad725f17d9b4abb21aa",
    (None, (8, 8)):
        "95dcb590e173ebd2dfc34cfe42cc5b47507f90cea7a1f3105f45a5443cda8f49",
    (8, None):
        "105984372f686946f30a02407fcebf2a93b3c83e2e83fe983b43056042afd0b2",
    (8, (8, 8)):
        "81b697c15eaa931e9cb657f408d0d3e48464fbd46073a5978dfec1c14db7ee1b",
}
CTX = [1, 2, 17, 128, 650, 2048, 2432]
COUNTS = {
    "tiny": {"model_ops": 3816448,
             "attend_cost_16": (2702336, 1351168),
             "attend_cost_8": (2702336, 886704),
             "matmul_cost_16": (2359296, 169984),
             "matmul_cost_2048": (301989888, 11094016),
             "packed_weight_bytes": 117432},
    "danube": {"model_ops": 55196958720,
               "attend_cost_16": (1945681920, 486420480),
               "attend_cost_8": (1945681920, 251823936),
               "matmul_cost_16": (118908518400, 3793858560),
               "matmul_cost_2048": (15220290355200, 12533084160),
               "packed_weight_bytes": 3971182752},
}
READINGS = {
    "bf16": {"step_mfu": 0.022746387492385788,
             "attend_roofline": 1.642450989010989,
             "matmul_roofline": 4.008318593406593},
    "w8a8": {"step_mfu": 0.01140213317048346,
             "attend_roofline": 0.850310564102564,
             "matmul_roofline": 4.008318593406593},
}


def _sha(tree):
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


def _danube():
    return bmodel.load_config(tiny.ROOT / "bench" / "configs"
                              / "danube3-4b-bf16.json")["model"]


@pytest.mark.parametrize("seed", sorted(WEIGHTS))
def test_weights_match_the_parent(seed):
    assert _sha(bmodel.weights(tiny.MODEL, seed)) == WEIGHTS[seed]


@pytest.mark.parametrize("window,bits", sorted(LOGITS, key=str))
def test_reference_logits_match_the_parent(window, bits):
    m = dict(tiny.MODEL, window=window)
    plain = bmodel.arch(m).plain_view(m, bmodel.weights(m, 3))
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], 32)
    assert _sha(reference.logits(m, plain, tokens, bits)) == \
        LOGITS[(window, bits)]


@pytest.mark.parametrize("shapes", sorted(COUNTS))
def test_counts_match_the_parent(shapes):
    m = tiny.MODEL if shapes == "tiny" else _danube()
    got = {"model_ops": costs.model_ops(m, CTX, 5),
           "packed_weight_bytes": bmodel.arch(m).packed_weight_bytes(m, 4)}
    for kv in (16, 8):
        got[f"attend_cost_{kv}"] = costs.attend_cost(m, CTX, kv)
    for rows in (16, 2048):
        got[f"matmul_cost_{rows}"] = costs.matmul_cost(m, 4, rows)
    assert got == COUNTS[shapes]


@pytest.mark.parametrize("path", sorted(READINGS))
def test_readers_match_the_parent(path):
    """What step_mfu, attend_roofline and matmul_roofline read from the
    same calls and trace, at danube3-4b's shapes."""
    calls = [{"kind": "decode", "shape": (4, 1),
              "pos": np.array([[5], [600], [-1], [2431]])},
             {"kind": "chunk", "shape": (2, 128),
              "pos": np.stack([np.arange(128), -np.ones(128, int)])},
             {"kind": "decode", "shape": (4, 1),
              "pos": np.array([[6], [601], [0], [-1]])}]
    rec = SimpleNamespace(
        model=_danube(), path=path, groups=4,
        kv_bits=16 if path == "bf16" else 8,
        peaks=costs.PEAKS["TPU v5 lite"], calls=calls, window=(10.0, 40.0),
        tokens=1400, trace={"op_s": {"paged_attend_decode.7": 0.0125,
                                     "paged_int8_attend_decode.2": 0.0125,
                                     "int8_matmul.3": 0.25,
                                     "int8_matmul_peg.1": 0.125,
                                     "fusion.1": 1.0}})
    got = {r.__name__.rsplit(".", 1)[-1]: r.read(rec)
           for r in (step_mfu, attend_roofline, matmul_roofline)}
    assert got == READINGS[path]


@pytest.mark.parametrize("name", ["no_such_arch", None])
def test_an_unknown_or_missing_architecture_names_the_known_ones(name):
    m = dict(tiny.MODEL, architecture=name)
    if name is None:
        del m["architecture"]
    with pytest.raises(ValueError, match=r"known: \[.*'llama'.*\]"):
        bmodel.arch(m)


def _register(monkeypatch, name, **attrs):
    """A module ``bench.archs.<name>`` that only ``sys.modules`` holds."""
    mod = types.ModuleType(f"bench.archs.{name}")
    mod.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_window_bounds_the_keys_of_its_own_layer_only(monkeypatch):
    layer = {"heads": 4, "kv_heads": 2, "head_dim": 16,
             "linears": [("a", 10, 60, False, 4, 0),
                         ("b", 20, 20, False, 4, 0)]}
    _register(monkeypatch, "two_kinds", layers=lambda m: [
        dict(layer, window=8), dict(layer, window=None)])
    m = {"architecture": "two_kinds", "d_model": 64, "vocab_size": 128}
    ctx = [3, 8, 9, 30]
    windowed, full = 3 + 8 + 8 + 8, 3 + 8 + 9 + 30
    ops, nbytes = costs.attend_cost(m, ctx, kv_bits=16)
    assert ops == 4 * 4 * 16 * (windowed + full)
    assert nbytes == 2 * 2 * 16 * 2 * (windowed + full)
    assert costs.model_ops(m, ctx, 2) == 2 * 64 * 128 * 2 \
        + 2 * 2 * 1000 * len(ctx) + 4 * 4 * 16 * (windowed + full)


def test_an_architecture_found_by_name_serves_a_cell(monkeypatch):
    """A module under a name that nothing in bench/*.py knows, re-exporting
    the Llama-style one, serves the toy bf16 cell and passes its check."""
    _register(monkeypatch, "plugged_in",
              **{k: v for k, v in vars(llama).items()
                 if not k.startswith("__")})
    cell = tiny.cell("bf16")
    cell["conf"]["model"]["architecture"] = "plugged_in"
    assert bmodel.arch(cell["conf"]["model"]) is sys.modules[
        "bench.archs.plugged_in"]
    r = harness.run(cell, 5, 4.0, False, t_start=0.0, require_tpu=False,
                    log=io.StringIO())
    assert r["correct"] is True
    assert r["checks"]["served_tokens_checked"]["value"] >= \
        tiny.PARAMS["check"]["min_checked"]
