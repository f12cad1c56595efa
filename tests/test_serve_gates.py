"""The serve launcher's parity gates, on the reduced h2o-danube3-4b (f32,
CPU interpreter): clean runs pass every gate, planted faults fail theirs,
and a missed tolerance exits non-zero.

The faults and the single-flip witness come from
``benchmarks/quant_floor.py``, which measures the same on the chip at full
width (PERF.md records those readings).
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Mode, QuantCtx, peg_policy
from repro.launch import serve
from repro.models import transformer as tfm
from repro.runtime.serve_loop import ServeStats

pytestmark = [pytest.mark.deploy, pytest.mark.serve]


@pytest.fixture(scope="module")
def witness():
    from benchmarks import quant_floor
    return quant_floor.measure(["--reduced"])


@pytest.mark.parametrize("check", ["served", "int8_clean",
                                   "kv_clean", "int8_fault_kv_grid"])
def test_clean_comparison_passes(witness, check):
    r = witness[check]
    assert r["rel_rms"] <= r["tolerance"], r


@pytest.mark.parametrize("check", ["int8_fault_peg_scales",
                                   "int8_fault_peg_perm", "kv_fault_kv_grid",
                                   "fault_kv_grid_served"])
def test_planted_fault_fails_its_gate(witness, check):
    r = witness[check]
    assert r["rel_rms"] > 10 * r["tolerance"], r


@pytest.mark.parametrize("site", ["attn_in", "ffn_in", "ffn_hidden"])
def test_interpreted_kernels_emit_the_oracle_codes(witness, site):
    r = witness[f"codes_{site}"]
    assert r["total"] > 0 and r["mismatching"] == 0, r


def test_one_flipped_code_moves_the_logits(witness):
    assert witness["flip_all_layers_2"]["rel_rms"] > 1e-3


def test_gate_exits_nonzero_past_tolerance(capsys):
    serve.gate("t", "within", (1e-4, 1e-3), 1e-3)
    with pytest.raises(SystemExit, match="tolerance"):
        serve.gate("t", "past", (2e-3, 1e-3), 1e-3)
    out = capsys.readouterr().out
    assert "within" in out and "OK" in out and "FAIL" in out


def test_rel_errors():
    ref = np.array([[3.0, 4.0]])
    assert serve.rel_errors(ref, ref) == (0.0, 0.0)
    rms, mx = serve.rel_errors(ref, ref + np.array([[0.0, 0.5]]))
    assert rms == pytest.approx(0.1) and mx == pytest.approx(0.125)


def test_deploy_residual_stream_is_f32():
    """Under Mode.DEPLOY bf16 params still carry an f32 residual stream:
    the embedding output is cast before the first quantizer."""
    cfg = get_config("h2o-danube3-4b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    toks = jnp.zeros((1, 4), jnp.int32)
    pol = peg_policy(4)
    deploy = tfm._embed(cfg, params, toks, None,
                        QuantCtx(policy=pol, mode=Mode.DEPLOY, act_state={}))
    plain = tfm._embed(cfg, params, toks, None, None)
    assert deploy.dtype == jnp.float32 and plain.dtype == jnp.bfloat16


def test_serve_verify_deploy_kv8():
    """``--verify`` on the integer path with the paged int8 cache and
    chunked prefill: every gate passes, and the replayed logits come back
    on the stats, outside its JSON form."""
    stats = serve.main(["--arch", "h2o-danube3-4b", "--reduced",
                        "--scheduler", "continuous", "--paged-kv",
                        "--block-size", "4", "--prefill-chunk", "8",
                        "--requests", "3", "--prompt-len", "16",
                        "--new-tokens", "4", "--batch-slots", "2",
                        "--max-len", "64", "--quantize", "--deploy-int8",
                        "--kv-bits", "8", "--verify"])
    cfg = get_config("h2o-danube3-4b").reduced()
    assert stats.replayed_logits.shape == (2, 4, cfg.vocab_size)
    doc = stats.to_json()
    assert "replayed_logits" not in doc
    json.dumps(doc, default=str)
    assert isinstance(ServeStats().to_json(), dict)
