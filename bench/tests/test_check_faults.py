"""The check that decides ``correct``, driven through a whole run on the
CPU (toy widths, no chip) with the timed path broken underneath the
harness: each fault a serving cell can have turns ``correct`` false."""
import io

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny


def _run(fault=None, seed=11, path="w8a8"):
    return harness.run(tiny.cell(path), seed, 4.0, False, t_start=0.0,
                       require_tpu=False, fault=fault, log=io.StringIO())


def _on(step_kind, wrap):
    def fault(kind, fn):
        return wrap(fn) if kind == step_kind else fn
    return fault


def _token_altered(fn):
    def step(params, tokens, pos, cache):
        logits, cache = fn(params, tokens, pos, cache)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


def _state_unchanged(fn):
    def step(params, *inputs):
        before = jax.tree.map(jnp.copy, inputs[-1])   # the step donates it
        logits, _ = fn(params, *inputs)
        return logits, before
    return step


def _half_batch(fn):
    def step(params, tokens, pos, cache):
        return fn(params, tokens, pos.at[pos.shape[0] // 2:].set(-1), cache)
    return step


# the chunk step carries the prompt into the cache: left unchanged, the
# decode steps attend over an empty past. (The decode step's own write left
# out reads 0.57 here against 0.2-0.4 clean: with random weights attention
# is spread over the whole context, and the check does not see the few
# newest tokens go missing.)
FAULTS = pytest.mark.parametrize(
    "step,wrap", [("decode", _token_altered), ("chunk", _state_unchanged),
                  ("decode", _half_batch)],
    ids=["token_altered", "state_unchanged", "half_batch"])


@FAULTS
def test_fault_fails_the_check(step, wrap):
    r = _run(_on(step, wrap))
    assert r["correct"] is False
    gap = r["checks"]["max_gap_sd"]
    assert gap["value"] > gap["limit"]


@FAULTS
def test_fault_fails_the_check_on_the_bf16_path(step, wrap):
    r = _run(_on(step, wrap), path="bf16")
    assert r["correct"] is False
    gap = r["checks"]["max_gap_sd"]
    assert gap["value"] > gap["limit"]
