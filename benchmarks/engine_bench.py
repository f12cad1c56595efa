"""Engine-API decode microbenchmark (MaxText/JetStream style): per-call
wall times for the decomposed triad — ``prefill`` (scratch-cache prompt
pass + payload extract), ``insert`` (lane landing) and ``generate`` (one
batched decode step) — on gemma2-2b-reduced with every lane occupied,
i.e. the steady-state cost profile of a saturated continuous server.

Parity is asserted IN-BENCH before any row is written, both ways the
engine can drift:

* reference ``serve_engine`` FIFO tokens == the continuous Scheduler's
  greedy tokens on the same request set (the conformance contract of
  tests/test_engine.py, re-checked on the bench workload);
* sharded == unsharded: the workload re-runs in process on a
  tensor-parallel mesh (1, N) over all N visible devices and token
  equality against the unsharded run is asserted. It needs N >= 2, and the
  bench fails without them: on the CPU, start the process with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=2``.

Rows land in ``BENCH_serving.json`` as an ``engine_*`` section via
read-modify-write (the serving bench's workload header and rows are
preserved; stale engine rows are replaced).

  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \
      python -m benchmarks.engine_bench   (or benchmarks/run.py --sections engine)
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

JSON_PATH = "BENCH_serving.json"

BATCH_SLOTS = 8
PROMPT_PAD = 8
PROMPT_LEN = 6
MAX_LEN = 64
QUOTA = 8
WARMUP = 3
N_CALLS = 20         # timed calls per op
REPEATS = 3          # best mean-per-call wins (CPU wall jitter)


def _build(dist=None):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.runtime.engine import make_engine

    cfg = get_config("gemma2-2b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), stacked=True,
                             dtype=jnp.float32)
    eng = make_engine(cfg, params, batch_slots=BATCH_SLOTS,
                      prompt_pad_len=PROMPT_PAD, max_len=MAX_LEN,
                      dtype=jnp.float32, dist=dist)
    return cfg, params, eng


def _reqs(cfg, seed=0):
    from repro.runtime import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size, size=PROMPT_LEN)
                    .astype(np.int32),
                    max_new_tokens=QUOTA)
            for i in range(2 * BATCH_SLOTS)]


def _time_op(op, n=N_CALLS, repeats=REPEATS):
    """Best-of-repeats mean wall microseconds per call. Every engine op
    returns host numpy (the np conversion blocks on the device work), so
    plain perf_counter brackets are honest."""
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            op()
        dt = (time.perf_counter() - t0) / n
        best = dt if best is None else min(best, dt)
    return best * 1e6


def _triad_timings(eng, cfg, seed=0):
    """Per-call µs for prefill / insert / generate with all lanes live."""
    rng = np.random.RandomState(seed)

    def prompt():
        return rng.randint(1, cfg.vocab_size,
                           size=PROMPT_LEN).astype(np.int32)

    state = eng.init_state()
    payloads = []
    for slot in range(BATCH_SLOTS):
        _, payload = eng.prefill(prompt())
        payloads.append(payload)
        state = eng.insert(payload, slot, state)
    for _ in range(WARMUP):
        _, cache = eng.generate(state)
        state = state._replace(cache=cache)

    us = {"prefill": _time_op(lambda: eng.prefill(prompt()))}

    def do_insert():
        nonlocal state
        state = eng.insert(payloads[0], 0, state)
    us["insert"] = _time_op(do_insert)

    def do_generate():
        nonlocal state
        toks, cache = eng.generate(state)
        state = DecodeStateHolder.set(state, toks, cache)
    us["generate"] = _time_op(do_generate)
    return us


class DecodeStateHolder:
    """Advance DecodeState between timed generate calls (tokens feed back,
    positions bump) so the loop measures a real decode chain, not the same
    step replayed on stale inputs."""

    @staticmethod
    def set(state, toks, cache):
        return state._replace(tokens=toks, pos=state.pos + 1, cache=cache)


def _parity_vs_scheduler(cfg, params, eng):
    """serve_engine == continuous Scheduler greedy tokens, asserted."""
    import jax

    from repro.models import transformer as tfm
    from repro.runtime import serve_continuous, serve_engine
    from repro.runtime.steps import make_admit_step, make_decode_step
    import jax.numpy as jnp

    eng_reqs = _reqs(cfg, seed=3)
    serve_engine(eng, eng_reqs)

    admit_j = jax.jit(make_admit_step(cfg))
    decode_j = jax.jit(make_decode_step(cfg))

    def init(b):
        return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32)

    sched_reqs = _reqs(cfg, seed=3)
    serve_continuous(lambda t, pm, m, c: admit_j(params, t, pm, m, c),
                     lambda t, p, c: decode_j(params, t, p, c),
                     init, sched_reqs, batch_slots=BATCH_SLOTS,
                     prompt_pad_len=PROMPT_PAD, max_len=MAX_LEN)
    for a, b in zip(eng_reqs, sched_reqs):
        assert a.tokens_out == b.tokens_out, \
            f"engine != scheduler greedy tokens (rid {a.rid})"
    return sum(len(r.tokens_out) for r in eng_reqs)


def _sharded_row():
    """Sharded vs unsharded parity + the sharded triad timings over every
    visible device (at least two)."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.parallel import make_dist
    from repro.runtime import serve_engine

    n_dev = len(jax.devices())
    cfg, params, eng_sh = _build(dist=make_dist(make_serving_mesh(n_dev)))
    _, _, eng_un = _build(dist=None)
    sh_reqs, un_reqs = _reqs(cfg, seed=4), _reqs(cfg, seed=4)
    serve_engine(eng_sh, sh_reqs)
    serve_engine(eng_un, un_reqs)
    toks_sh = [r.tokens_out for r in sh_reqs]
    assert toks_sh == [r.tokens_out for r in un_reqs], \
        "sharded != unsharded greedy tokens"
    row = {"name": "engine_sharded_generate",
           "op": "generate",
           "devices": n_dev,
           "mesh": ["data", "model"],
           "batch_slots": BATCH_SLOTS,
           "max_len": MAX_LEN,
           "sharded_equals_unsharded": True,
           "parity_tokens": sum(len(t) for t in toks_sh),
           "trace_counts": eng_sh.trace_counts}
    for op, v in _triad_timings(eng_sh, cfg, seed=5).items():
        row[f"{op}_us_per_call"] = round(v, 1)
    return row


def bench():
    import jax
    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the sharded == unsharded parity needs at least two devices, "
            "one is visible; on the CPU start the process with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2")
    cfg, params, eng = _build()
    tokens = _parity_vs_scheduler(cfg, params, eng)
    us = _triad_timings(eng, cfg, seed=1)
    rows = []
    for op in ("prefill", "insert", "generate"):
        rows.append({
            "name": f"engine_{op}",
            "op": op,
            "batch_slots": BATCH_SLOTS,
            "prompt_len": PROMPT_LEN,
            "prompt_pad_len": PROMPT_PAD,
            "max_len": MAX_LEN,
            "us_per_call": round(us[op], 1),
            "calls_timed": N_CALLS,
            "repeats": REPEATS,
            "parity_tokens_vs_scheduler": tokens,
        })
    rows[-1]["tokens_per_s"] = round(BATCH_SLOTS / (us["generate"] / 1e6), 1)
    rows.append(_sharded_row())
    return rows


def report(rows) -> str:
    lines = ["name,op,us_per_call,tokens_per_s,devices,"
             "sharded_equals_unsharded"]
    for r in rows:
        lines.append(f"{r['name']},{r.get('op', '')},"
                     f"{r.get('us_per_call', r.get('generate_us_per_call', ''))},"
                     f"{r.get('tokens_per_s', '')},"
                     f"{r.get('devices', '')},"
                     f"{r.get('sharded_equals_unsharded', '')}")
    return "\n".join(lines)


def write_json(rows, path=JSON_PATH):
    """Read-modify-write: keep the serving bench's header + rows, replace
    any stale engine_* rows with this run's."""
    doc = {"workload": {}, "rows": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc["rows"] = [r for r in doc.get("rows", [])
                   if not r.get("name", "").startswith("engine_")] + rows
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return path


if __name__ == "__main__":
    rows = bench()
    print(report(rows))
    print(f"# wrote {write_json(rows)}")
