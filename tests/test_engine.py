"""Engine API conformance suite (runtime/engine.py).

The contract under test: the decomposed prefill -> insert -> generate triad
driven by the reference FIFO loop (``serve_engine``) emits exactly the same
greedy tokens as the continuous Scheduler — whatever serving mode the
Scheduler runs in (dense/paged, prefix-cache, over-commit, f32 / deploy-int8
/ kv-bits 8/4). The triad reuses the Scheduler's ONE admit trace on a
private scratch cache, so the suite also pins the recompile guard (each of
prefill / insert / generate traces exactly once across arbitrary admission
patterns) and the insert bit-isolation invariant (landing a payload in one
lane leaves every other lane's cache bytes untouched).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.runtime import (BlockPool, RadixCache, Request, serve_continuous,
                           serve_engine)
from repro.runtime.engine import make_engine
from repro.runtime.steps import (make_admit_step, make_chunk_prefill_step,
                                 make_decode_step, make_swap_steps)

pytestmark = [pytest.mark.engine, pytest.mark.serve]

MAX_LEN = 32
PAD = 8
BLOCK = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("gemma2-2b").reduced()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), stacked=True,
                             dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def deployed():
    """Integer deployment path (packed int8 weights + Pallas kernels),
    mirroring tests/test_scheduler.py's setup."""
    from repro.core import Mode, QuantCtx, build_deploy, peg_policy
    from repro.core.pipeline import ptq
    cfg = get_config("gemma2-2b").reduced()
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key, stacked=True, dtype=jnp.float32)
    pol = peg_policy(4)
    flat = tfm.init_params(cfg, key, stacked=False, dtype=jnp.float32)
    calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(10),
                                           (2, 8), 0, cfg.vocab_size)}]

    def fwd(p, b, ctx):
        logits, _ = tfm.forward(cfg, p, b["tokens"], ctx=ctx)
        return logits

    qm = ptq(fwd, flat, calib, pol, collect_inputs=True)
    shared = {}
    for site, qp in qm.act_state.items():
        base = ("layer/" + site.split("/", 1)[1]
                if site.startswith("layer") else site)
        shared.setdefault(base, qp)
    packed, acts = build_deploy(cfg, params, pol, shared)

    def ctx_factory():
        return QuantCtx(policy=pol, mode=Mode.DEPLOY, act_state=shared,
                        deploy_acts=acts)
    return cfg, packed, ctx_factory


def _mk_reqs(rng, cfg, lens_quotas):
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size, size=n)
                    .astype(np.int32),
                    max_new_tokens=q)
            for i, (n, q) in enumerate(lens_quotas)]


SPEC = [(4, 2), (8, 6), (3, 1), (6, 4), (5, 3)]


def _engine(cfg, params, *, kv_bits=16, paged=False, ctx_factory=None,
            batch_slots=2):
    return make_engine(cfg, params, batch_slots=batch_slots,
                       prompt_pad_len=PAD, max_len=MAX_LEN,
                       dtype=jnp.float32, kv_bits=kv_bits, paged=paged,
                       block_size=BLOCK, ctx_factory=ctx_factory)


def _scheduler_tokens(cfg, params, reqs, *, kv_bits=16, ctx_factory=None,
                      batch_slots=2, paged=False, prefix=False,
                      over_commit=False, swap=False, num_blocks=None):
    """The Scheduler side of the conformance check: serve ``reqs`` through
    serve_continuous in the requested mode (the Scheduler itself routes
    every model call through its internal Engine)."""
    admit_j = jax.jit(make_admit_step(cfg, ctx_factory=ctx_factory))
    decode_j = jax.jit(make_decode_step(cfg, ctx_factory=ctx_factory))
    admit = lambda t, pm, m, c: admit_j(params, t, pm, m, c)
    decode = lambda t, p, c: decode_j(params, t, p, c)
    chunk = None
    if prefix or over_commit:
        chunk_j = jax.jit(make_chunk_prefill_step(cfg, ctx_factory=ctx_factory))
        chunk = lambda t, pm, m, c: chunk_j(params, t, pm, m, c)
    nb_lane = tfm.paged_lane_blocks(cfg, MAX_LEN, BLOCK)
    pool = (BlockPool(num_blocks or batch_slots * nb_lane, BLOCK,
                      batch_slots, nb_lane) if paged else None)
    swap_out = swap_in = None
    if swap:
        so, si = make_swap_steps()
        swap_out, swap_in = jax.jit(so), jax.jit(si, donate_argnums=(0,))

    def init(b):
        if not paged:
            return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                                  kv_bits=kv_bits)
        return tfm.init_cache(cfg, b, MAX_LEN, dtype=jnp.float32,
                              kv_bits=kv_bits, paged=True, block_size=BLOCK,
                              num_blocks=pool.num_blocks, mapped=False)

    serve_continuous(
        admit, decode, init, reqs, batch_slots=batch_slots,
        prompt_pad_len=PAD, max_len=MAX_LEN, block_pool=pool,
        chunk_fn=chunk, prefill_chunk=PAD if chunk is not None else None,
        radix_cache=RadixCache(BLOCK) if prefix else None,
        write_caps=(tfm.attn_write_caps(cfg, MAX_LEN, BLOCK)
                    if paged else None),
        ring_tokens=(tfm.paged_ring_tokens(cfg, MAX_LEN, BLOCK)
                     if paged else None),
        copy_block_fn=(jax.jit(tfm.cache_copy_block, donate_argnums=(0,))
                       if prefix else None),
        over_commit=over_commit, swap_out_fn=swap_out, swap_in_fn=swap_in)
    return [r.tokens_out for r in reqs]


def _assert_same_tokens(eng_reqs, sched_toks, kv_bits):
    if kv_bits == 4:
        # int4 per-slot dynamic grids round-trip prefill reads
        # approximately (house rule, launch/serve.py compare()): report a
        # strict match-rate floor instead of exact equality
        matched = sum(1 for r, s in zip(eng_reqs, sched_toks)
                      for x, y in zip(r.tokens_out, s) if x == y)
        total = sum(min(len(r.tokens_out), len(s))
                    for r, s in zip(eng_reqs, sched_toks))
        assert matched / max(total, 1) >= 0.9, (matched, total)
        return
    for r, s in zip(eng_reqs, sched_toks):
        assert r.tokens_out == s, f"rid {r.rid}: {r.tokens_out} != {s}"


class TestEngineSchedulerParity:
    @pytest.mark.parametrize("kv_bits", [16, 8, 4])
    def test_dense(self, tiny, kv_bits):
        cfg, params = tiny
        rng = np.random.RandomState(7)
        reqs = _mk_reqs(rng, cfg, SPEC)
        sched = _scheduler_tokens(
            cfg, params, _mk_reqs(np.random.RandomState(7), cfg, SPEC),
            kv_bits=kv_bits)
        serve_engine(_engine(cfg, params, kv_bits=kv_bits), reqs)
        _assert_same_tokens(reqs, sched, kv_bits)

    @pytest.mark.paged
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_paged(self, tiny, kv_bits):
        """Identity-mapped paged engine == pool-managed paged Scheduler ==
        each other's greedy tokens (the decomposed insert's drop-in dense
        layout contract)."""
        cfg, params = tiny
        reqs = _mk_reqs(np.random.RandomState(8), cfg, SPEC)
        sched = _scheduler_tokens(
            cfg, params, _mk_reqs(np.random.RandomState(8), cfg, SPEC),
            kv_bits=kv_bits, paged=True)
        serve_engine(_engine(cfg, params, kv_bits=kv_bits, paged=True), reqs)
        _assert_same_tokens(reqs, sched, kv_bits)

    @pytest.mark.prefix
    def test_prefix_cache(self, tiny):
        """Prefix sharing is parity-preserving: the Scheduler WITH a radix
        cache (shared-prefix workload, real hits) matches the bare dense
        engine's FIFO tokens."""
        cfg, params = tiny
        rng = np.random.RandomState(9)
        shared = rng.randint(1, cfg.vocab_size, size=4).astype(np.int32)
        spec = [(8, 4)] * 4

        def mk():
            r = np.random.RandomState(9)
            r.randint(1, cfg.vocab_size, size=4)    # burn the shared draw
            return [Request(rid=i,
                            prompt=np.concatenate(
                                [shared, r.randint(1, cfg.vocab_size,
                                                   size=n - 4)])
                            .astype(np.int32),
                            max_new_tokens=q)
                    for i, (n, q) in enumerate(spec)]
        reqs = mk()
        sched = _scheduler_tokens(cfg, params, mk(), paged=True, prefix=True)
        serve_engine(_engine(cfg, params), reqs)
        _assert_same_tokens(reqs, sched, 16)

    @pytest.mark.preempt
    @pytest.mark.parametrize("swap", [False, True])
    def test_over_commit(self, tiny, swap):
        """Over-commit preemption (drop AND swap resume) is
        parity-preserving vs the bare dense engine. A starved pool forces
        real preemptions."""
        cfg, params = tiny
        nb_lane = tfm.paged_lane_blocks(cfg, MAX_LEN, BLOCK)
        reqs = _mk_reqs(np.random.RandomState(10), cfg, SPEC)
        sched = _scheduler_tokens(
            cfg, params, _mk_reqs(np.random.RandomState(10), cfg, SPEC),
            paged=True, over_commit=True, swap=swap,
            num_blocks=nb_lane + nb_lane // 2)
        serve_engine(_engine(cfg, params), reqs)
        _assert_same_tokens(reqs, sched, 16)

    @pytest.mark.deploy
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_deploy_int8(self, deployed, kv_bits):
        cfg, packed, ctx_factory = deployed
        reqs = _mk_reqs(np.random.RandomState(11), cfg, SPEC[:4])
        sched = _scheduler_tokens(
            cfg, packed, _mk_reqs(np.random.RandomState(11), cfg, SPEC[:4]),
            kv_bits=kv_bits, ctx_factory=ctx_factory)
        serve_engine(_engine(cfg, packed, kv_bits=kv_bits,
                             ctx_factory=ctx_factory), reqs)
        _assert_same_tokens(reqs, sched, kv_bits)


class TestRecompileGuard:
    @pytest.mark.parametrize("paged", [False, True])
    def test_each_step_traces_once(self, tiny, paged):
        """Across arbitrary admission patterns — varying prompt lengths,
        quotas, lane compositions, a mid-stream second wave — each of
        prefill / insert (payload extract + lane insert) / generate traces
        exactly once. A recompile would show as a count > 1 (the counters
        bump inside the traced python body, once per trace)."""
        cfg, params = tiny
        eng = _engine(cfg, params, batch_slots=3, paged=paged)
        rng = np.random.RandomState(12)
        state = serve_engine(eng, _mk_reqs(rng, cfg, [(4, 2), (7, 5)]))
        # second wave reuses the same state object — new lane compositions
        serve_engine(eng, _mk_reqs(rng, cfg, [(3, 1), (8, 3), (5, 4)]),
                     state=state)
        assert eng.trace_counts == {"prefill": 1, "generate": 1,
                                    "extract": 1, "insert": 1}, \
            eng.trace_counts


def _lane_bytes(cache, lane):
    """Concatenated raw bytes of one batch lane across every cache leaf
    (scan leaves carry batch on axis 1, tail leaves on axis 0)."""
    parts = []
    for c in cache["scan"]:
        parts.extend(np.asarray(leaf[:, lane]).tobytes() for leaf in c)
    for c in cache["tail"]:
        parts.extend(np.asarray(leaf[lane]).tobytes() for leaf in c)
    return b"".join(parts)


class TestLaneBitIsolation:
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_insert_touches_only_target_lane(self, tiny, kv_bits):
        """engine.insert is a FULL-lane overwrite: landing a payload in
        lane 1 leaves lanes 0 and 2 BIT-IDENTICAL across every cache leaf
        — including after those lanes already hold live requests."""
        cfg, params = tiny
        eng = _engine(cfg, params, kv_bits=kv_bits, batch_slots=3)
        rng = np.random.RandomState(13)
        state = eng.init_state()
        # occupy lanes 0 and 2 first so isolation is tested against live
        # bytes, not just zero-init
        for slot, n in ((0, 5), (2, 7)):
            _, payload = eng.prefill(
                rng.randint(1, cfg.vocab_size, size=n).astype(np.int32))
            state = eng.insert(payload, slot, state)
        before = {i: _lane_bytes(state.cache, i) for i in (0, 2)}
        _, payload = eng.prefill(
            rng.randint(1, cfg.vocab_size, size=6).astype(np.int32))
        state = eng.insert(payload, 1, state)
        for i in (0, 2):
            assert _lane_bytes(state.cache, i) == before[i], \
                f"insert into lane 1 perturbed lane {i}"
        # and the overwrite really replaced lane 1: a second insert of a
        # DIFFERENT prompt changes lane 1's bytes
        mid = _lane_bytes(state.cache, 1)
        _, payload = eng.prefill(
            rng.randint(1, cfg.vocab_size, size=4).astype(np.int32))
        state = eng.insert(payload, 1, state)
        assert _lane_bytes(state.cache, 1) != mid
        for i in (0, 2):
            assert _lane_bytes(state.cache, i) == before[i]


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.parallel import make_dist
    from repro.runtime import Request, serve_engine
    from repro.runtime.engine import make_engine

    assert len(jax.devices()) == 2, jax.devices()
    cfg = get_config("gemma2-2b").reduced()
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key, stacked=True, dtype=jnp.float32)
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(2)
    dist = make_dist(mesh)
    SPEC = [(4, 2), (8, 6), (3, 1), (6, 4)]

    def mk_reqs(seed):
        rng = np.random.RandomState(seed)
        return [Request(rid=i,
                        prompt=rng.randint(1, cfg.vocab_size, size=n)
                        .astype(np.int32),
                        max_new_tokens=q)
                for i, (n, q) in enumerate(SPEC)]

    def run(p, d, ctx_factory=None):
        eng = make_engine(cfg, p, batch_slots=2, prompt_pad_len=8,
                          max_len=32, dtype=jnp.float32, dist=d,
                          ctx_factory=ctx_factory)
        reqs = mk_reqs(21)
        serve_engine(eng, reqs)
        return eng, [r.tokens_out for r in reqs]

    # 1) sharded == unsharded greedy tokens, f32
    eng_sh, toks_sh = run(params, dist)
    _, toks_un = run(params, None)
    assert toks_sh == toks_un, (toks_sh, toks_un)

    # 2) admit-mask broadcast: engine._put replicates host masks onto
    # EVERY mesh device (the insert/admit mask must be identical on all
    # shards or lanes diverge per-device)
    mask = np.array([True, False])
    put = eng_sh._put(mask)
    assert put.sharding.is_fully_replicated, put.sharding
    assert len(put.sharding.device_set) == 2, put.sharding
    np.testing.assert_array_equal(np.asarray(put), mask)

    # 3) deploy-int8 path under the same mesh (packed integer payloads
    # ride the replicate-by-default sharding rule)
    from repro.core import Mode, QuantCtx, build_deploy, peg_policy
    from repro.core.pipeline import ptq
    pol = peg_policy(4)
    flat = tfm.init_params(cfg, key, stacked=False, dtype=jnp.float32)
    calib = [{"tokens": jax.random.randint(jax.random.PRNGKey(10),
                                           (2, 8), 0, cfg.vocab_size)}]

    def fwd(p, b, ctx):
        logits, _ = tfm.forward(cfg, p, b["tokens"], ctx=ctx)
        return logits

    qm = ptq(fwd, flat, calib, pol, collect_inputs=True)
    shared = {}
    for site, qp in qm.act_state.items():
        base = ("layer/" + site.split("/", 1)[1]
                if site.startswith("layer") else site)
        shared.setdefault(base, qp)
    packed, acts = build_deploy(cfg, params, pol, shared)

    def ctx_factory():
        return QuantCtx(policy=pol, mode=Mode.DEPLOY, act_state=shared,
                        deploy_acts=acts)

    _, dep_sh = run(packed, dist, ctx_factory)
    _, dep_un = run(packed, None, ctx_factory)
    assert dep_sh == dep_un, (dep_sh, dep_un)
    print("SHARDED ENGINE OK")
""")


@pytest.mark.slow
def test_sharded_decode_parity(tmp_path):
    """Engine on 2 simulated CPU devices (tensor-parallel mesh (1, 2) over
    ("data", "model")): sharded == unsharded greedy tokens for f32 AND the
    deploy-int8 path, and the admit-mask broadcast lands fully replicated.
    Subprocess because XLA_FLAGS must be set before jax import (same idiom
    as tests/test_distribution.py)."""
    script = tmp_path / "sharded_engine.py"
    script.write_text(SHARDED_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src") + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED ENGINE OK" in proc.stdout
