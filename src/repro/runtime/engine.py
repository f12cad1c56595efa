"""JetStream-style serving engine: every jitted device call of the serving
stack behind one explicit interface.

The continuous scheduler (runtime.serve_loop) used to call its jitted steps
directly; this module is the seam that separates *policy* (which requests to
admit or preempt — the Scheduler's job) from *mechanism* (the fixed-shape
device calls and decode-state transitions — the Engine's job). The engine
exposes two granularities over the same compiled steps:

* the **fused path** the continuous scheduler's hot loop drives —
  ``admit`` (slot-insert prefill: reset + prefill + insert in ONE model
  call), ``chunk`` (append-mode chunked prefill) and ``generate`` (one
  greedy decode step over every lane), plus the paged plumbing
  (``swap_out`` / ``swap_in`` / ``copy_block``);

* the **decomposed path** — ``prefill(request) -> (first_token,
  LanePayload)`` runs a request's prefill into a private scratch cache and
  extracts its lane as a transferable payload; ``insert(payload, slot,
  state)`` lands that payload in any decode slot (a full lane overwrite, so
  no separate reset and bit-isolation for every other lane);
  ``generate(state)`` then decodes as usual. This is the JetStream seam:
  prefill and decode need not share a cache — or, eventually, a host — and
  the async front-end (runtime.async_serve) and the decode microbenchmark
  (benchmarks/engine_bench.py) drive exactly this triad.

The fused ``admit`` and the decomposed ``prefill``+``insert`` are
semantically the same operation (the engine conformance suite asserts
greedy-token equality between a Scheduler run and a bare-engine run), and
each of prefill / insert / generate traces exactly once — shapes are fixed
(prompts pad to ``prompt_pad_len``, decode is always (B, 1)) and slots /
block ids are data.

**Mesh-aware serving**: pass ``dist`` (parallel.sharding.make_dist over a
mesh with a ``model`` axis) to :func:`make_engine` and the steps are built
with tensor-parallel sharding constraints threaded through every matmul,
parameters and cache are placed with the sharding rules, and every host
input (tokens, positions, the admission mask) is *broadcast* — replicated
across the mesh with an explicit all-device sharding — so a host-local
admission decision drives all N devices in lockstep. Works on simulated CPU
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) exactly as
on a real mesh.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class DecodeState(NamedTuple):
    """Fixed-shape per-slot decode state threaded through the jitted steps:
    one row per lane. ``pos`` == -1 marks an idle lane (its decode output is
    discarded and its cache writes are position-dropped). ``tokens`` and
    ``pos`` are host numpy arrays — the policy layer mutates them between
    device calls; only ``cache`` lives on device."""
    tokens: np.ndarray          # (B, 1) int32 current token per lane
    pos: np.ndarray             # (B, 1) int32 its absolute position (-1 idle)
    cache: Any                  # model cache pytree with B lanes


class LanePayload(NamedTuple):
    """The transferable result of a decomposed ``prefill``: one lane's
    complete KV payload (dense lane slices, or the gathered block rows of a
    paged lane) plus the host-side decode seed. ``insert`` lands ``kv`` in a
    slot and seeds the lane with (``first_token``, ``next_pos``)."""
    kv: Any                     # single-lane cache payload pytree
    first_token: int            # greedy token from the prefill's last logits
    next_pos: int               # len(prompt): the first decode write position


def _lane_rows(prompt: np.ndarray, width: int):
    """Left-pad one prompt into a (width,) row pair (tokens, positions) with
    real positions 0..len-1 and the -1 dead-cell sentinel on pads."""
    n = len(prompt)
    if n == 0:
        raise ValueError("empty prompt (an all-dead lane has no last-token "
                         "logits to decode from)")
    if n > width:
        raise ValueError(f"prompt length {n} exceeds the engine's "
                         f"prompt_pad_len {width}")
    toks = np.zeros((width,), np.int32)
    posm = np.full((width,), -1, np.int32)
    toks[width - n:] = prompt
    posm[width - n:] = np.arange(n)
    return toks, posm


class Engine:
    """Fixed-shape serving engine over jitted step functions.

    admit_fn: (tokens (B,P), positions (B,P), admit_mask (B,), cache)
              -> (last_logits (B,1,V) | (B,P,V), cache)
    decode_fn: (tokens (B,1), pos (B,1), cache) -> (logits (B,1,V), cache)
    chunk_fn:  (tokens (B,C), positions (B,C), reset_mask (B,), cache)
              -> (last_logits (B,1,V), cache)       [chunked prefill only]
    init_cache_fn: (batch,) -> model cache pytree

    Steps built with ``quant_telemetry=True`` return an extra telemetry
    dict; the engine folds it into ``telemetry_sink`` (when given) and
    hands back the plain outputs, so callers never see the arity change.
    With a ``tracer`` (runtime.telemetry.Tracer) each fused op records a
    ``dispatch`` span (host inputs placed, the jitted call returned) and a
    ``readback`` span (the greedy argmax and its device-to-host copy)
    inside the caller's span.

    Only greedy (argmax) decoding is implemented — the parity property
    "continuous == static == async == served alone, token for token" is
    only well-defined for deterministic sampling. Every op returns decoded
    tokens as HOST numpy (the conversion synchronizes on the device
    result), and the decomposed ops lazily build two engine-internal jits
    (payload extract / insert) that each trace exactly once.
    """

    def __init__(self, admit_fn: Callable, decode_fn: Callable,
                 init_cache_fn: Callable, *, batch_slots: int,
                 prompt_pad_len: Optional[int] = None,
                 max_len: Optional[int] = None,
                 chunk_fn: Optional[Callable] = None,
                 swap_out_fn: Optional[Callable] = None,
                 swap_in_fn: Optional[Callable] = None,
                 copy_block_fn: Optional[Callable] = None,
                 dist=None,
                 telemetry_sink: Optional[Callable[[Dict], None]] = None,
                 tracer=None):
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        self.admit_fn = admit_fn
        self.decode_fn = decode_fn
        self.chunk_fn = chunk_fn
        self.init_cache_fn = init_cache_fn
        self.swap_out_fn = swap_out_fn
        self.swap_in_fn = swap_in_fn
        self.copy_block_fn = copy_block_fn
        self.batch_slots = batch_slots
        self.prompt_pad_len = prompt_pad_len
        self.max_len = max_len
        self.dist = dist
        self.telemetry_sink = telemetry_sink
        self.tracer = tracer
        # trace-time counters: engine-internal jits bump these from inside
        # the traced python body, so a recompile is observable as a count
        # > 1 (make_engine extends this to the step functions themselves)
        self.trace_counts: Dict[str, int] = {}
        self._scratch = None            # decomposed-prefill scratch cache
        self._extract_jit = None
        self._insert_jit = None
        self._scratch_ids = None        # paged scratch: lane 0's block ids

    # -- host -> device placement ------------------------------------------

    def _put(self, x):
        """Host input placement. On a mesh this is the admit-mask broadcast:
        an explicit fully-replicated sharding, so the host-local admission
        decision reaches every device instead of relying on implicit
        single-device placement."""
        if self.dist is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            jnp.asarray(x), NamedSharding(self.dist.mesh, PartitionSpec()))

    def _unwrap(self, out):
        """Steps built with quant_telemetry=True return (logits, cache,
        telemetry_dict); fold the extra output into the sink and hand back
        the plain pair."""
        if len(out) == 3:
            logits, cache, tel = out
            if self.telemetry_sink is not None:
                self.telemetry_sink(tel)
            return logits, cache
        return out

    @staticmethod
    def _greedy(logits) -> np.ndarray:
        """(B, 1) int32 greedy tokens from the LAST position's logits —
        np conversion blocks on the device computation."""
        return np.asarray(jnp.argmax(logits[:, -1:], axis=-1), np.int32)

    def _run(self, fn, host_inputs, cache):
        """One fused op: place the host inputs, call ``fn`` and read the
        greedy tokens back. Returns ((B, 1) tokens, cache)."""
        if self.tracer is None:
            logits, cache = self._unwrap(
                fn(*[self._put(x) for x in host_inputs], cache))
            return self._greedy(logits), cache
        with self.tracer.span("dispatch"):
            logits, cache = self._unwrap(
                fn(*[self._put(x) for x in host_inputs], cache))
        with self.tracer.span("readback"):
            return self._greedy(logits), cache

    # -- state -------------------------------------------------------------

    def init_state(self) -> DecodeState:
        """A fresh all-idle decode state: every lane dead (pos -1)."""
        B = self.batch_slots
        return DecodeState(tokens=np.zeros((B, 1), np.int32),
                           pos=np.full((B, 1), -1, np.int32),
                           cache=self.init_cache_fn(B))

    # -- fused ops (the continuous Scheduler's hot loop) --------------------

    def admit(self, tokens, positions, admit_mask, cache):
        """Fused prefill+insert: reset the masked lanes and prefill their
        packed prompts in one model call. Returns ((B,1) greedy first
        tokens, cache) — semantically ``insert(prefill(r), slot)`` for every
        masked lane, in one step."""
        return self._run(self.admit_fn, (tokens, positions, admit_mask),
                         cache)

    def chunk(self, tokens, positions, reset_mask, cache):
        """One append-mode chunked-prefill step (see
        runtime.steps.make_chunk_prefill_step). Returns ((B,1) greedy
        tokens from the chunk's final position, cache)."""
        if self.chunk_fn is None:
            raise ValueError("engine was built without a chunk_fn")
        return self._run(self.chunk_fn, (tokens, positions, reset_mask),
                         cache)

    def generate(self, state: DecodeState):
        """One greedy decode step over every lane. Returns ((B,1) per-lane
        next tokens, cache); idle (pos -1) lanes produce garbage tokens the
        policy layer ignores, and their cache writes are position-dropped."""
        return self._run(self.decode_fn, (state.tokens, state.pos),
                         state.cache)

    # -- paged plumbing (over-commit preemption, prefix COW) ----------------

    def swap_out(self, cache, ids) -> Any:
        """Gather the payload of physical blocks ``ids`` into a HOST spill
        buffer (device_get included — preemption's swap-out half)."""
        if self.swap_out_fn is None:
            raise ValueError("engine was built without swap steps")
        return jax.device_get(self.swap_out_fn(cache, jnp.asarray(ids)))

    def swap_in(self, cache, ids, payload):
        """Re-upload a host spill payload into newly allocated blocks
        ``ids`` (resume's swap-in half) — bit-exact."""
        if self.swap_in_fn is None:
            raise ValueError("engine was built without swap steps")
        return self.swap_in_fn(cache, jnp.asarray(ids),
                               jax.device_put(payload))

    def copy_block(self, cache, src: int, dst: int):
        """Clone physical block ``src`` into ``dst`` across every paged
        arena (the device half of copy-on-write)."""
        if self.copy_block_fn is None:
            raise ValueError("engine was built without a copy_block_fn")
        return self.copy_block_fn(cache, jnp.asarray(src, jnp.int32),
                                  jnp.asarray(dst, jnp.int32))

    # -- decomposed path: prefill -> insert -> generate ---------------------

    def _is_paged(self, cache) -> bool:
        return isinstance(cache, dict) and "block_table" in cache

    def _ensure_scratch(self):
        """Lazily build the decomposed-prefill scratch cache: a private
        cache of the engine's own shape (so the ONE admit trace serves it
        too). Paged scratches identity-map lane 0 to blocks 0..nb-1 — the
        payload gather then reads a fixed id vector, one trace forever."""
        if self._scratch is not None:
            return
        scratch = self.init_cache_fn(self.batch_slots)
        if self._is_paged(scratch):
            table = np.array(scratch["block_table"])   # mutable host copy
            nb = table.shape[1]
            num_blocks = self._arena_blocks(scratch)
            if nb > num_blocks:
                raise ValueError(
                    f"decomposed prefill needs {nb} scratch blocks for one "
                    f"lane but the paged arena holds {num_blocks}")
            table[0] = np.arange(nb, dtype=np.int32)
            scratch["block_table"] = jnp.asarray(table)
            self._scratch_ids = np.arange(nb, dtype=np.int32)
        self._scratch = scratch

    @staticmethod
    def _arena_blocks(cache) -> int:
        from repro.models import transformer as tfm
        for node in tfm._cache_nodes(cache):
            pos = node.pos if hasattr(node, "pos") else None
            if pos is not None:
                return pos.shape[-2]
        raise ValueError("paged cache holds no attention arenas")

    def _ensure_payload_jits(self, paged: bool):
        from repro.models import transformer as tfm
        if self._extract_jit is None:
            self.trace_counts.setdefault("extract", 0)
            if paged:
                ids = jnp.asarray(self._scratch_ids)

                def extract(cache):
                    self.trace_counts["extract"] += 1
                    return tfm.cache_gather_blocks(cache, ids)
            else:
                def extract(cache):
                    self.trace_counts["extract"] += 1
                    return tfm.cache_extract_lane(cache, 0)
            self._extract_jit = jax.jit(extract)
        if self._insert_jit is None:
            self.trace_counts.setdefault("insert", 0)
            if paged:
                def insert(cache, ids, payload):
                    self.trace_counts["insert"] += 1
                    return tfm.cache_scatter_blocks(cache, ids, payload)
            else:
                def insert(cache, lane, payload):
                    self.trace_counts["insert"] += 1
                    return tfm.cache_insert_lane(cache, lane, payload)
            self._insert_jit = jax.jit(insert, donate_argnums=(0,))

    def prefill(self, request) -> (int, LanePayload):
        """Prefill ONE request into the engine's private scratch cache and
        extract its lane as a transferable payload. ``request`` is a
        serve_loop.Request or a raw (T,) int32 prompt array. Returns
        (first_token, LanePayload) — the first token is already decoded
        from the prefill's last-position logits (the admit-path contract),
        so a quota-1 request never needs a decode step.

        Reuses the engine's ONE admit trace (the scratch cache has the
        live cache's exact structure); the payload extract is an
        engine-internal jit that also traces exactly once."""
        prompt = np.asarray(getattr(request, "prompt", request), np.int32)
        width = self.prompt_pad_len or len(prompt)
        row_t, row_p = _lane_rows(prompt, width)
        B = self.batch_slots
        toks = np.zeros((B, width), np.int32)
        posm = np.full((B, width), -1, np.int32)
        toks[0], posm[0] = row_t, row_p
        mask = np.zeros((B,), bool)
        mask[0] = True
        self._ensure_scratch()
        first, self._scratch = self.admit(toks, posm, mask, self._scratch)
        self._ensure_payload_jits(self._is_paged(self._scratch))
        kv = self._extract_jit(self._scratch)
        tok = int(first[0, 0])
        return tok, LanePayload(kv=kv, first_token=tok,
                                next_pos=len(prompt))

    def insert(self, payload: LanePayload, slot: int,
               state: DecodeState) -> DecodeState:
        """Land a prefilled lane payload in decode slot ``slot``: a FULL
        lane overwrite (prompt KV plus dead-cell padding), so the slot's
        previous occupant needs no separate reset and every other lane's
        bytes pass through bit-identical. Seeds the lane's host decode row
        with (first_token, next_pos). Paged decode caches route the write
        through the slot's block-table row, which must be fully mapped
        (the bare engine serves paged caches with the identity-mapped
        drop-in dense layout — pool-managed admission uses the fused
        ``admit`` instead)."""
        if not 0 <= slot < self.batch_slots:
            raise ValueError(f"slot {slot} outside 0..{self.batch_slots - 1}")
        cache = state.cache
        paged = self._is_paged(cache)
        self._ensure_payload_jits(paged)
        if paged:
            row = np.asarray(cache["block_table"])[slot]
            if (row < 0).any():
                raise ValueError(
                    f"slot {slot}'s block-table row is not fully mapped — "
                    "decomposed insert needs the identity-mapped paged "
                    "layout (init_cache(paged=True) default)")
            cache = self._insert_jit(cache, jnp.asarray(row), payload.kv)
        else:
            cache = self._insert_jit(cache, jnp.asarray(slot, jnp.int32),
                                     payload.kv)
        tokens, pos = state.tokens.copy(), state.pos.copy()
        tokens[slot, 0] = payload.first_token
        pos[slot, 0] = payload.next_pos
        return DecodeState(tokens, pos, cache)

    def release(self, slot: int, state: DecodeState) -> DecodeState:
        """Host-side lane retirement: mark ``slot`` idle (pos -1). The
        cache lane's stale bytes are unreadable behind the dead-cell
        sentinel and the next ``insert`` fully overwrites them, so no
        device call is needed — cancellation mid-generation is free."""
        tokens, pos = state.tokens.copy(), state.pos.copy()
        pos[slot, 0] = -1
        return DecodeState(tokens, pos, state.cache)


def make_engine(cfg, params, *, batch_slots: int, prompt_pad_len: int,
                max_len: int, dtype=jnp.float32, kv_bits: int = 16,
                paged: bool = False, block_size: int = 16,
                ctx_factory: Optional[Callable] = None,
                chunked=None, dist=None, quant_telemetry: bool = False,
                telemetry_sink: Optional[Callable] = None,
                with_chunk_fn: bool = False) -> Engine:
    """Build a ready-to-serve :class:`Engine` for a model config: jitted
    admit/decode (and optionally chunk) steps with the cache donated, params
    bound, and — when ``dist`` is given — parameters and caches placed with
    the tensor-parallel sharding rules (parallel.sharding) so decode runs
    under ``jax.sharding`` across the mesh while admission stays host-local.

    Every step is wrapped with a trace-time counter
    (``engine.trace_counts``): the conformance suite's recompile guard
    asserts each of prefill/insert/generate traced exactly once. Paged
    engines use the identity-mapped drop-in dense layout (the decomposed
    insert's contract)."""
    from repro.models import transformer as tfm
    from repro.runtime.steps import (make_admit_step, make_chunk_prefill_step,
                                     make_decode_step)

    if dist is not None:
        from repro.parallel.sharding import (make_cache_shardings,
                                             make_param_shardings)
        params = jax.tree.map(jax.device_put, params,
                              make_param_shardings(params, dist))

    counts: Dict[str, int] = {}

    def counted(name, fn):
        counts.setdefault(name, 0)

        def wrapper(*args):
            counts[name] += 1
            return fn(*args)
        return wrapper

    admit = jax.jit(counted("prefill", make_admit_step(
        cfg, dist=dist, ctx_factory=ctx_factory, chunked=chunked,
        quant_telemetry=quant_telemetry)), donate_argnums=(4,))
    decode = jax.jit(counted("generate", make_decode_step(
        cfg, dist=dist, ctx_factory=ctx_factory,
        quant_telemetry=quant_telemetry)), donate_argnums=(3,))
    chunk = None
    if with_chunk_fn:
        chunk = jax.jit(counted("chunk", make_chunk_prefill_step(
            cfg, dist=dist, ctx_factory=ctx_factory, chunked=chunked,
            quant_telemetry=quant_telemetry)), donate_argnums=(4,))

    def init_cache_fn(batch):
        cache = tfm.init_cache(cfg, batch, max_len, dtype=dtype,
                               kv_bits=kv_bits, paged=paged,
                               block_size=block_size)
        if dist is not None:
            from repro.parallel.sharding import make_cache_shardings
            cache = jax.tree.map(jax.device_put, cache,
                                 make_cache_shardings(cache, dist))
        return cache

    engine = Engine(
        lambda t, pm, m, c: admit(params, t, pm, m, c),
        lambda t, p, c: decode(params, t, p, c),
        init_cache_fn, batch_slots=batch_slots,
        prompt_pad_len=prompt_pad_len, max_len=max_len,
        chunk_fn=(None if chunk is None else
                  lambda t, pm, m, c: chunk(params, t, pm, m, c)),
        dist=dist, telemetry_sink=telemetry_sink)
    engine.trace_counts = counts
    return engine


def serve_engine(engine: Engine, requests: List[Any],
                 state: Optional[DecodeState] = None) -> DecodeState:
    """Reference FIFO driver over the decomposed triad — the engine
    conformance suite's 'bare engine' side, and the simplest possible
    serving loop: fill free slots with prefill+insert, run generate until
    every request drained. Appends tokens to each request's ``tokens_out``
    (greedy, identical to the Scheduler's emissions for the same
    requests). Requests with ``max_new_tokens <= 0`` retire untouched."""
    B = engine.batch_slots
    if state is None:
        state = engine.init_state()
    queue = [r for r in requests if r.max_new_tokens > 0]
    for r in requests:
        if r.max_new_tokens <= 0:
            r.done = True
    lanes: List[Optional[Any]] = [None] * B
    while queue or any(r is not None for r in lanes):
        for slot in range(B):
            if lanes[slot] is not None or not queue:
                continue
            r = queue.pop(0)
            first, payload = engine.prefill(r)
            state = engine.insert(payload, slot, state)
            r.tokens_out.append(first)
            if len(r.tokens_out) >= r.max_new_tokens:
                r.done = True
                state = engine.release(slot, state)
            else:
                lanes[slot] = r
        if not any(r is not None for r in lanes):
            continue
        toks, cache = engine.generate(state)
        tokens, pos = state.tokens.copy(), state.pos.copy()
        for slot in range(B):
            r = lanes[slot]
            if r is None:
                continue
            tokens[slot, 0] = toks[slot, 0]
            pos[slot, 0] += 1
            r.tokens_out.append(int(toks[slot, 0]))
            if len(r.tokens_out) >= r.max_new_tokens:
                r.done = True
                lanes[slot] = None
                pos[slot, 0] = -1
        state = DecodeState(tokens, pos, cache)
    return state
