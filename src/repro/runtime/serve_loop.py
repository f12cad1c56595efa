"""Batched serving loops over jitted prefill / decode / admit steps.

Policy / mechanism split: every jitted model call the continuous
``Scheduler`` makes — fused admit, chunk prefill, batched decode, block
swap in/out, copy-on-write block copy — goes through a
:class:`repro.runtime.engine.Engine` it builds internally. The Scheduler
is a pure POLICY layer: it decides which requests to admit, preempt or
retire and bookkeeps lanes, block tables and stats; the Engine owns the
MECHANISM (device dispatch, greedy readback, telemetry unwrapping,
mesh-aware input placement). The Engine is also usable standalone through
its decomposed prefill/insert/generate triad — see runtime/engine.py.

Two schedulers share the Request / ServeStats bookkeeping:

* ``serve_batch`` — STATIC group batching. Requests are packed into groups
  of up to ``batch_slots`` (prompts left-padded to the group max), each
  group is prefilled once and then decoded in lockstep until every request
  in the group hits its quota. A lane whose request finished early idles
  (still pays for decode steps) until the group's slowest request is done;
  the next group only starts after that. Simple, but measured tokens/s
  collapses when ``max_new_tokens`` is skewed across requests.

* ``Scheduler`` / ``serve_continuous`` — CONTINUOUS batching. A fixed pool
  of ``batch_slots`` decode lanes, each carrying its own request, position
  and KV-cache lane. Finished requests retire immediately and queued
  requests are admitted into the freed lanes mid-flight via a slot-insert
  prefill (runtime.steps.make_admit_step) that writes one request's cache
  lane while every other lane passes through bit-identical. All shapes are
  fixed (prompts pad to ``prompt_pad_len``, decode is always (B, 1)), so
  the jitted steps never recompile across admissions.

  With ``prefill_chunk=N`` (chunked prefill) admission becomes host-side
  bookkeeping only: an admitted lane enters a PREFILLING state and its
  prompt is appended chunk by chunk — at most N tokens per model call
  (runtime.steps.make_chunk_prefill_step) — interleaved 1:1 with the
  resident lanes' decode steps, so one long prompt never stalls resident
  decoding for a whole monolithic prefill. A lane becomes decodable only
  after its last chunk, whose final-position logits emit its first token
  (the admit-path contract), and the emitted tokens are identical to the
  unchunked schedulers'.

  With ``over_commit=True`` (paged + chunked only) the worst-case block
  reservations are dropped: admission claims only the actual prefix +
  first-chunk need, the queue becomes priority-aware ((-priority, seq) —
  FIFO within a tier, no head-of-line blocking), and when growth runs the
  pool dry a victim lane (lowest priority, then youngest) is PREEMPTED —
  its blocks either swap to a host-memory spill buffer (re-uploaded on
  resume) or are dropped and recomputed through chunked re-admission
  (radix hits make the recompute O(novel suffix)). Emitted tokens are
  identical either way: a preempted lane's cache holds exactly the first
  ``written`` tokens of prompt + generated-so-far, so re-prefilling that
  sequence reproduces the greedy continuation.

Position sentinel contract (models/attention.py): position -1 marks a dead
cell — a pad token inside a left-packed prompt or an idle decode lane. Dead
cells are masked out of attention and their KV-cache writes are dropped,
which is what makes the slot-insert prefill and the masked decode step
lane-safe. Both schedulers therefore pack prompts with per-request real
positions 0..len-1 (pads -1), so a short prompt packed next to longer ones
decodes exactly as if it were served alone.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attend_decode import pages_per_block, walk_blocks
from repro.runtime.block_pool import BlockPool, blocks_for_tokens
from repro.runtime.engine import DecodeState, Engine
from repro.runtime.radix_cache import RadixCache
from repro.runtime.telemetry import ServeTelemetry

# what a span site enters when tracing is off (reusable: it keeps no state)
_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (T,) int32
    max_new_tokens: int = 16
    # admission tier: larger = more important. The over-commit scheduler
    # admits in (-priority, arrival) order and preempts lowest-tier lanes
    # first; the FIFO schedulers ignore it.
    priority: int = 0
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class RequestLatency:
    """Per-request latency in model-call steps (every prefill/admit or
    decode call increments the global step counter by one — a wall-clock-
    free proxy). ``enqueue_step`` is recorded when the request enters the
    scheduler's queue, so first-token latency measured from it INCLUDES
    queueing delay; ``queue_wait_steps`` isolates the queued portion
    (summed across re-queues when the request was preempted)."""
    enqueue_step: int = 0       # step count when the request was queued
    admit_step: int = -1        # step count at (last) admission (-1: never)
    first_token_step: int = -1  # step whose output produced token 1
    finish_step: int = -1       # step whose output produced the last token
    queue_wait_steps: int = 0   # total steps spent queued before admission


@dataclasses.dataclass
class TierLatency:
    """Per-priority-tier latency percentiles, in model-call steps.

    First-token latency is measured from ``enqueue_step`` (queueing delay
    included — the whole point of the tier split); inter-token latency is
    the mean step gap between a request's consecutive tokens, defined only
    for requests that emitted >= 2 tokens."""
    requests: int = 0
    first_token_p50: float = 0.0
    first_token_p99: float = 0.0
    inter_token_p50: float = 0.0
    inter_token_p99: float = 0.0


@dataclasses.dataclass
class ServeStats:
    prefill_calls: int = 0
    # chunked prefill only: number of chunk-step model calls (each also
    # counts as a prefill_call); 0 when serving unchunked
    chunk_steps: int = 0
    decode_steps: int = 0
    tokens_generated: int = 0
    wall_s: float = 0.0
    # PEAK live KV-cache bytes across the run. Dense lanes: the whole cache
    # pytree (every lane owns max_len slots). Paged serving: ALLOCATED
    # block bytes only (blocks_in_use x per-block bytes across layers) —
    # bytes scale with live tokens, which is the paged win this stat makes
    # visible.
    cache_bytes: int = 0
    tokens_per_s: float = 0.0
    # fraction of (decode step x slot) cells occupied by a live request;
    # denominator uses batch_slots so half-empty tail groups count as idle
    slot_utilization: float = 0.0
    # paged-pool gauges (0 for dense serving): peak mapped blocks, and the
    # fraction of allocated token cells not holding a live token at that
    # peak (internal fragmentation of the block_size granularity)
    blocks_in_use: int = 0
    block_fragmentation: float = 0.0
    # prefix-sharing gauges (0 unless a RadixCache is attached): total
    # prompt tokens found in the radix cache at admission (longest cached
    # match, before the >=1-token-suffix cap), prompt tokens whose prefill
    # was actually skipped (block-aligned, capped), peak count of physical
    # blocks mapped by a lane AND retained in the radix cache, and
    # hit-tokens / admitted prompt tokens
    prefix_hit_tokens: int = 0
    prefill_tokens_saved: int = 0
    shared_blocks: int = 0
    prefix_hit_rate: float = 0.0
    # over-commit gauges (0 unless over_commit=True): lane preemptions,
    # blocks spilled to the host swap buffer, and tokens re-prefilled by
    # drop-mode resume (already-computed positions recomputed)
    preemptions: int = 0
    swapped_blocks: int = 0
    recomputed_tokens: int = 0
    # total steps requests spent queued before admission, summed over all
    # requests (per-request values live in request_latency)
    queue_wait_steps: int = 0
    request_latency: Dict[int, RequestLatency] = \
        dataclasses.field(default_factory=dict)
    # priority tier -> latency percentiles (always at least tier 0 when any
    # request produced a token)
    tier_latency: Dict[int, TierLatency] = \
        dataclasses.field(default_factory=dict)
    # ``serve.py --verify``: the replayed cached logits (n, N, V), a
    # function of the seed and the model only (not part of to_json)
    replayed_logits: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    def to_json(self) -> Dict[str, Any]:
        """JSON-serializable dict of every field but ``replayed_logits``
        (nested RequestLatency / TierLatency dataclasses included) — the
        machine-readable form behind ``serve.py --stats-json`` and the
        serving bench rows."""
        out = dataclasses.asdict(
            dataclasses.replace(self, replayed_logits=None))
        del out["replayed_logits"]
        return out


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "dtype"))


def _paged_block_bytes(cache) -> int:
    """Per-physical-block bytes of a paged model cache (0 for anything
    else, e.g. the stub caches the scheduler tests drive)."""
    if not isinstance(cache, dict):
        return 0
    from repro.models.transformer import paged_block_bytes
    return paged_block_bytes(cache)


def _check_capacity(requests: List[Request], max_len: Optional[int],
                    pool: Optional[BlockPool] = None,
                    ring_tokens: Optional[int] = None) -> None:
    """Reject requests whose decode would write past a ``max_len``-slot
    cache segment (the final token is emitted without a write, so the last
    write lands at position len(prompt) + quota - 2). Writes past the
    segment are scatter-dropped by design (dead-cell contract), which would
    silently truncate the attended context — an error beats degraded
    output. ``max_len`` None (capacity unknown to the caller) skips the
    check; sliding-window ring caches wrap and never overflow.

    With a paged ``pool``, the same up-front rule extends to pool capacity:
    a request whose worst case exceeds ``num_blocks`` (or the per-lane
    block-table width) could never be admitted — backpressure would queue
    it forever — so it raises here instead. ``ring_tokens`` (models whose
    EVERY attention layer is a sliding-window ring — see
    models.transformer.paged_ring_tokens) caps the pool-side need: a ring
    lane never holds more than ``ceil(ring_tokens / block_size)`` blocks
    however long it decodes, so window layers stop inflating reservations.
    """
    if max_len is None and pool is None:
        return
    for r in requests:
        if r.max_new_tokens <= 0:
            continue                # zero-quota: never occupies a lane
        need = len(r.prompt) + r.max_new_tokens - 1
        if max_len is not None and need > max_len:
            raise ValueError(
                f"request {r.rid}: prompt ({len(r.prompt)}) + "
                f"max_new_tokens ({r.max_new_tokens}) needs {need} cache "
                f"slots but the cache holds max_len={max_len}; later KV "
                "writes would be silently dropped")
        if pool is not None:
            if ring_tokens is not None:
                need = min(need, ring_tokens)
            nb = blocks_for_tokens(need, pool.block_size)
            lane_cap = pool.max_blocks_per_lane * pool.block_size
            if nb > pool.num_blocks or need > lane_cap:
                raise ValueError(
                    f"request {r.rid}: prompt ({len(r.prompt)}) + "
                    f"max_new_tokens ({r.max_new_tokens}) needs {nb} cache "
                    f"blocks but the pool holds num_blocks="
                    f"{pool.num_blocks} (lane capacity {lane_cap} cells); "
                    "later KV writes would be silently dropped")


def _require_nonempty_prompt(r: Request) -> None:
    """Shared by the monolithic and chunked admission paths so the
    dead-lane/logits contract cannot drift between them."""
    if len(r.prompt) == 0:
        raise ValueError(f"request {r.rid}: empty prompt (an all-dead "
                         f"lane has no last-token logits to decode from)")


def _pack_prompts(group: List[Request], T: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Left-pad prompts to length T. Returns (tokens (B,T), positions (B,T))
    with real positions 0..len-1 and the -1 dead-cell sentinel on pads."""
    toks = np.zeros((len(group), T), np.int32)
    posm = np.full((len(group), T), -1, np.int32)
    for i, r in enumerate(group):
        n = len(r.prompt)
        _require_nonempty_prompt(r)
        if n > T:
            raise ValueError(f"request {r.rid}: prompt length {n} exceeds "
                             f"the packing length {T}")
        toks[i, T - n:] = r.prompt
        posm[i, T - n:] = np.arange(n)
    return toks, posm


class _Book:
    """Shared emission / latency / utilization bookkeeping."""

    def __init__(self, stats: ServeStats, batch_slots: int):
        self.stats = stats
        self.slots = batch_slots
        self.step = 0               # global model-call counter
        self.cells = 0
        self.active_cells = 0
        self.prompt_tokens = 0      # admitted prompt tokens (hit-rate denom)
        self.priority: Dict[int, int] = {}   # rid -> tier, for finalize
        self.emitted: Dict[int, int] = {}    # rid -> tokens emitted
        self._enq_step: Dict[int, int] = {}  # rid -> last (re)enqueue step

    def enqueue(self, r: Request) -> None:
        """Record queue entry: creates the request's latency record at the
        CURRENT step so first-token latency includes queueing delay."""
        self.stats.request_latency[r.rid] = RequestLatency(
            enqueue_step=self.step)
        self.priority[r.rid] = r.priority
        self._enq_step[r.rid] = self.step

    def requeue(self, r: Request) -> None:
        """A preempted request re-enters the queue: its renewed wait counts
        toward queue_wait_steps, but enqueue_step keeps the original entry
        step (first-token latency is measured from FIRST arrival)."""
        self._enq_step[r.rid] = self.step

    def admit(self, r: Request) -> None:
        lat = self.stats.request_latency.get(r.rid)
        if lat is None:             # defensive: enqueue() not seen
            lat = RequestLatency(enqueue_step=self.step)
            self.stats.request_latency[r.rid] = lat
            self.priority[r.rid] = r.priority
            self._enq_step[r.rid] = self.step
        wait = self.step - self._enq_step[r.rid]
        lat.queue_wait_steps += wait
        self.stats.queue_wait_steps += wait
        lat.admit_step = self.step

    def emit(self, r: Request, tok: int) -> None:
        r.tokens_out.append(int(tok))
        self.stats.tokens_generated += 1
        self.emitted[r.rid] = self.emitted.get(r.rid, 0) + 1
        lat = self.stats.request_latency.get(r.rid)
        if lat is None:             # defensive: caller skipped enqueue/admit
            lat = RequestLatency(enqueue_step=self.step)
            self.stats.request_latency[r.rid] = lat
            self.priority[r.rid] = r.priority
        if lat.first_token_step < 0:
            lat.first_token_step = self.step
        lat.finish_step = self.step
        if len(r.tokens_out) >= r.max_new_tokens:
            r.done = True

    def track_cache(self, cache) -> None:
        self.stats.cache_bytes = max(self.stats.cache_bytes,
                                     _tree_bytes(cache))

    def track_pool(self, pool: BlockPool, live_tokens: int,
                   block_bytes: int) -> None:
        """Paged serving: peak ALLOCATED bytes + pool gauges (fragmentation
        is sampled at the FIRST blocks_in_use peak — a strict > comparison,
        so a later equal-height peak cannot silently overwrite the first
        sample's fragmentation)."""
        s = self.stats
        s.cache_bytes = max(s.cache_bytes, pool.blocks_in_use * block_bytes)
        if pool.blocks_in_use > s.blocks_in_use:
            s.blocks_in_use = pool.blocks_in_use
            s.block_fragmentation = pool.fragmentation(live_tokens)
        s.shared_blocks = max(s.shared_blocks, pool.shared_blocks)

    def count_decode(self, n_active: int) -> None:
        self.stats.decode_steps += 1
        self.cells += self.slots
        self.active_cells += n_active

    def finalize(self, t_start: float) -> ServeStats:
        s = self.stats
        s.wall_s = time.perf_counter() - t_start
        s.tokens_per_s = s.tokens_generated / max(s.wall_s, 1e-9)
        s.slot_utilization = (self.active_cells / self.cells
                              if self.cells else 0.0)
        s.prefix_hit_rate = (s.prefix_hit_tokens / self.prompt_tokens
                             if self.prompt_tokens else 0.0)
        # per-tier percentiles over requests that produced a first token
        # (zero-quota requests keep their latency entry but are skipped)
        by_tier: Dict[int, List[Tuple[int, RequestLatency]]] = {}
        for rid, lat in s.request_latency.items():
            if lat.first_token_step < 0:
                continue
            by_tier.setdefault(self.priority.get(rid, 0), []).append(
                (rid, lat))
        for tier, entries in sorted(by_tier.items()):
            first = [lat.first_token_step - lat.enqueue_step
                     for _, lat in entries]
            inter = [(lat.finish_step - lat.first_token_step)
                     / (self.emitted[rid] - 1)
                     for rid, lat in entries if self.emitted.get(rid, 0) >= 2]
            s.tier_latency[tier] = TierLatency(
                requests=len(entries),
                first_token_p50=float(np.percentile(first, 50)),
                first_token_p99=float(np.percentile(first, 99)),
                inter_token_p50=(float(np.percentile(inter, 50))
                                 if inter else 0.0),
                inter_token_p99=(float(np.percentile(inter, 99))
                                 if inter else 0.0))
        return s


# ---------------------------------------------------------------------------
# Static group batching (legacy mode, kept for comparison + compatibility)
# ---------------------------------------------------------------------------

def serve_batch(prefill_fn: Callable, decode_fn: Callable, init_cache_fn,
                requests: List[Request], *, batch_slots: int,
                max_len: Optional[int] = None) -> ServeStats:
    """Static-batch serving: pack up to ``batch_slots`` requests (prompts
    left-padded to the group max, pads carrying the -1 position sentinel),
    prefill once, then decode the group in lockstep until every request has
    produced its max_new_tokens. Freed lanes idle until the group drains.
    Decoding is greedy (argmax), as in :class:`Scheduler`.

    prefill_fn: (tokens (B,T), positions (B,T), cache) -> (logits, cache)
    decode_fn:  (tokens (B,1), pos (B,1), cache) -> (logits, cache)
    """
    _check_capacity(requests, max_len)
    stats = ServeStats()
    book = _Book(stats, batch_slots)
    t_start = time.perf_counter()
    # zero-quota requests retire without consuming a group slot (as in the
    # continuous scheduler) — filtered before slicing AND before packing,
    # so an empty prompt on a zero-quota request is not an error either
    for r in requests:
        if r.max_new_tokens <= 0:
            r.done = True
    live = [r for r in requests if r.max_new_tokens > 0]
    for r in live:
        book.enqueue(r)
    for lo in range(0, len(live), batch_slots):
        group = live[lo:lo + batch_slots]
        T = max(len(r.prompt) for r in group)
        toks, posm = _pack_prompts(group, T)
        cache = init_cache_fn(len(group))
        book.track_cache(cache)
        for r in group:
            book.admit(r)
        logits, cache = prefill_fn(jnp.asarray(toks), jnp.asarray(posm),
                                   cache)
        stats.prefill_calls += 1
        book.step += 1
        book.track_cache(cache)
        # each lane decodes at ITS next position (prompt length), not the
        # padded group length — pads are dead cells, not context
        pos = np.array([[len(r.prompt)] for r in group], np.int32)
        cur = np.asarray(jnp.argmax(logits[:, -1:], axis=-1), np.int32)
        steps = max((r.max_new_tokens for r in group), default=0)
        for _ in range(steps):
            for i, r in enumerate(group):
                if not r.done:
                    book.emit(r, cur[i, 0])
            # check BEFORE decoding: once every request hit its quota the
            # group must not pay for (or emit tokens from) another step
            if all(r.done for r in group):
                break
            n_active = sum(not r.done for r in group)
            logits, cache = decode_fn(jnp.asarray(cur), jnp.asarray(pos),
                                      cache)
            book.count_decode(n_active)
            book.step += 1
            book.track_cache(cache)
            cur = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            pos = pos + 1
    return book.finalize(t_start)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Swapped:
    """Swap-mode preemption residue: the lane's block payload lives in a
    host-memory spill buffer until re-admission re-uploads it. Bit-exact
    resume — no token is ever recomputed."""
    payload: Any                # host pytree from swap_out_fn (n_blocks live)
    n_blocks: int               # live blocks at preemption (prefix of ids)
    prompt: np.ndarray          # the lane's working prompt at preemption
    pref_off: Optional[int]     # PREFILLING offset, or None if decodable
    token: int                  # pending decode token (decodable lanes)
    pos: int                    # its write position (decodable lanes)


@dataclasses.dataclass
class _Dropped:
    """Drop-mode preemption residue: the blocks were freed (prompt blocks
    donated to the radix cache when attached) and resume re-prefills
    prompt + tokens-emitted-so-far through the chunk path. Radix hits make
    the recompute O(novel suffix); the re-prefill reproduces the identical
    greedy continuation because the cache held exactly those tokens."""
    written: int                # cache positions held at preemption


@dataclasses.dataclass(eq=False)      # identity compare: queue.remove(entry)
class _QEntry:
    """Admission-queue entry. ``seq`` is the arrival number — the FIFO key
    within a priority tier, kept across preemptions so a re-queued request
    does not lose its place to later arrivals of the same tier."""
    req: Request
    seq: int
    resume: Optional[Any] = None    # _Swapped | _Dropped | None (fresh)


class Scheduler:
    """Slot-scheduled continuous batching over a fixed pool of decode lanes.

    Admission policy: FIFO and greedy — before every decode step, if at
    least one lane is free and the queue is non-empty, ALL free lanes are
    (re)filled in one slot-insert prefill call. Prompts are left-padded to
    the fixed ``prompt_pad_len`` and non-admitted lanes carry all -1
    positions, so one jitted admit step serves every admission without
    recompiling and without perturbing the resident lanes' caches.

    admit_fn: (tokens (B,P), positions (B,P), admit_mask (B,), cache)
              -> (last_logits (B,1,V) | (B,P,V), cache)
    decode_fn: (tokens (B,1), pos (B,1), cache) -> (logits (B,1,V), cache)
    chunk_fn:  (tokens (B,C), positions (B,C), reset_mask (B,), cache)
              -> (last_logits (B,1,V), cache)       [chunked prefill only]
    init_cache_fn: (batch,) -> model cache pytree

    Only greedy (argmax) decoding is implemented — the parity property
    "continuous == static == served alone, token for token" is only
    well-defined for deterministic sampling.

    **Chunked prefill** (``prefill_chunk=N`` + ``chunk_fn``): a lane's
    lifecycle gains a PREFILLING state between admission and decode.
    Admission marks the lane PREFILLING at prompt offset 0 (FIFO, greedy,
    and — when paged — with the same worst-case reservation, but mapping
    only the first chunk's blocks); every loop iteration then issues ONE
    chunk step advancing ALL prefilling lanes by up to N prompt tokens,
    followed by one decode step for the decodable lanes — a 1:1
    interleave, so resident lanes keep emitting between chunks. The lane
    becomes decodable after its last chunk (first token emitted from that
    chunk's logits). Prefilling lanes are dead (pos -1) in the decode
    step and count as idle in slot_utilization.

    **Paged mode** (``block_pool`` given): the scheduler owns a
    :class:`~repro.runtime.block_pool.BlockPool` whose block table rides
    inside the cache pytree (``cache["block_table"]``). Admission reserves
    a request's worst-case block count and maps its prompt blocks (a
    request whose reservation does not fit WAITS at the head of the queue
    — FIFO backpressure the dense path never needed); decode grows a
    lane's mapped prefix as its position crosses block boundaries (growth
    draws from the reservation, so it cannot fail mid-flight); retirement
    returns every block to the free list. All of it is host-side table
    bookkeeping between jitted calls — shapes never change, the steps
    still trace once.

    **Prefix sharing** (``radix_cache`` given; needs paged mode AND a
    ``chunk_fn``): admission matches the prompt against a
    :class:`~repro.runtime.radix_cache.RadixCache`, maps the longest
    block-aligned cached prefix read-only into the lane's table
    (``BlockPool.map_shared``) and prefills ONLY the novel suffix through
    the append-mode chunk path — the lane enters PREFILLING at offset
    K_aligned instead of 0, so the chunk step's reset_mask stays False and
    the shared blocks are never clobbered. Reservations count the novel
    suffix + decode growth only (plus a copy-on-write allowance when the
    request can wrap a ring-window layer back into its shared prefix);
    retirement donates the lane's full prompt blocks into the tree instead
    of freeing them — unless the lane ever wrapped a ring layer, which
    would leave stale generation data in prompt cells. ``write_caps``
    (models.transformer.attn_write_caps) lists the distinct token
    capacities at which the model's attention layers wrap their paged
    write index; ``copy_block_fn(cache, src, dst) -> cache`` (a jitted
    models.transformer.cache_copy_block) services copy-on-write when a
    wrapping write would land in a shared block. ``ring_tokens``
    (models.transformer.paged_ring_tokens, all-window models only) caps
    per-lane reservations and growth at the ring size.

    **Over-commit + preemption** (``over_commit=True``; needs paged mode
    AND a ``chunk_fn``): admission stops reserving the worst case and
    claims only the actual prefix + first-chunk blocks; growth extends the
    reservation on demand (``BlockPool.try_grow``). The queue becomes
    priority-aware — snapshot-sorted by ``(-priority, seq)``, so a starved
    head no longer blocks lower-demand requests behind it — and when the
    pool runs dry a victim lane (lowest priority, then youngest; admission
    only ever preempts a STRICTLY lower tier) is PREEMPTED: with
    ``swap_out_fn``/``swap_in_fn`` (runtime.steps.make_swap_steps) its
    blocks spill to a host buffer and re-upload bit-exact on resume,
    otherwise its blocks are dropped (prompt blocks donated to the radix
    cache when attached) and resume re-prefills prompt + emitted tokens
    through the chunk path — token-for-token identical either way.
    ``decode_ratio=N`` holds decode cadence under prefill pressure: N
    decode steps run per chunk step once lanes are decodable (1 = the
    classic 1:1 interleave).
    """

    def __init__(self, admit_fn: Callable, decode_fn: Callable,
                 init_cache_fn: Callable, *, batch_slots: int,
                 prompt_pad_len: Optional[int] = None,
                 max_len: Optional[int] = None,
                 block_pool: Optional[BlockPool] = None,
                 chunk_fn: Optional[Callable] = None,
                 prefill_chunk: Optional[int] = None,
                 radix_cache: Optional[RadixCache] = None,
                 write_caps: Optional[List[int]] = None,
                 ring_tokens: Optional[int] = None,
                 copy_block_fn: Optional[Callable] = None,
                 over_commit: bool = False,
                 swap_out_fn: Optional[Callable] = None,
                 swap_in_fn: Optional[Callable] = None,
                 decode_ratio: int = 1,
                 telemetry: Optional[ServeTelemetry] = None):
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        if block_pool is not None and block_pool.batch_slots != batch_slots:
            raise ValueError(
                f"block_pool is sized for {block_pool.batch_slots} lanes, "
                f"scheduler has batch_slots={batch_slots}")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if chunk_fn is None:
                raise ValueError("prefill_chunk requires a chunk_fn "
                                 "(runtime.steps.make_chunk_prefill_step)")
        if (write_caps is not None or ring_tokens is not None) \
                and block_pool is None:
            raise ValueError("write_caps / ring_tokens only apply to "
                             "paged serving (block_pool)")
        if radix_cache is not None:
            if block_pool is None:
                raise ValueError("radix_cache requires a block_pool "
                                 "(prefix sharing is a paged feature)")
            if chunk_fn is None:
                raise ValueError(
                    "radix_cache requires a chunk_fn: prefix-hit lanes "
                    "prefill their novel suffix through the append-mode "
                    "chunk path (the monolithic admit step would reset "
                    "the shared blocks)")
            if radix_cache.block_size != block_pool.block_size:
                raise ValueError(
                    f"radix_cache block_size {radix_cache.block_size} != "
                    f"pool block_size {block_pool.block_size}")
            block_pool.attach_cache(radix_cache)
        if over_commit:
            if block_pool is None:
                raise ValueError("over_commit requires a block_pool "
                                 "(preemption is a paged feature)")
            if chunk_fn is None:
                raise ValueError(
                    "over_commit requires a chunk_fn: optimistic admission "
                    "maps only the first chunk's blocks and drop-mode "
                    "resume re-prefills through the chunk path")
        if (swap_out_fn is None) != (swap_in_fn is None):
            raise ValueError("swap_out_fn and swap_in_fn come as a pair")
        if swap_out_fn is not None and not over_commit:
            raise ValueError("swap functions only apply to over_commit "
                             "preemption")
        if decode_ratio < 1:
            raise ValueError(f"decode_ratio must be >= 1, got {decode_ratio}")
        if decode_ratio > 1 and chunk_fn is None:
            raise ValueError("decode_ratio > 1 requires a chunk_fn (it "
                             "paces decode steps against chunk steps)")
        self.admit_fn = admit_fn
        self.decode_fn = decode_fn
        self.chunk_fn = chunk_fn
        self.init_cache_fn = init_cache_fn
        self.batch_slots = batch_slots
        self.prompt_pad_len = prompt_pad_len
        self.prefill_chunk = prefill_chunk
        self.max_len = max_len          # per-lane cache slots (None: unchecked)
        self.pool = block_pool
        self.radix = radix_cache
        self.copy_block_fn = copy_block_fn
        self.over_commit = over_commit
        self.swap_out_fn = swap_out_fn
        self.swap_in_fn = swap_in_fn
        self.decode_ratio = decode_ratio
        # observability (runtime/telemetry.py): None = fully disabled — the
        # hot loop then never touches a tracer, timer or metrics object
        self.tel = telemetry
        self._tracer = telemetry.tracer if telemetry is not None else None
        self._book: Optional[_Book] = None
        if block_pool is not None:
            lane_cap = block_pool.max_blocks_per_lane * block_pool.block_size
            caps = sorted(set(write_caps)) if write_caps else [lane_cap]
            if caps[0] < 1 or caps[-1] > lane_cap:
                raise ValueError(f"write_caps {caps} outside the lane "
                                 f"capacity 1..{lane_cap}")
            self._write_caps = caps
            self._min_cap = caps[0]
            if radix_cache is not None and copy_block_fn is None \
                    and caps[0] < lane_cap:
                raise ValueError(
                    "prefix sharing with a sliding-window layer (write cap "
                    f"{caps[0]} < lane capacity {lane_cap}) requires a "
                    "copy_block_fn for copy-on-write")
        else:
            self._write_caps = None
            self._min_cap = None
        self._ring_tokens = ring_tokens
        self._ring_blocks = (None if ring_tokens is None else
                             blocks_for_tokens(ring_tokens,
                                               block_pool.block_size))
        self._block_bytes = 0
        # per-lane PREFILLING state: next prompt offset to append, or None
        # when the lane is idle / decodable (chunked prefill only). With a
        # radix cache a prefix-hit lane STARTS at its matched offset.
        self._pref: List[Optional[int]] = [None] * batch_slots
        # per-lane count of shared (radix-mapped) tokens, for deduplicated
        # live-token accounting in _track
        self._shared_tok: List[int] = [0] * batch_slots
        # fixed chunk width: prefill_chunk when chunking, else the prompt
        # pad (radix mode routes ALL admissions through _chunk); set in run
        self._chunk_width: Optional[int] = prefill_chunk
        # over-commit per-lane state: the WORKING prompt (original prompt,
        # or prompt + emitted tokens for a drop-resumed lane — _chunk and
        # _decode read token sources / end positions from it, never from
        # r.prompt directly), the lane's queue entry (carries resume
        # residue across preemptions), and an admission age for
        # youngest-first victim selection
        self._lane_prompt: List[Optional[np.ndarray]] = [None] * batch_slots
        self._lane_entry: List[Optional[_QEntry]] = [None] * batch_slots
        self._lane_age: List[int] = [0] * batch_slots
        self._age = 0
        self._queue: collections.deque = collections.deque()
        # decode:chunk pacing credit — decremented per decode step, topped
        # back to decode_ratio after each chunk step; a chunk runs only
        # when the credit is spent (or nothing is decodable)
        self._decode_credit = 0
        # mechanism layer: every jitted model call (fused admit, chunk,
        # decode, swap in/out, block copy) goes through the Engine — the
        # Scheduler only decides WHICH lanes take part and bookkeeps the
        # results (runtime.engine for the interface contract)
        self.engine = Engine(
            admit_fn, decode_fn, init_cache_fn, batch_slots=batch_slots,
            prompt_pad_len=prompt_pad_len, max_len=max_len,
            chunk_fn=chunk_fn, swap_out_fn=swap_out_fn,
            swap_in_fn=swap_in_fn, copy_block_fn=copy_block_fn,
            telemetry_sink=(telemetry.quant.update
                            if telemetry is not None
                            and telemetry.quant is not None else None),
            tracer=self._tracer)

    def run(self, requests: List[Request]) -> ServeStats:
        _check_capacity(requests, self.max_len, self.pool, self._ring_tokens)
        stats = ServeStats()
        book = self._book = _Book(stats, self.batch_slots)
        if self.pool is not None and self._tracer is not None:
            self.pool.on_evict = lambda blocks: self._ev(
                "radix_evict", blocks=len(blocks))
        t_start = time.perf_counter()
        queue = self._queue = collections.deque()
        for seq, r in enumerate(requests):
            if r.max_new_tokens <= 0:
                r.done = True                # never occupies a lane
            else:
                book.enqueue(r)
                queue.append(_QEntry(r, seq))
                self._ev("enqueue", rid=r.rid, prompt_len=len(r.prompt),
                         max_new=r.max_new_tokens)
        pad = self.prompt_pad_len or max(
            (len(e.req.prompt) for e in queue), default=1)
        # radix mode prefills every admission (hit or miss) through _chunk;
        # without an explicit prefill_chunk the chunk width is the pad, so
        # a miss still completes in one chunk step exactly like _admit
        self._chunk_width = self.prefill_chunk or pad
        B = self.batch_slots
        lanes: List[Optional[Request]] = [None] * B
        self._pref = [None] * B
        self._shared_tok = [0] * B
        self._lane_prompt = [None] * B
        self._lane_entry = [None] * B
        self._lane_age = [0] * B
        self._age = 0
        self._decode_credit = 0
        state = self.engine.init_state()
        if self.pool is not None:
            self.pool.reset()
            self._block_bytes = _paged_block_bytes(state.cache)
            self._sync_table(state.cache)
        self._track(state.cache, lanes, state, book)

        while queue or any(r is not None for r in lanes):
            # progress snapshot for the deadlock guard: a preemption frees
            # blocks without issuing a model call, so it counts as progress
            before = (book.step, stats.preemptions)
            free = [i for i in range(B) if lanes[i] is None]
            if queue and self.over_commit:
                with self._span("admission"):
                    state = self._admit_over_commit(lanes, state, book)
            elif free and queue and self._head_fits(queue[0].req):
                if self.prefill_chunk is None and self.radix is None:
                    with self._span("admission"):
                        state = self._admit(free, queue, pad, lanes, state,
                                            book)
                    continue    # immediate retirees may have freed lanes
                with self._span("admission"):
                    self._admit_chunked(free, queue, lanes, book)
            prefilling = any(off is not None for off in self._pref)
            has_decodable = any(lanes[i] is not None and self._pref[i] is None
                                for i in range(B))
            # decode:chunk pacing: chunk only once the decode credit is
            # spent (ratio=1 reproduces the classic 1:1 interleave) or when
            # nothing is decodable anyway
            if prefilling and (self._decode_credit <= 0 or not has_decodable):
                state = self._chunk(lanes, state, book)
                self._decode_credit = self.decode_ratio
            decodable = [i for i in range(B) if lanes[i] is not None
                         and self._pref[i] is None]
            if decodable:
                state = self._decode(lanes, state, book)
                self._decode_credit -= 1
            elif (book.step, stats.preemptions) == before \
                    and not any(r is not None for r in lanes):
                # no model call, no preemption, no resident lane while the
                # queue is non-empty: nothing can ever make progress.
                # _check_capacity guarantees an empty pool fits any single
                # request, so reaching this means the pool violated that
                # contract (e.g. a leaked allocation).
                raise RuntimeError(
                    "scheduler deadlock: no queued request fits an empty "
                    f"pool (queue head rid {queue[0].req.rid})")
            self._snapshot(queue, lanes, book)
        if self.tel is not None and self.tel.quant is not None:
            self.tel.quant.update_kv_scales(state.cache)
        return book.finalize(t_start)

    # -- paged-pool plumbing (no-ops in dense mode) -------------------------

    def _need_blocks(self, r: Request) -> int:
        """Worst-case per-lane block count for ``r``, ring-clamped: an
        all-window model's lane never maps more than ``_ring_blocks``
        blocks no matter how long it decodes (writes wrap in place)."""
        need = len(r.prompt) + r.max_new_tokens - 1
        if self._ring_tokens is not None:
            need = min(need, self._ring_tokens)
        return blocks_for_tokens(need, self.pool.block_size)

    def _head_fits(self, r: Request) -> bool:
        """Admission backpressure: the queue head's worst-case reservation
        must fit or the whole admission waits (FIFO — later requests do not
        overtake a starved head)."""
        if self.pool is None:
            return True
        if self.radix is not None:
            blocks, _, _, n_alloc, n_reserve, total = self._plan_prefix(r)
            if blocks:
                return self.pool.can_map_shared(blocks, n_reserve, total)
            return self.pool.can_reserve(n_reserve)
        return self.pool.can_reserve(self._need_blocks(r))

    def _plan_prefix(self, r: Request):
        """Radix admission plan: match the prompt, then size the lane.

        Returns (shared_blocks, raw_hit_tokens, K_aligned, n_alloc,
        n_reserve, n_cols) where n_reserve counts the NOVEL blocks only
        (suffix + decode growth, ring-clamped) plus a copy-on-write
        allowance of one fresh block per shared block whenever the request
        can wrap a ring-window layer (its last write position reaches
        min(write_caps)) — COW replaces a shared block with a private one,
        drawing from the reservation like any growth. The match is capped
        at (P-1)//block_size blocks so the novel suffix keeps >= 1 token
        (the chunk step's final-position logits emit the first token)."""
        P = len(r.prompt)
        bs = self.pool.block_size
        blocks, raw = self.radix.match(r.prompt, max_blocks=(P - 1) // bs)
        k = len(blocks)
        total = self._need_blocks(r)        # ring-clamped table columns
        wraps = P + r.max_new_tokens - 2 >= self._min_cap
        cow_allow = k if wraps else 0
        first = min(self._chunk_width, P - k * bs)
        cols_first = blocks_for_tokens(k * bs + first, bs)
        if self._ring_blocks is not None:
            cols_first = min(cols_first, self._ring_blocks)
        n_alloc = max(cols_first - k, 0)
        n_reserve = (total - k) + cow_allow
        return blocks, raw, k * bs, n_alloc, n_reserve, total

    def _reserve(self, lane: int, r: Request) -> bool:
        """Worst-case reservation + prompt-block mapping at admission. In
        chunked mode only the FIRST chunk's blocks are mapped now; _chunk
        grows the prefix by O(chunk / block_size) blocks per chunk."""
        if self.pool is None:
            return True
        bs = self.pool.block_size
        first = len(r.prompt) if self.prefill_chunk is None \
            else min(len(r.prompt), self.prefill_chunk)
        n_alloc = blocks_for_tokens(first, bs)
        if self._ring_blocks is not None:
            n_alloc = min(n_alloc, self._ring_blocks)
        return self.pool.reserve_and_alloc(
            lane, n_alloc, self._need_blocks(r))

    def _reserve_prefix(self, lane: int, r: Request,
                        book: _Book) -> Optional[int]:
        """Radix admission: map the matched prefix read-only (refcounted)
        plus the first chunk's novel blocks, reserving novel growth only.
        Returns the prompt offset the lane starts prefilling at (K_aligned;
        0 on a miss), or None when the plan does not fit (backpressure)."""
        blocks, raw, k_tok, n_alloc, n_reserve, total = self._plan_prefix(r)
        if blocks:
            ok = self.pool.map_shared(lane, blocks, n_alloc, n_reserve,
                                      n_cols=total)
        else:
            ok = self.pool.reserve_and_alloc(lane, n_alloc, n_reserve)
        if not ok:
            return None
        self._shared_tok[lane] = k_tok
        book.stats.prefix_hit_tokens += raw
        book.stats.prefill_tokens_saved += k_tok
        return k_tok

    def _release(self, lane: int, r: Optional[Request] = None) -> None:
        if r is not None:
            self._ev("retire", rid=r.rid, lane=lane,
                     tokens=len(r.tokens_out))
        if self.pool is not None:
            if self.radix is not None and r is not None:
                self._donate(lane, r)
            self.pool.free_lane(lane)
            self._shared_tok[lane] = 0
        self._lane_prompt[lane] = None
        self._lane_entry[lane] = None

    def _donate(self, lane: int, r: Request) -> None:
        """Retirement donation: insert the lane's full WORKING-prompt
        blocks into the radix tree instead of freeing them (the working
        prompt is the original prompt, or prompt + pre-preemption tokens
        for a drop-resumed lane — either way exactly what those blocks
        hold). Skipped when the lane ever wrapped a ring-window layer
        (last write position >= min cap): a wrapping write lands
        generation data inside prompt cells, so those blocks no longer
        hold a clean prefix. The skip also guarantees any cached path is
        window-read-valid for every future recipient."""
        seq = self._lane_prompt[lane]
        if seq is None:
            seq = r.prompt
        n_full = len(seq) // self.pool.block_size
        if n_full == 0:
            return
        if len(r.prompt) + r.max_new_tokens - 2 >= self._min_cap:
            return
        blocks = [int(b) for b in self.pool.table[lane, :n_full]]
        adopted = self.radix.insert(
            np.asarray(seq[:n_full * self.pool.block_size]), blocks)
        for b in adopted:
            self.pool.set_cached(b, True)

    def _cow_barrier(self, lane: int, positions, cache,
                     lanes=None, state=None, book=None):
        """Copy-on-write barrier, called before any step that writes
        ``positions`` for ``lane``: for every attention write cap, find
        the table column each write wraps into; if that column still maps
        a shared (refcounted/cached) block, redirect it to a private copy
        first. Device copy via copy_block_fn (traced once — src/dst are
        data); the pool swap marks the table dirty for the next sync.

        Under over-commit the COW allowance was never reserved, so the
        fresh block may not physically exist: victims are preempted until
        it does (the lane itself as last resort — the caller then sees
        ``lanes[lane] is None`` and skips the step for it)."""
        if self.pool.lane_shared(lane) == 0:
            return cache
        bs = self.pool.block_size
        cols = sorted({(p % cap) // bs
                       for p in positions for cap in self._write_caps})
        for col in cols:
            if self.over_commit and self.pool.needs_cow(lane, col):
                while self.pool.available_blocks() < 1:
                    victim = self._pick_victim(lanes)
                    self._preempt(victim, lanes, state, book)
                    if victim == lane:
                        return cache
            pair = (self.pool.cow(lane, col, extend=True)
                    if self.over_commit else self.pool.cow(lane, col))
            if pair is not None:
                cache = self.engine.copy_block(cache, pair[0], pair[1])
                self._ev("cow", lane=lane, src=int(pair[0]),
                         dst=int(pair[1]))
        return cache

    def _sync_table(self, cache) -> None:
        """Re-upload the block table only when the pool mutated it since
        the last sync — steady-state decode steps (no admission, no growth,
        no retirement) reuse the device table flowing through the jitted
        step's outputs."""
        if self.pool is not None and self.pool.dirty \
                and isinstance(cache, dict):
            with self._span("table", table_uploads=1):
                cache["block_table"] = jnp.asarray(self.pool.table)
            self.pool.dirty = False

    def _track(self, cache, lanes, state: DecodeState, book: _Book) -> None:
        if self.pool is None:
            book.track_cache(cache)
        else:
            # live tokens are DEDUPLICATED: each lane counts only the
            # tokens it privately wrote (position minus its shared-prefix
            # tokens); every cached block's tokens count once, however
            # many lanes map it
            live = sum(int(state.pos[i, 0]) - self._shared_tok[i]
                       for i, r in enumerate(lanes)
                       if r is not None and state.pos[i, 0] > 0)
            # PREFILLING lanes carry pos -1 but already hold their written
            # chunk tokens (offset counts from 0 — shared tokens excluded)
            live += sum(off - self._shared_tok[i]
                        for i, off in enumerate(self._pref) if off)
            live += self.pool.blocks_cached * self.pool.block_size
            book.track_pool(self.pool, live, self._block_bytes)

    # -- observability hooks (all no-ops when telemetry is None) ------------

    def _ev(self, name: str, rid: Optional[int] = None,
            lane: Optional[int] = None, **args) -> None:
        if self._tracer is not None:
            self._tracer.event(name, self._book.step, rid=rid, lane=lane,
                               **args)

    def _span(self, name: str, **args):
        """A tracer span at the current step (a stateless no-op context
        when tracing is off)."""
        if self._tracer is None:
            return _NO_SPAN
        return self._tracer.span(name, self._book.step, **args)

    def _step_call(self, phase: str, op: Callable, args,
                   n_lanes: Optional[int] = None, **counters):
        """One engine op (a jitted model call plus greedy readback). Under
        tracing it becomes a phase span holding the engine's dispatch and
        readback spans — the readback's host-side token conversion blocks
        on device execution, so the duration covers the computation, not
        just dispatch; ``counters`` become span args. Telemetry unwrapping
        happens inside the engine (telemetry_sink)."""
        if self._tracer is None:
            return op(*args)
        with self._tracer.phase(phase, self._book.step) as ph:
            toks, cache = op(*args)
            if n_lanes is not None:
                ph.args["lanes"] = n_lanes
            ph.args.update(counters)
        return toks, cache

    def _attend_blocks(self, state: DecodeState) -> int:
        """Compute blocks the paged decode attention walks this step in
        one layer of the widest span, summed over lanes — the kernel's own
        count (kernels/paged_attend_decode.py::walk_blocks) at the lanes'
        positions (-1 for a lane the step does not decode)."""
        bs = self.pool.block_size
        quantized = any(x.dtype == jnp.int8
                        for x in jax.tree.leaves(state.cache))
        return int(np.sum(walk_blocks(
            np.asarray(state.pos)[:, 0], nb=-(-self._write_caps[-1] // bs),
            bs=bs, pages=pages_per_block(bs, quantized))))

    def _timed(self, phase: str, thunk: Callable, **args):
        """Time a host-side phase (block swap in/out) as a duration event."""
        if self._tracer is None:
            return thunk()
        with self._tracer.phase(phase, self._book.step) as ph:
            out = thunk()
            ph.args.update(args)
        return out

    def _snapshot(self, queue, lanes, book: _Book) -> None:
        """Periodic metrics snapshot (queue/lane/pool gauges), emitted at
        most once per global step when a MetricsLogger is attached."""
        m = self.tel.metrics if self.tel is not None else None
        if m is None or not m.due(book.step):
            return
        s = book.stats
        gauges: Dict[str, Any] = {
            "queue_depth": len(queue),
            "resident_lanes": sum(r is not None for r in lanes),
            "prefilling_lanes": sum(o is not None for o in self._pref),
            "tokens_generated": s.tokens_generated,
            "decode_steps": s.decode_steps,
            "prefill_calls": s.prefill_calls,
            "preemptions": s.preemptions,
            "swapped_blocks": s.swapped_blocks,
            "prefix_hit_rate": (s.prefix_hit_tokens / book.prompt_tokens
                                if book.prompt_tokens else 0.0),
        }
        if self.pool is not None:
            gauges.update(
                blocks_in_use=self.pool.blocks_in_use,
                blocks_free=self.pool.blocks_free,
                blocks_evictable=self.pool.blocks_evictable,
                blocks_cached=self.pool.blocks_cached,
                shared_blocks=self.pool.shared_blocks,
                refcount_total=self.pool.refcount_total)
        m.emit(book.step, gauges)

    # -----------------------------------------------------------------------

    def _admit(self, free, queue, pad, lanes, state: DecodeState,
               book: _Book) -> DecodeState:
        B = self.batch_slots
        group, entries, slots = [], [], []
        for i in free:
            if not queue:
                break
            if not self._reserve(i, queue[0].req):
                break           # head-of-line backpressure: keep FIFO order
            entries.append(queue.popleft())
            group.append(entries[-1].req)
            slots.append(i)
            book.prompt_tokens += len(group[-1].prompt)
        toks = np.zeros((B, pad), np.int32)
        posm = np.full((B, pad), -1, np.int32)
        g_toks, g_posm = _pack_prompts(group, pad)
        admit_mask = np.zeros((B,), bool)
        for j, i in enumerate(slots):
            toks[i], posm[i] = g_toks[j], g_posm[j]
            admit_mask[i] = True
            lanes[i] = group[j]
            self._register_lane(i, entries[j], group[j].prompt, book)
            self._ev("admit", rid=group[j].rid, lane=i)
        self._sync_table(state.cache)
        first, cache = self._step_call(
            "admit", self.engine.admit,
            (toks, posm, admit_mask, state.cache), n_lanes=len(slots))
        book.stats.prefill_calls += 1
        book.step += 1
        tokens, pos = state.tokens.copy(), state.pos.copy()
        for i in slots:
            r = lanes[i]
            tokens[i, 0] = first[i, 0]
            pos[i, 0] = len(r.prompt)
            book.emit(r, tokens[i, 0])
        # sample gauges BEFORE releasing quota-1 retirees: their blocks
        # were mapped during this prefill, so the peak must include them
        self._track(cache, lanes, DecodeState(tokens, pos, cache), book)
        for i in slots:
            if lanes[i].done:                # quota 1: retire before decoding
                r = lanes[i]
                lanes[i] = None
                pos[i, 0] = -1
                self._release(i, r)
        return DecodeState(tokens, pos, cache)

    def _admit_chunked(self, free, queue, lanes, book: _Book) -> None:
        """Chunked-prefill admission is pure host bookkeeping: mark each
        admitted lane PREFILLING at prompt offset 0 (FIFO, head-of-line
        backpressure as in _admit); the model work happens chunk by chunk
        in _chunk, interleaved with resident decode steps. With a radix
        cache a prefix hit starts the lane at offset K_aligned instead —
        the matched blocks are already mapped (read-only) and the chunk
        step's append-mode positions make them the lane's attended past."""
        for i in free:
            if not queue:
                break
            r = queue[0].req
            _require_nonempty_prompt(r)
            if self.radix is not None:
                off = self._reserve_prefix(i, r, book)
                if off is None:
                    break       # head-of-line backpressure: keep FIFO order
            else:
                if not self._reserve(i, r):
                    break       # head-of-line backpressure: keep FIFO order
                off = 0
            entry = queue.popleft()
            lanes[i] = r
            self._pref[i] = off
            self._register_lane(i, entry, r.prompt, book)
            book.prompt_tokens += len(r.prompt)
            self._ev("admit", rid=r.rid, lane=i)
            if off:
                self._ev("prefix_hit", rid=r.rid, lane=i, tokens=off)

    # -- over-commit: preemption + priority admission -----------------------

    def _register_lane(self, lane: int, entry: _QEntry,
                       prompt: np.ndarray, book: _Book) -> None:
        """Admission bookkeeping shared by every path: record the lane's
        working prompt (token source for _chunk/_decode), its queue entry
        (resume residue carrier), an age stamp for youngest-first victim
        selection, and the queue-wait/admit latency sample."""
        self._lane_prompt[lane] = prompt
        self._lane_entry[lane] = entry
        self._age += 1
        self._lane_age[lane] = self._age
        book.admit(entry.req)

    def _pick_victim(self, lanes,
                     *, below: Optional[int] = None) -> Optional[int]:
        """Victim lane for preemption: lowest priority first, youngest
        (largest age stamp) within a tier. ``below`` restricts candidates
        to strictly lower priority than the given tier (admission-driven
        preemption must never evict a peer to seat an equal); growth-driven
        callers pass no bound — the demander itself is then a candidate,
        guaranteeing a victim always exists."""
        cand = [i for i in range(self.batch_slots) if lanes[i] is not None]
        if below is not None:
            cand = [i for i in cand if lanes[i].priority < below]
        if not cand:
            return None
        return min(cand, key=lambda i: (lanes[i].priority,
                                        -self._lane_age[i]))

    def _pad_block_ids(self, ids: np.ndarray) -> np.ndarray:
        """Pad a lane's live block ids to the fixed swap-step width with
        ``num_blocks`` — an out-of-range POSITIVE id, so the gather clips
        to a garbage row and the scatter drops the write (a negative pad
        would wrap around under jnp indexing)."""
        pad = np.full((self.pool.max_blocks_per_lane,),
                      self.pool.num_blocks, np.int32)
        pad[:len(ids)] = ids
        return pad

    def _preempt(self, lane: int, lanes, state: DecodeState,
                 book: _Book) -> None:
        """Preempt ``lane``: spill its blocks to the host swap buffer
        (swap mode — bit-exact resume) or free them after donating the
        fully written prefix to the radix cache (drop mode — resume
        re-prefills prompt + emitted tokens, O(novel suffix) on a radix
        hit), then re-queue its request with the resume residue attached.
        The request keeps its original arrival seq, so it does not lose
        its FIFO place within its tier."""
        r = lanes[lane]
        entry = self._lane_entry[lane]
        off = self._pref[lane]
        written = off if off is not None else int(state.pos[lane, 0])
        stats = book.stats
        if self.swap_out_fn is not None:
            ids = self.pool.lane_blocks(lane)
            payload = self._timed(
                "swap_out",
                lambda: self.engine.swap_out(state.cache,
                                             self._pad_block_ids(ids)),
                blocks=len(ids))
            entry.resume = _Swapped(
                payload=payload, n_blocks=len(ids),
                prompt=self._lane_prompt[lane], pref_off=off,
                token=int(state.tokens[lane, 0]),
                pos=int(state.pos[lane, 0]))
            stats.swapped_blocks += len(ids)
        else:
            self._donate_written(lane, r, written)
            entry.resume = _Dropped(written=written)
        self.pool.free_lane(lane)
        self._shared_tok[lane] = 0
        self._lane_prompt[lane] = None
        self._lane_entry[lane] = None
        lanes[lane] = None
        self._pref[lane] = None
        state.pos[lane, 0] = -1        # idle: decode treats it as dead
        stats.preemptions += 1
        self._ev("preempt", rid=r.rid, lane=lane, written=written,
                 mode="swap" if self.swap_out_fn is not None else "drop")
        book.requeue(r)
        self._queue.append(entry)

    def _donate_written(self, lane: int, r: Request, written: int) -> None:
        """Drop-mode preemption donation: the lane's blocks hold positions
        0..written-1 of prompt + emitted tokens, so donate the fully
        covered blocks — the radix cache then turns the resume re-prefill
        into O(novel suffix). Skipped without a radix cache, and when a
        ring-window layer may already have wrapped (highest written
        position >= min cap would mean generation data landed inside
        earlier cells)."""
        if self.radix is None:
            return
        bs = self.pool.block_size
        n_full = written // bs
        if n_full == 0 or written - 1 >= self._min_cap:
            return
        full = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.tokens_out, np.int32)])
        blocks = [int(b) for b in self.pool.table[lane, :n_full]]
        adopted = self.radix.insert(full[:n_full * bs], blocks)
        for b in adopted:
            self.pool.set_cached(b, True)

    def _ensure_blocks(self, lane: int, n_total: int, lanes,
                       state: DecodeState, book: _Book) -> bool:
        """Over-commit growth: grow ``lane`` to ``n_total`` mapped blocks,
        preempting victims (lowest priority, youngest) until the pool can
        supply them. The demander itself is the last-resort victim —
        False means it was preempted and the caller must skip it this
        step (it resumes through the queue)."""
        while not self.pool.try_grow(lane, n_total):
            victim = self._pick_victim(lanes)
            # the demander is always a candidate, so victim is never None
            self._preempt(victim, lanes, state, book)
            if victim == lane:
                return False
        return True

    def _admit_over_commit(self, lanes, state: DecodeState,
                           book: _Book) -> DecodeState:
        """Priority-aware over-commit admission: try queued entries in
        (-priority, seq) order — FIFO within a tier, but a starved head no
        longer blocks other tiers. An entry with no free lane may preempt
        a STRICTLY lower-tier victim to take its slot; an entry whose
        first chunk does not fit the pool may do the same. Entries that
        still cannot be placed stay queued (skipped, not blocking)."""
        B = self.batch_slots
        for entry in sorted(self._queue,
                            key=lambda e: (-e.req.priority, e.seq)):
            _require_nonempty_prompt(entry.req)
            free = [i for i in range(B) if lanes[i] is None]
            if not free:
                victim = self._pick_victim(lanes, below=entry.req.priority)
                if victim is None:
                    break       # every resident lane is >= this tier: wait
                self._preempt(victim, lanes, state, book)
                free = [victim]
            lane = free[0]
            placed, state = self._try_place(lane, entry, state, book)
            while not placed:
                victim = self._pick_victim(lanes, below=entry.req.priority)
                if victim is None:
                    break
                self._preempt(victim, lanes, state, book)
                placed, state = self._try_place(lane, entry, state, book)
            if not placed:
                continue        # pool too full even after preemption
            self._queue.remove(entry)
            lanes[lane] = entry.req
        return state

    def _try_place(self, lane: int, entry: _QEntry, state: DecodeState,
                   book: _Book) -> Tuple[bool, DecodeState]:
        """Seat ``entry`` in the free ``lane``. Swap residue re-allocates
        the same block count and re-uploads the host payload (bit-exact);
        anything else (fresh or drop residue) goes through optimistic
        chunked placement. Returns (placed, state) — False leaves the
        pool untouched."""
        r = entry.req
        res = entry.resume
        pool = self.pool
        if isinstance(res, _Swapped):
            n = res.n_blocks
            if n > pool.available_blocks() \
                    or not pool.reserve_and_alloc(lane, n, n):
                return False, state
            ids = pool.lane_blocks(lane)
            cache = self._timed(
                "swap_in",
                lambda: self.engine.swap_in(state.cache,
                                            self._pad_block_ids(ids),
                                            res.payload),
                blocks=len(ids))
            tokens, pos = state.tokens.copy(), state.pos.copy()
            self._pref[lane] = res.pref_off
            if res.pref_off is None:    # decodable: restore pending token
                tokens[lane, 0] = res.token
                pos[lane, 0] = res.pos
            self._register_lane(lane, entry, res.prompt, book)
            self._shared_tok[lane] = 0  # every re-uploaded block is private
            entry.resume = None
            self._ev("resume", rid=r.rid, lane=lane, mode="swap")
            return True, DecodeState(tokens, pos, cache)
        if isinstance(res, _Dropped):
            prompt = np.concatenate([np.asarray(r.prompt, np.int32),
                                     np.asarray(r.tokens_out, np.int32)])
        else:
            prompt = r.prompt
        off = self._place_chunked(lane, prompt, book)
        if off is None:
            return False, state
        if isinstance(res, _Dropped):
            book.stats.recomputed_tokens += max(res.written - off, 0)
            entry.resume = None
            self._ev("resume", rid=r.rid, lane=lane, mode="drop")
        else:
            self._ev("admit", rid=r.rid, lane=lane)
        if off:
            self._ev("prefix_hit", rid=r.rid, lane=lane, tokens=off)
        self._pref[lane] = off
        self._register_lane(lane, entry, prompt, book)
        book.prompt_tokens += len(prompt)
        return True, state

    def _place_chunked(self, lane: int, prompt: np.ndarray,
                       book: _Book) -> Optional[int]:
        """Optimistic admission sizing: map the radix-matched prefix (if
        any) plus ONLY the blocks the first chunk's writes land in — no
        worst-case reservation (try_grow extends it later). Returns the
        starting prefill offset, or None when even the first chunk does
        not physically fit."""
        pool = self.pool
        bs = pool.block_size
        P = len(prompt)
        blocks, raw = [], 0
        if self.radix is not None:
            blocks, raw = self.radix.match(np.asarray(prompt),
                                           max_blocks=(P - 1) // bs)
        k = len(blocks)
        first = min(self._chunk_width, P - k * bs)
        cols_first = blocks_for_tokens(k * bs + first, bs)
        if self._ring_blocks is not None:
            cols_first = min(cols_first, self._ring_blocks)
        n_alloc = max(cols_first - k, 0)
        if n_alloc > pool.available_blocks():
            return None
        if blocks:
            ok = pool.map_shared(lane, blocks, n_alloc, n_alloc,
                                 n_cols=cols_first)
        else:
            ok = pool.reserve_and_alloc(lane, n_alloc, n_alloc)
        if not ok:
            return None
        self._shared_tok[lane] = k * bs
        if k:
            book.stats.prefix_hit_tokens += raw
            book.stats.prefill_tokens_saved += k * bs
        return k * bs

    def _chunk(self, lanes, state: DecodeState, book: _Book) -> DecodeState:
        """One fixed-shape chunk step: append up to ``prefill_chunk`` prompt
        tokens to every PREFILLING lane (left-padded into the fixed chunk
        width; lanes starting chunk 1 are reset first via the step's
        reset_mask). Lanes finishing their last chunk emit their first
        token from the chunk's final-position logits and become decodable
        (quota-1 requests retire immediately, as in _admit).

        Token sources and end positions come from the lane's WORKING
        prompt (prompt + pre-preemption tokens for a drop-resumed lane),
        so a resumed lane re-prefills exactly what its cache held plus the
        pending token — the final-position logits then emit the NEXT
        (never-emitted) token, preserving greedy parity."""
        C = self._chunk_width
        B = self.batch_slots
        cache = state.cache
        if self.pool is not None:
            # pool pre-pass BEFORE building the step inputs: under
            # over-commit a COW or growth may PREEMPT a lane (possibly one
            # already visited, or the demander itself), changing who
            # chunks this step
            bs = self.pool.block_size
            with self._span("pool"):
                for i in range(B):
                    if self._pref[i] is None or lanes[i] is None:
                        continue
                    off = self._pref[i]
                    seq = self._lane_prompt[i]
                    c = min(C, len(seq) - off)
                    # copy-on-write BEFORE growth/sync: a ring-window write
                    # in this chunk may wrap into a shared prefix column
                    if self.radix is not None:
                        cache = self._cow_barrier(i, range(off, off + c),
                                                  cache, lanes, state, book)
                        if lanes[i] is None:
                            continue    # preempted inside the COW barrier
                    # map the blocks this chunk's writes land in
                    # (reservation-backed, cannot fail mid-flight — unless
                    # over-commit, which grows on demand and preempts when
                    # the pool is dry)
                    n_total = (off + c - 1) // bs + 1
                    if self._ring_blocks is not None:
                        n_total = min(n_total, self._ring_blocks)
                    self._grow(i, n_total, lanes, state, book)
        prefilling = [i for i in range(B) if self._pref[i] is not None]
        if not prefilling:          # every prefilling lane was preempted
            return DecodeState(state.tokens, state.pos, cache)
        with self._span("inputs"):
            toks = np.zeros((B, C), np.int32)
            posm = np.full((B, C), -1, np.int32)
            reset = np.zeros((B,), bool)
            ends = {}
            for i in prefilling:
                off = self._pref[i]
                seq = self._lane_prompt[i] \
                    if self._lane_prompt[i] is not None else lanes[i].prompt
                c = min(C, len(seq) - off)
                toks[i, C - c:] = seq[off:off + c]
                posm[i, C - c:] = np.arange(off, off + c, dtype=np.int32)
                reset[i] = off == 0
                ends[i] = off + c
            tokens, pos = state.tokens.copy(), state.pos.copy()
        self._sync_table(cache)
        last, cache = self._step_call(
            "chunk", self.engine.chunk,
            (toks, posm, reset, cache), n_lanes=len(prefilling))
        book.stats.prefill_calls += 1
        book.stats.chunk_steps += 1
        book.step += 1
        with self._span("emit"):
            for i in prefilling:
                r = lanes[i]
                seq = self._lane_prompt[i] \
                    if self._lane_prompt[i] is not None else r.prompt
                if ends[i] < len(seq):
                    self._pref[i] = ends[i]     # more chunks to go
                    continue
                self._pref[i] = None            # last chunk: decodable
                tokens[i, 0] = last[i, 0]
                pos[i, 0] = len(seq)
                book.emit(r, tokens[i, 0])
        with self._span("retirement"):
            # sample gauges BEFORE releasing quota-1 retirees (as in _admit)
            self._track(cache, lanes, DecodeState(tokens, pos, cache), book)
            for i in prefilling:
                if self._pref[i] is None and lanes[i].done:
                    r = lanes[i]
                    lanes[i] = None         # quota 1: retire immediately
                    pos[i, 0] = -1
                    self._release(i, r)
        return DecodeState(tokens, pos, cache)

    def _grow(self, lane: int, n_total: int, lanes, state: DecodeState,
              book: _Book) -> None:
        """Map ``lane``'s blocks up to ``n_total`` (over-commit: growing on
        demand, preempting when the pool is dry). Under tracing, a growth
        emits a ``block_grow`` event."""
        n_before = (self.pool.lane_mapped(lane)
                    if self._tracer is not None else 0)
        if self.over_commit:
            self._ensure_blocks(lane, n_total, lanes, state, book)
        else:
            self.pool.grow(lane, n_total)
        if self._tracer is not None and lanes[lane] is not None \
                and self.pool.lane_mapped(lane) > n_before:
            self._ev("block_grow", rid=lanes[lane].rid, lane=lane,
                     blocks=self.pool.lane_mapped(lane) - n_before)

    def _decode(self, lanes, state: DecodeState, book: _Book) -> DecodeState:
        cache = state.cache
        if self.pool is not None:
            # incremental growth: map the block the coming write lands in.
            # Reservation-backed growth cannot fail mid-flight; over-commit
            # growth may PREEMPT a lane instead (possibly the demander),
            # so the active set is recomputed after this pre-pass.
            bs = self.pool.block_size
            with self._span("pool"):
                for i in range(self.batch_slots):
                    if lanes[i] is None or self._pref[i] is not None:
                        continue
                    p = int(state.pos[i, 0])
                    if self.radix is not None:
                        # a ring-window write may wrap into a shared column
                        cache = self._cow_barrier(i, (p,), cache,
                                                  lanes, state, book)
                        if lanes[i] is None:
                            continue    # preempted inside the COW barrier
                    n_total = p // bs + 1
                    if self._ring_blocks is not None:
                        n_total = min(n_total, self._ring_blocks)
                    self._grow(i, n_total, lanes, state, book)
            self._sync_table(cache)
        active = [i for i, r in enumerate(lanes)
                  if r is not None and self._pref[i] is None]
        if not active:              # every decodable lane was preempted
            return DecodeState(state.tokens, state.pos, cache)
        with self._span("inputs"):
            tokens, pos = state.tokens.copy(), state.pos.copy()
        step_state = DecodeState(state.tokens, state.pos, cache)
        counters = ({"attend_blocks": self._attend_blocks(step_state)}
                    if self._tracer is not None and self.pool is not None
                    else {})
        nxt, cache = self._step_call(
            "decode_batch", self.engine.generate, (step_state,),
            n_lanes=len(active), **counters)
        book.count_decode(len(active))
        book.step += 1
        with self._span("emit"):
            for i in active:
                r = lanes[i]
                tokens[i, 0] = nxt[i, 0]
                pos[i, 0] += 1
                book.emit(r, tokens[i, 0])
        with self._span("retirement"):
            # sample gauges BEFORE releasing retirees: a lane whose final
            # write just grew a block still holds it during this step, and
            # the peak must include it
            self._track(cache, lanes, DecodeState(tokens, pos, cache), book)
            for i in active:
                if lanes[i].done:
                    r = lanes[i]
                    lanes[i] = None
                    pos[i, 0] = -1
                    self._release(i, r)
        return DecodeState(tokens, pos, cache)


def serve_continuous(admit_fn: Callable, decode_fn: Callable, init_cache_fn,
                     requests: List[Request], *, batch_slots: int,
                     prompt_pad_len: Optional[int] = None,
                     max_len: Optional[int] = None,
                     block_pool: Optional[BlockPool] = None,
                     chunk_fn: Optional[Callable] = None,
                     prefill_chunk: Optional[int] = None,
                     radix_cache: Optional[RadixCache] = None,
                     write_caps: Optional[List[int]] = None,
                     ring_tokens: Optional[int] = None,
                     copy_block_fn: Optional[Callable] = None,
                     over_commit: bool = False,
                     swap_out_fn: Optional[Callable] = None,
                     swap_in_fn: Optional[Callable] = None,
                     decode_ratio: int = 1,
                     telemetry: Optional[ServeTelemetry] = None) -> ServeStats:
    """Continuous-batching counterpart of :func:`serve_batch` (see
    :class:`Scheduler` for the step-function contracts)."""
    return Scheduler(admit_fn, decode_fn, init_cache_fn,
                     batch_slots=batch_slots, prompt_pad_len=prompt_pad_len,
                     max_len=max_len, block_pool=block_pool,
                     chunk_fn=chunk_fn, prefill_chunk=prefill_chunk,
                     radix_cache=radix_cache, write_caps=write_caps,
                     ring_tokens=ring_tokens,
                     copy_block_fn=copy_block_fn, over_commit=over_commit,
                     swap_out_fn=swap_out_fn, swap_in_fn=swap_in_fn,
                     decode_ratio=decode_ratio,
                     telemetry=telemetry).run(requests)


def serve(prefill_step: Callable, admit_step: Callable,
          decode_step: Callable, init_cache_fn, params,
          requests: List[Request], *, scheduler: str = "static",
          batch_slots: int, prompt_pad_len: Optional[int] = None,
          max_len: Optional[int] = None,
          block_pool: Optional[BlockPool] = None,
          chunk_step: Optional[Callable] = None,
          prefill_chunk: Optional[int] = None,
          radix_cache: Optional[RadixCache] = None,
          write_caps: Optional[List[int]] = None,
          ring_tokens: Optional[int] = None,
          copy_block_fn: Optional[Callable] = None,
          over_commit: bool = False,
          swap_out_fn: Optional[Callable] = None,
          swap_in_fn: Optional[Callable] = None,
          decode_ratio: int = 1,
          telemetry: Optional[ServeTelemetry] = None) -> ServeStats:
    """Dispatch to a scheduler, binding ``params`` into step functions with
    the ``runtime.steps.make_*_step`` signatures (params first):

      prefill_step(params, tokens, cache, positions) — static mode
      admit_step(params, tokens, positions, admit_mask, cache) — continuous
      chunk_step(params, tokens, positions, reset_mask, cache) — chunked
      decode_step(params, tokens, pos, cache)

    The unused step for the chosen scheduler may be None. ``block_pool``
    (continuous only) switches the Scheduler to pool-managed paged
    admission; the static scheduler serves paged caches through a fully
    mapped identity table instead (init_cache(paged=True) default).
    ``prefill_chunk`` (continuous only, needs ``chunk_step``) admits
    prompts in chunks of at most that many tokens, interleaved with
    resident decode steps. ``radix_cache`` (+ ``write_caps`` /
    ``ring_tokens`` / ``copy_block_fn``, continuous paged only) enables
    prefix sharing — see :class:`Scheduler`. ``copy_block_fn`` takes
    (cache, src, dst) with no params (models.transformer.cache_copy_block).
    ``over_commit`` (+ optional ``swap_out_fn``/``swap_in_fn`` from
    runtime.steps.make_swap_steps, continuous paged chunked only) drops
    worst-case reservations in favor of preemption; ``decode_ratio``
    paces decode steps against chunk steps — see :class:`Scheduler`.
    Swap fns take (cache, ids) / (cache, ids, payload) with no params.
    """
    if scheduler == "continuous":
        return serve_continuous(
            lambda t, pm, m, c: admit_step(params, t, pm, m, c),
            lambda t, p, c: decode_step(params, t, p, c),
            init_cache_fn, requests, batch_slots=batch_slots,
            prompt_pad_len=prompt_pad_len, max_len=max_len,
            block_pool=block_pool,
            chunk_fn=(None if chunk_step is None else
                      lambda t, pm, m, c: chunk_step(params, t, pm, m, c)),
            prefill_chunk=prefill_chunk, radix_cache=radix_cache,
            write_caps=write_caps, ring_tokens=ring_tokens,
            copy_block_fn=copy_block_fn, over_commit=over_commit,
            swap_out_fn=swap_out_fn, swap_in_fn=swap_in_fn,
            decode_ratio=decode_ratio, telemetry=telemetry)
    if scheduler != "static":
        raise ValueError(f"unknown scheduler {scheduler!r}")
    if telemetry is not None:
        raise ValueError("telemetry is a continuous-scheduler feature; "
                         "the static scheduler has no request lifecycle")
    if block_pool is not None:
        raise ValueError("block_pool is a continuous-scheduler feature; "
                         "static paged serving uses a fully mapped table")
    if prefill_chunk is not None:
        raise ValueError("prefill_chunk is a continuous-scheduler feature; "
                         "static groups prefill each group monolithically")
    if radix_cache is not None:
        raise ValueError("radix_cache is a continuous-scheduler feature; "
                         "prefix sharing needs the paged block pool")
    if over_commit:
        raise ValueError("over_commit is a continuous-scheduler feature; "
                         "preemption needs the paged block pool")
    if decode_ratio != 1:
        raise ValueError("decode_ratio is a continuous-scheduler feature; "
                         "static groups have no chunk/decode interleave")
    return serve_batch(lambda t, pm, c: prefill_step(params, t, c, pm),
                       lambda t, p, c: decode_step(params, t, p, c),
                       init_cache_fn, requests, batch_slots=batch_slots,
                       max_len=max_len)
