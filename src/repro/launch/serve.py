"""Serving launcher: batched requests against a (optionally W8A8-quantized)
model — prefill + decode with KV cache.

``--quantize`` serves with *simulated* quantization (fake-quant, f32
matmuls). ``--quantize --deploy-int8`` serves the true fixed-point path:
weights are pre-packed to int8 in the param pytree and the FFN / attention
projections run on the Pallas kernels (``ln/rms_quantize ->
int8_matmul_peg(+fused epilogue) -> int8_matmul``); a parity check against
the fake-quant reference is printed at startup.

``--kv-bits 8`` additionally stores the KV cache int8 (per-head per-slot
scales) and decodes through the fused ``int8_attend_decode`` kernel; a
multi-step decode parity check against the bf16-cache path is printed at
startup. ``--kv-bits 4`` packs two int4 cells per cache byte (half the
int8 cache HBM — ~2x resident decode lanes per pool byte) and decodes
through the same kernels' in-VMEM nibble-unpack path; startup additionally
quantifies int4-vs-int8 drift (max-abs logit delta + greedy-token match
rate over teacher-forced decode steps).

``--weight-bits 4`` packs the projection/FFN weights at 4 bits (paper
Tables 5-7 sub-8-bit regime, MSE ranges): two int4 rows per byte in the
packed payload; the matmul kernels unpack to int8 in VMEM, halving HBM
weight reads. Sites the packing cannot express (odd K / odd PEG group)
fall back to 8-bit-style fake-quant exactly as today.

``--scheduler continuous`` replaces the static group batching with the
slot-scheduled continuous-batching runtime (in-flight admission into freed
decode lanes, see repro.runtime.serve_loop); ``--parity`` serves the same
requests under both schedulers and verifies identical greedy tokens.

``--paged-kv`` switches every attention layer's cache to the block-paged
layout (``--block-size`` cells per block): the continuous scheduler owns a
block pool (``--num-blocks``, default = the dense worst case) that
allocates on admission, grows lanes at decode and frees on retirement —
HBM cache bytes then scale with LIVE tokens instead of
batch_slots x max_len; the static scheduler serves through a fully mapped
identity table (dense-equivalent paging). With ``--parity`` the same
requests are additionally served on the dense cache and greedy tokens are
verified identical (paged == dense), on top of the scheduler parity check.

``--prefill-chunk N`` (continuous scheduler only) admits prompts in chunks
of at most N tokens interleaved with resident decode steps (chunked
prefill), so one long prompt never stalls the resident lanes for a whole
monolithic prefill. ``--parity`` then additionally serves the requests
unchunked and verifies chunked == unchunked greedy tokens.

``--over-commit`` (continuous + ``--paged-kv``) drops worst-case block
reservations: admission claims only the actual prefix + first-chunk need,
the queue becomes priority-aware (``--priority`` gives every other request
a higher tier) and a pool running dry preempts a victim lane — spilling
its blocks to a host buffer with ``--swap-blocks`` (bit-exact resume) or
dropping + re-prefilling them through chunked admission. ``--decode-ratio``
holds decode cadence under prefill pressure. ``--parity`` then additionally
serves the same requests with worst-case reservations (no preemption) and
verifies preempted == unpreempted greedy tokens — including under
``--deploy-int8 --kv-bits 8``.

``--prefix-cache`` (continuous + ``--paged-kv``) enables prefix sharing: a
radix tree caches retired lanes' prompt blocks, admission maps the longest
block-aligned cached prefix read-only (refcounted, copy-on-write under
ring-window wrap) and prefills only the novel suffix. The launcher then
synthesizes a shared-prefix workload (every request opens with the same
``--prompt-len``/2-token system prefix) so the cache actually hits;
``--parity`` additionally serves the same requests with sharing disabled
and verifies shared == unshared greedy tokens — in particular under
``--quantize --deploy-int8 [--kv-bits 8]``, where the int8 KV blocks carry
their per-head per-slot scales inside the block and sharing stays
bit-exact.

Without ``--reduced`` the model runs at its published widths in bf16 on a
``(1, --tp)`` (data, model) mesh over the local devices. Every parity check
gates the run: a missed tolerance exits non-zero, and each tolerance
states its reason. ``--verify`` also checks what was served: every request
got its tokens, and the served requests' cached prefill + decode logits,
replayed through the same jitted steps with the served params, match an
un-cached forward. References run at ``highest`` matmul precision.

CPU smoke:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --requests 8 --new-tokens 8 [--quantize [--deploy-int8 [--kv-bits 8]]] \
      [--scheduler continuous [--parity] [--prefill-chunk 16]] \
      [--paged-kv [--block-size 16] [--prefix-cache]]
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import Mode, QuantCtx
from repro.core.pipeline import ptq
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as tfm
from repro.parallel import make_dist, make_param_shardings
from repro.runtime import Request, serve
from repro.runtime.steps import (make_admit_step, make_chunk_prefill_step,
                                 make_decode_step, make_prefill_step)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI. Exposed as a function so tests (tests/test_docs.py)
    can introspect the flag set and keep the docs from drifting."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--scheduler", choices=("static", "continuous"),
                    default="static",
                    help="static: group batching, lockstep decode per "
                         "group; continuous: slot-scheduled decode with "
                         "in-flight admission into freed lanes")
    ap.add_argument("--parity", action="store_true",
                    help="serve the same requests under BOTH schedulers "
                         "and verify identical per-request greedy tokens")
    ap.add_argument("--skew", type=int, default=0, metavar="N",
                    help="give every other request max_new_tokens=N "
                         "(skewed-quota workload; shows the continuous "
                         "scheduler's utilization win)")
    ap.add_argument("--quantize", action="store_true",
                    help="W8A8 PTQ (PEG on the FFN path) before serving")
    ap.add_argument("--deploy-int8", action="store_true",
                    help="serve the integer path: packed int8 weights + "
                         "Pallas kernels (requires --quantize)")
    ap.add_argument("--kv-bits", type=int, default=16, choices=(4, 8, 16),
                    help="8: int8 KV cache + fused int8 decode attention; "
                         "4: nibble-packed int4 cache (half the int8 HBM), "
                         "decoded through the kernels' in-VMEM unpack path "
                         "(both require --deploy-int8); 16: bf16/f32 cache")
    ap.add_argument("--weight-bits", type=int, default=8, choices=(4, 8),
                    help="4: pack deployable weights as int4 (two rows per "
                         "byte, MSE ranges; kernels unpack in VMEM — "
                         "halves HBM weight reads; requires --quantize); "
                         "8: standard W8A8 packing")
    ap.add_argument("--paged-kv", action="store_true",
                    help="block-paged KV cache: continuous scheduling "
                         "allocates blocks per LIVE token (block pool + "
                         "per-lane block tables); static serves through a "
                         "fully mapped identity table")
    ap.add_argument("--block-size", type=int, default=16, metavar="N",
                    help="token cells per KV block (with --paged-kv)")
    ap.add_argument("--num-blocks", type=int, default=0, metavar="N",
                    help="physical blocks in the paged pool (0 = dense "
                         "worst case batch_slots x ceil(max_len/bs); "
                         "smaller values exercise admission backpressure; "
                         "continuous scheduler only)")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="N",
                    help="admit prompts in chunks of at most N tokens "
                         "interleaved with resident decode steps (chunked "
                         "prefill; 0 = monolithic slot-insert prefill; "
                         "continuous scheduler only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over retired prompt blocks: "
                         "admission maps the longest block-aligned cached "
                         "prefix read-only (refcounted, copy-on-write) and "
                         "prefills only the novel suffix; synthesizes a "
                         "shared-prefix workload (continuous + --paged-kv)")
    ap.add_argument("--over-commit", action="store_true",
                    help="drop worst-case block reservations: admit "
                         "against actual prefix + first-chunk need, grow "
                         "on demand, and preempt a victim lane (lowest "
                         "priority, then youngest) when the pool runs dry "
                         "(continuous + --paged-kv)")
    ap.add_argument("--swap-blocks", action="store_true",
                    help="preempt by spilling the victim's blocks to a "
                         "host-memory buffer and re-uploading on resume "
                         "(bit-exact) instead of dropping + re-prefilling "
                         "them (requires --over-commit)")
    ap.add_argument("--priority", type=int, default=0, metavar="N",
                    help="give every other request priority tier N "
                         "(mirrors --skew; the over-commit scheduler "
                         "admits high tiers first and preempts low tiers "
                         "first; 0 = all requests tier 0)")
    ap.add_argument("--decode-ratio", type=int, default=1, metavar="N",
                    help="decode steps per chunk-prefill step once lanes "
                         "are decodable (>1 holds decode cadence under "
                         "prefill pressure; needs a chunked path: "
                         "--prefill-chunk or --over-commit)")
    ap.add_argument("--trace", metavar="FILE", default="",
                    help="record request-lifecycle events and write a "
                         "Chrome-trace-event JSON (load in "
                         "https://ui.perfetto.dev) to FILE; also prints "
                         "per-phase step-latency p50/p95/p99 (continuous "
                         "scheduler only)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="snapshot scheduler gauges (queue depth, resident "
                         "lanes, pool blocks, prefix hit rate, preemptions) "
                         "every N steps; written as JSON-lines next to "
                         "--trace (FILE.metrics.jsonl) and printed as "
                         "Prometheus text at exit (continuous only)")
    ap.add_argument("--quant-telemetry", action="store_true",
                    help="thread fixed-shape clip/saturation reductions out "
                         "of the jitted steps and report per-site clip "
                         "fractions + observed-amax/calibrated-range ratios "
                         "(and kv-cache scale stats at --kv-bits 8/4); "
                         "requires --quantize, continuous scheduler only")
    ap.add_argument("--stats-json", metavar="FILE", default="",
                    help="write the primary run's ServeStats as JSON to "
                         "FILE (ServeStats.to_json)")
    ap.add_argument("--async", dest="async_serve", action="store_true",
                    help="serve through the async front-end: requests "
                         "submit into a thread-safe queue and stream "
                         "tokens back per request while ONE scheduler "
                         "thread drives the engine's decomposed "
                         "prefill/insert/generate triad "
                         "(runtime.async_serve; dense cache only — "
                         "incompatible with --paged-kv/--prefill-chunk/"
                         "--prefix-cache/--over-commit and the telemetry "
                         "flags)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="shard the engine tensor-parallel over N local "
                         "devices (jax.sharding mesh (1, N) over (data, "
                         "model); admission stays host-local, the admit "
                         "mask broadcasts replicated). On CPU, simulate "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "(with --reduced, 1 = unsharded)")
    ap.add_argument("--warmup", action="store_true",
                    help="serve the workload once before the measured run, "
                         "so its wall time and tokens/s exclude "
                         "compilation")
    ap.add_argument("--verify", action="store_true",
                    help="check the served output: every request got its "
                         "tokens, and the served requests' cached prefill "
                         "+ decode logits (replayed through the serving "
                         "steps) match an un-cached forward within a "
                         "stated tolerance (exits non-zero otherwise); "
                         "greedy agreement with the reference is printed")
    ap.add_argument("--seed", type=int, default=0)
    return ap


# Parity tolerances, on the relative RMS error of the logits,
# ||logits - reference|| / ||reference|| (the max |error| over max
# |reference| is printed beside it: one flipped code of the 8-bit logits
# grid alone moves that by ~1/128, so it is reported, not gated).
# f32 (--reduced): the paths differ only in summation order and in the odd
# requantization tie an order change moves by one int8 code.
TOL_F32 = 1e-3
# bf16 (full width): activations between ops are bf16, whose ulp is 2^-8
# (0.4% relative); an order-of-summation difference flips single roundings,
# and the flips compound through the residual stream of every layer
# (cached vs un-cached over 24 layers: 1.6e-2 on a TPU v5e).
TOL_BF16 = 5e-2
# quantized vs float KV cache, any width: the cache stores values already
# on the calibrated k/v grids and reads them back exactly, so both runs
# attend over the same f32 values and read 0 (CPU and TPU v5e). Any error
# fails: k written on the v grid reads 0.23 on a TPU v5e, and so would one
# requantization tie landing between the two attention paths (4.1e-2 with
# the PEG scales shifted), which the seeded traffic does not hit.
TOL_KV8 = 1e-6
# quantized model at full width. Two implementations of one site (Mosaic
# vs XLA, chunked vs whole-sequence attention) round a few of ~10^6 values
# to the other side of a tie (10 of 7.9M norm+quantize codes of one layer
# on a TPU v5e), and every later 8-bit site requantizes the difference
# into whole grid steps: one code per sequence moved at the first site
# moves the logits by 8.4e-2 over 2 layers and 0.23 over 24. The clean
# readings sit below that (int8 vs fake-quant 4.5e-2, served 7.4e-2);
# the planted faults of benchmarks/quant_floor.py above it (PEG
# permutation off by one channel 1.34, k cache on the v grid 0.36 served).
# int8 kernels vs the fake-quant reference, first REF_SUPERS layers:
TOL_INT8 = 0.15
# the served program's cached prefill + decode vs its un-cached forward:
TOL_SERVED = 0.15
# depth of the deploy parity references: the fake-quant reference needs
# float weights, and only these first super-blocks' are kept once the rest
# is packed to int8
REF_SUPERS = 2


def rel_errors(ref, got) -> tuple:
    """(relative RMS error, max |error| over max |ref|) of ``got``."""
    ref = np.asarray(ref, np.float64)
    err = np.asarray(got, np.float64) - ref
    return (float(np.sqrt(np.sum(err ** 2) / (np.sum(ref ** 2) + 1e-30))),
            float(np.max(np.abs(err)) / (np.max(np.abs(ref)) + 1e-30)))


def gate(tag: str, what: str, errors: tuple, tol: float) -> None:
    """Print one parity measurement (``rel_errors``); exit non-zero when
    its relative RMS error passes the tolerance."""
    rms, mx = errors
    ok = rms <= tol
    print(f"[{tag}] {what}: rel rms logits error {rms:.3e} (tolerance "
          f"{tol:g}) {'OK' if ok else 'FAIL'}; max rel {mx:.3e}")
    if not ok:
        raise SystemExit(f"[{tag}] FAIL: {what}: rel rms logits error "
                         f"{rms:.3e} > tolerance {tol:g}")


def _f32(tree):
    """Float leaves as f32; packed integer payloads unchanged."""
    return jax.tree.map(lambda x: x.astype(jnp.float32)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def first_supers(params, n: int):
    """The first ``n`` super-blocks of a stacked param pytree (a copy) —
    the params of ``cfg.with_supers(n)``."""
    out = {k: v for k, v in params.items() if k != "scan"}
    out["scan"] = [jax.tree.map(lambda x: x[:n], g) for g in params["scan"]]
    return out


def calibrate(args, cfg, params):
    """PTQ on a few synthetic prompts through the unrolled view of the
    served params (per-layer site names), then layer-shared quant params
    for the scan layout (DESIGN.md §4). Returns (policy, act_state)."""
    import dataclasses
    from repro.core import peg_policy
    pol = peg_policy(4)
    if args.weight_bits == 4:
        # sub-8-bit weights (paper Tables 5-7): symmetric int4 grid,
        # MSE-fit ranges; activations stay on the W8A8/PEG policy
        from repro.core import QuantizerConfig, RangeEstimator
        pol = dataclasses.replace(
            pol, weight_default=QuantizerConfig(
                bits=4, symmetric=True, estimator=RangeEstimator.MSE))
    calib = [{"tokens": jax.random.randint(
        jax.random.PRNGKey(10 + i), (2, args.prompt_len), 0,
        cfg.vocab_size)} for i in range(2)]

    def fwd(p, b, ctx):
        logits, _ = tfm.forward(cfg, p, b["tokens"], ctx=ctx)
        return logits
    qm = ptq(fwd, tfm.unrolled_view(cfg, params), calib, pol,
             collect_inputs=args.deploy_int8)
    # collapse per-layer sites to shared "layer/..." names (the first
    # layer's grid)
    shared = {}
    for site, qp in qm.act_state.items():
        base = "layer/" + site.split("/", 1)[1] if site.startswith("layer") \
            else site
        shared.setdefault(base, qp)
    return pol, shared


def deploy_errors(args, cfg, ref_fp, params, pol, state, ctx_factory):
    """``rel_errors`` of the integer path against its references on the
    first REF_SUPERS super-blocks, whose float weights ``ref_fp`` are all
    that is kept: ``"int8"``, the int8 kernels (``ctx_factory``) vs the
    fake-quant forward; at ``--kv-bits`` 8 or 4 ``"kv"``, the quantized vs
    the float KV cache over prefill + 4 teacher-forced decode steps. Both
    sides run f32 at ``highest`` precision: this checks the kernels
    against the fake-quant arithmetic, not bf16 roundings of it."""
    n_ref = min(REF_SUPERS, cfg.n_super)
    ref_cfg = cfg.with_supers(n_ref)
    int_params = _f32(first_supers(params, n_ref))
    toks = jax.random.randint(jax.random.PRNGKey(99),
                              (2, args.prompt_len), 0, cfg.vocab_size)
    ref_fwd, _, _ = _ref_steps(ref_cfg, lambda: QuantCtx(
        policy=pol, mode=Mode.APPLY, act_state=state))
    int_fwd, prefill, decode = _ref_steps(ref_cfg, ctx_factory)
    with jax.default_matmul_precision("highest"):
        errors = {"int8": rel_errors(ref_fwd(_f32(ref_fp), toks),
                                     int_fwd(int_params, toks))}
    if args.kv_bits in (4, 8):
        pairs = _teacher_forced(args, ref_cfg, prefill, decode, int_params,
                                toks, 16, args.kv_bits)
        errors["kv"] = rel_errors(np.stack([a for a, _ in pairs]),
                                  np.stack([b for _, b in pairs]))
    return errors


def _teacher_forced(args, cfg, prefill, decode, params, toks, kv_a, kv_b,
                    steps=4):
    """Prefill + ``steps`` decode steps on f32 caches of ``kv_a`` and
    ``kv_b`` bits, both fed the ``kv_a`` path's argmax; the per-step logits
    of each."""
    B = toks.shape[0]
    ca = tfm.init_cache(cfg, B, args.max_len, dtype=jnp.float32,
                        kv_bits=kv_a)
    cb = tfm.init_cache(cfg, B, args.max_len, dtype=jnp.float32,
                        kv_bits=kv_b)
    with jax.default_matmul_precision("highest"):
        la, ca = prefill(params, toks, ca)
        lb, cb = prefill(params, toks, cb)
        out = [(la, lb)]
        pos = jnp.full((B, 1), toks.shape[1], jnp.int32)
        for _ in range(steps):
            cur = jnp.argmax(la, axis=-1).astype(jnp.int32)
            la, ca = decode(params, cur, pos, ca)
            lb, cb = decode(params, cur, pos, cb)
            out.append((la, lb))
            pos = pos + 1
    return [(np.asarray(a), np.asarray(b)) for a, b in out]


def _check_deploy(args, cfg, params, quant, ctx_factory, dtype):
    """The integer path's gates: every deployable linear packed, the
    quantized KV cache engaged, int8 == fake-quant reference and quantized
    == float cache within tolerance (``deploy_errors``)."""
    from repro.core import deploy
    pol, state, deploy_acts, ref_fp = quant
    n_packed, n_total = deploy.count_packed(params)
    print(f"[deploy-int8] {n_packed}/{n_total} per-layer linears packed")
    if n_packed != n_total:
        raise SystemExit(f"[deploy-int8] FAIL: {n_total - n_packed} "
                         f"linears left on the simulate path")
    if args.kv_bits < 16:
        caches = jax.eval_shape(lambda: tfm.init_cache(
            cfg, 1, args.max_len, dtype=dtype, kv_bits=args.kv_bits,
            paged=args.paged_kv, block_size=args.block_size))
        nodes = tfm._cache_nodes(caches)
        quant = [n for n in nodes if isinstance(n, (tfm.QuantKVCache,
                                                    tfm.PagedQuantKVCache))]
        grids = sum(1 for k in deploy_acts if k.endswith("/attn/kv"))
        print(f"[kv-int{args.kv_bits}] quantized cache engaged on "
              f"{len(quant)}/{len(nodes)} cache groups ({grids} calibrated "
              f"k/v grids)")
        if len(quant) != len(nodes):
            raise SystemExit(f"[kv-int{args.kv_bits}] FAIL: cache not "
                             f"quantized")

    n_layers = cfg.with_supers(min(REF_SUPERS, cfg.n_super)).num_layers
    errors = deploy_errors(args, cfg, ref_fp, params, pol, state,
                           ctx_factory)
    gate("deploy-int8", f"int8 vs fake-quant, first {n_layers} layers",
         errors["int8"], TOL_F32 if dtype == jnp.float32 else TOL_INT8)
    if "kv" in errors:
        what = (f"int{args.kv_bits} vs float KV cache over prefill + 4 "
                f"decode steps, first {n_layers} layers")
        if args.kv_bits == 8:
            gate("kv-int8", what, errors["kv"], TOL_KV8)
        else:
            # int4 is lossy by construction: quantified, not asserted
            print(f"[kv-int4] {what}: rel rms logits error "
                  f"{errors['kv'][0]:.4%}, max rel {errors['kv'][1]:.4%}")

    if args.kv_bits == 4:
        # drift quantification (int4 vs int8 cache): max-abs logit delta
        # and greedy-token match rate, teacher-forced on the int8 path's
        # argmax so both see identical inputs
        n_ref = min(REF_SUPERS, cfg.n_super)
        ref_cfg = cfg.with_supers(n_ref)
        _, prefill, decode = _ref_steps(ref_cfg, ctx_factory)
        toks = jax.random.randint(jax.random.PRNGKey(99),
                                  (2, args.prompt_len), 0, cfg.vocab_size)
        pairs = _teacher_forced(args, ref_cfg, prefill, decode,
                                _f32(first_supers(params, n_ref)), toks, 8, 4)
        delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in pairs)
        matched = sum(int(jnp.sum(jnp.argmax(a, axis=-1) ==
                                  jnp.argmax(b, axis=-1))) for a, b in pairs)
        total = sum(a.shape[0] for a, _ in pairs)
        print(f"[kv-int4] int4 vs int8 cache drift over prefill + "
              f"{len(pairs) - 1} decode steps: max |logit delta| "
              f"{delta:.5f}, greedy-token match {matched}/{total} "
              f"({matched / total:.1%})")


def _ref_steps(cfg, ctx_factory):
    """Jitted un-cached forward (logits), prefill and decode step of
    ``cfg`` under ``ctx_factory``'s quantization. Jitted, not eager: the
    range search of a linear left unpacked (the head, 32000 x 3840 on an
    MSE grid of 100 points) then fuses into its reduction instead of
    materializing a (grid, K, N) f32 temporary."""
    fwd = jax.jit(lambda p, t: tfm.forward(cfg, p, t, ctx=ctx_factory())[0])
    prefill = jax.jit(lambda p, t, c: tfm.prefill(cfg, p, t, c,
                                                  ctx=ctx_factory()))
    decode = jax.jit(lambda p, t, pos, c: tfm.decode_step(
        cfg, p, t, pos, c, ctx=ctx_factory()))
    return fwd, prefill, decode


def replay(args, cfg, params, prompts, teacher, n_live, *, dtype, admit,
           chunk_step, decode):
    """Cached logits of the served program: ``prompts`` (B, T) through the
    serving steps' prefill (chunked as served) on a fresh cache, then
    decode steps fed ``teacher`` (B, N-1). Only the first ``n_live`` lanes
    hold requests. Returns (n_live, N, V)."""
    B, T = prompts.shape
    live = np.arange(B) < n_live
    # a fully mapped identity table when paged, as the static scheduler's
    cache = tfm.init_cache(cfg, B, args.max_len, dtype=dtype,
                           kv_bits=args.kv_bits, paged=args.paged_kv,
                           block_size=args.block_size)
    if args.prefill_chunk:
        C = args.prefill_chunk
        for off in range(0, T, C):
            c = min(C, T - off)
            toks = np.zeros((B, C), np.int32)
            posm = np.full((B, C), -1, np.int32)
            toks[live, C - c:] = prompts[live, off:off + c]
            posm[live, C - c:] = np.arange(off, off + c, dtype=np.int32)
            last, cache = chunk_step(params, toks, posm, live & (off == 0),
                                     cache)
    else:
        posm = np.where(live[:, None], np.arange(T, dtype=np.int32), -1)
        last, cache = admit(params, prompts, posm, live, cache)
    cached = [last]
    for i in range(teacher.shape[1]):
        pos = np.where(live, T + i, -1).astype(np.int32)[:, None]
        last, cache = decode(params, teacher[:, i:i + 1], pos, cache)
        cached.append(last)
    return np.asarray(jnp.concatenate(cached, axis=1)[:n_live])


def uncached(cfg, params, seqs, T, *, ctx_factory, dist=None):
    """Reference logits at positions T-1.. of ``seqs``: one un-cached
    forward at ``highest`` matmul precision."""
    def fwd(p, s):
        ctx = ctx_factory() if ctx_factory is not None else None
        return tfm.forward(cfg, p, s, ctx=ctx, dist=dist)[0][:, T - 1:]
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(params, seqs))


def _verify(args, cfg, params, requests, *, dist, ctx_factory, dtype, admit,
            chunk_step, decode):
    """Check what was served. Every request got its tokens. The served
    requests' prompts, continued by N-1 tokens drawn from ``--seed``, are
    replayed through the serving steps, with the served params, dtype and
    matmul precision, on a fresh cache: these cached prefill + decode
    logits must match an un-cached forward over the same sequence (gated).
    Greedy agreement of the served tokens with the un-cached forward over
    prompt + served tokens is printed. Returns the replayed logits (a
    function of the seed and the model only, so runs compare)."""
    short = [(r.rid, len(r.tokens_out)) for r in requests
             if len(r.tokens_out) != r.max_new_tokens]
    if short:
        raise SystemExit(f"[verify] FAIL: requests (rid, tokens) {short} "
                         f"did not get their tokens")
    print(f"[verify] all {len(requests)} requests got their "
          f"{sorted({r.max_new_tokens for r in requests})} tokens")
    B = args.batch_slots
    reqs = requests[:B]
    n_live = len(reqs)
    T = args.prompt_len
    N = min(len(r.tokens_out) for r in reqs)
    prompts = np.zeros((B, T), np.int32)
    served = np.zeros((B, N), np.int32)
    for i, r in enumerate(reqs):
        prompts[i] = r.prompt
        served[i] = r.tokens_out[:N]
    teacher = np.random.RandomState(args.seed + 1).randint(
        10, cfg.vocab_size, size=(B, N)).astype(np.int32)[:, :N - 1]

    cached = replay(args, cfg, params, prompts, teacher, n_live, dtype=dtype,
                    admit=admit, chunk_step=chunk_step, decode=decode)
    ref = uncached(cfg, params, np.concatenate([prompts, teacher],
                                               axis=1)[:n_live], T,
                   ctx_factory=ctx_factory, dist=dist)
    ref_served = uncached(cfg, params, np.concatenate(
        [prompts, served[:, :N - 1]], axis=1)[:n_live], T,
        ctx_factory=ctx_factory, dist=dist)
    agree = float(np.mean(np.argmax(ref_served, axis=-1) == served[:n_live]))
    print(f"[verify] greedy agreement of the {n_live}x{N} served tokens "
          f"with the un-cached forward: {agree:.1%} (printed, not gated: "
          f"argmax can flip on rounding)")
    what = (f"cached prefill + {N - 1} decode steps vs un-cached forward, "
            f"{cfg.num_layers} layers")
    errors = rel_errors(ref, cached)
    if args.kv_bits == 4:
        # the int4 cache is lossy by construction: quantified, not gated
        print(f"[verify] {what}: rel rms logits error {errors[0]:.3e}, max "
              f"rel {errors[1]:.3e} (int4 cache: reported, not gated)")
    elif dtype == jnp.float32:
        gate("verify", what, errors, TOL_F32)
    else:
        gate("verify", what, errors,
             TOL_BF16 if ctx_factory is None else TOL_SERVED)
    return cached


def deploy_ctx_factory(pol, state, deploy_acts):
    """QuantCtx factory of the integer path (``Mode.DEPLOY``)."""
    def ctx_factory():
        return QuantCtx(policy=pol, mode=Mode.DEPLOY, act_state=state,
                        deploy_acts=deploy_acts)
    return ctx_factory


def build_model(args, cfg, dtype, dist):
    """The served model: random params of ``cfg`` from ``--seed``
    (stacked, placed for ``dist``), calibrated with ``--quantize`` and
    packed with ``--deploy-int8``. Returns ``(params, ctx_factory,
    quant)``; ``quant`` is ``(pol, act_state, deploy_acts, ref_fp)`` on the
    integer path (``ref_fp``: the float weights of the first REF_SUPERS
    super-blocks, the rest are dropped once packed), else None."""
    key = jax.random.PRNGKey(args.seed)
    init = functools.partial(tfm.init_params, cfg, stacked=True, dtype=dtype)
    shardings = (make_param_shardings(jax.eval_shape(init, key), dist)
                 if dist is not None else None)
    # jitted: the stacks are generated in place, without eager temporaries
    params = jax.jit(init, out_shardings=shardings)(key)
    if not args.quantize:
        return params, None, None
    pol, state = calibrate(args, cfg, params)
    if not args.deploy_int8:
        return params, (lambda: QuantCtx(policy=pol, mode=Mode.APPLY,
                                         act_state=state)), None
    from repro.core import build_deploy
    ref_fp = first_supers(params, min(REF_SUPERS, cfg.n_super))
    # rebinding drops the float weights: only ref_fp's stay
    params, deploy_acts = build_deploy(cfg, params, pol, state)
    return (params, deploy_ctx_factory(pol, state, deploy_acts),
            (pol, state, deploy_acts, ref_fp))


def serving_steps(cfg, dist, ctx_factory):
    """The jitted serving steps: (prefill, admit, decode, chunk_step), the
    cache argument donated where the scheduler replaces it."""
    prefill = jax.jit(make_prefill_step(cfg, dist=dist,
                                        ctx_factory=ctx_factory))
    admit = jax.jit(make_admit_step(cfg, dist=dist,
                                    ctx_factory=ctx_factory),
                    donate_argnums=(4,))
    decode = jax.jit(make_decode_step(cfg, dist=dist,
                                      ctx_factory=ctx_factory),
                     donate_argnums=(3,))
    chunk_step = jax.jit(make_chunk_prefill_step(cfg, dist=dist,
                                                 ctx_factory=ctx_factory),
                         donate_argnums=(4,))
    return prefill, admit, decode, chunk_step


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.deploy_int8 and not args.quantize:
        ap.error("--deploy-int8 requires --quantize")
    if args.kv_bits < 16 and not args.deploy_int8:
        ap.error(f"--kv-bits {args.kv_bits} requires --deploy-int8 "
                 "(the quantized KV cache is a deploy-path feature; "
                 "without it the cache stays bf16/f32)")
    if args.weight_bits != 8 and not args.quantize:
        ap.error(f"--weight-bits {args.weight_bits} requires --quantize")
    if args.block_size < 1:
        ap.error("--block-size must be >= 1")
    if args.prefill_chunk < 0:
        ap.error("--prefill-chunk must be >= 0")
    if args.prefill_chunk and args.scheduler != "continuous":
        ap.error("--prefill-chunk requires --scheduler continuous "
                 "(static groups prefill monolithically)")
    from repro.runtime import BlockPool, RadixCache, blocks_for_tokens
    from repro.runtime.serve_loop import _check_capacity
    if args.num_blocks and not args.paged_kv:
        ap.error("--num-blocks requires --paged-kv")
    if args.prefix_cache and not args.paged_kv:
        ap.error("--prefix-cache requires --paged-kv (prefix sharing maps "
                 "cached blocks through the block pool)")
    if args.prefix_cache and args.scheduler != "continuous":
        ap.error("--prefix-cache requires --scheduler continuous (the "
                 "static scheduler has no pool to share blocks from)")
    if args.over_commit and not (args.paged_kv
                                 and args.scheduler == "continuous"):
        ap.error("--over-commit requires --paged-kv and --scheduler "
                 "continuous (preemption is a paged feature)")
    if args.swap_blocks and not args.over_commit:
        ap.error("--swap-blocks requires --over-commit")
    if args.decode_ratio < 1:
        ap.error("--decode-ratio must be >= 1")
    if args.decode_ratio > 1 and not (args.prefill_chunk
                                      or args.over_commit):
        ap.error("--decode-ratio > 1 requires a chunked path "
                 "(--prefill-chunk or --over-commit)")
    if args.metrics_every < 0:
        ap.error("--metrics-every must be >= 0")
    if (args.trace or args.metrics_every or args.quant_telemetry) \
            and args.scheduler != "continuous":
        ap.error("--trace/--metrics-every/--quant-telemetry require "
                 "--scheduler continuous (telemetry instruments the "
                 "continuous scheduler's request lifecycle)")
    if args.quant_telemetry and not args.quantize:
        ap.error("--quant-telemetry requires --quantize (clip fractions "
                 "are measured against the calibrated quantization grids)")
    if args.async_serve and (args.paged_kv or args.prefill_chunk
                             or args.prefix_cache or args.over_commit
                             or args.trace or args.metrics_every
                             or args.quant_telemetry or args.stats_json):
        ap.error("--async serves through the bare engine triad (dense "
                 "cache, FIFO admission) — incompatible with --paged-kv/"
                 "--prefill-chunk/--prefix-cache/--over-commit and the "
                 "telemetry/--stats-json flags")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.verify and args.async_serve:
        ap.error("--verify replays the synchronous serving steps "
                 "(incompatible with --async)")

    use_compile_cache()
    cfg = get_config(args.arch)
    dist = None
    dtype = jnp.bfloat16
    if args.reduced:
        cfg = cfg.reduced()
        dtype = jnp.float32
    if args.tp > 1 or not args.reduced:
        ndev = len(jax.local_devices())
        if ndev < args.tp:
            ap.error(
                f"--tp {args.tp}: only {ndev} device(s) visible; "
                "simulate CPU devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.tp} "
                "(set BEFORE the process imports jax)")
        dist = make_dist(make_serving_mesh(args.tp))

    # per-lane table width: ring-window bounded for all-window archs
    # (ceil(S_w / block_size) instead of ceil(max_len / block_size))
    nb_lane = (tfm.paged_lane_blocks(cfg, args.max_len, args.block_size)
               if args.paged_kv
               else blocks_for_tokens(args.max_len, args.block_size))
    ring_tokens = (tfm.paged_ring_tokens(cfg, args.max_len, args.block_size)
                   if args.paged_kv else None)
    full_blocks = args.batch_slots * nb_lane
    num_blocks = args.num_blocks or full_blocks
    if args.paged_kv and args.scheduler == "static" \
            and num_blocks < full_blocks:
        ap.error("static paged serving needs the dense worst case "
                 f"(--num-blocks >= {full_blocks}); pool-constrained "
                 "admission is a continuous-scheduler feature")
    # fail before model build on workloads the serve loop would reject
    # (same shared check serve() re-runs on the real requests)
    probe_pool = BlockPool(num_blocks, args.block_size, args.batch_slots,
                           nb_lane) if args.paged_kv else None
    try:
        _check_capacity([Request(rid=-1,
                                 prompt=np.zeros(args.prompt_len, np.int32),
                                 max_new_tokens=max(args.new_tokens,
                                                    args.skew))],
                        args.max_len, probe_pool, ring_tokens)
    except ValueError as e:
        ap.error(f"--max-len / --num-blocks too small: {e}")

    params, ctx_factory, quant = build_model(args, cfg, dtype, dist)
    print(f"[serve] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads of "
          f"{cfg.hd}, d_ff {cfg.d_ff}, {dtype.__name__} on "
          f"{jax.devices()[0].platform} x{args.tp}")
    if args.deploy_int8:
        _check_deploy(args, cfg, params, quant, ctx_factory, dtype)
    del quant
    prefill, admit, decode, chunk_step = serving_steps(cfg, dist, ctx_factory)

    telemetry = None
    if args.trace or args.metrics_every or args.quant_telemetry:
        from repro.runtime import ServeTelemetry
        telemetry = ServeTelemetry.create(trace=bool(args.trace),
                                          metrics_every=args.metrics_every,
                                          quant=args.quant_telemetry)
    # quant telemetry uses SEPARATE jitted closures (the plain steps keep
    # their 2-output signature — parity runs reuse them untraced and the
    # tracer-off path never recompiles)
    admit_t = decode_t = chunk_t = None
    if args.quant_telemetry:
        admit_t = jax.jit(make_admit_step(cfg, dist=dist,
                                          ctx_factory=ctx_factory,
                                          quant_telemetry=True),
                          donate_argnums=(4,))
        decode_t = jax.jit(make_decode_step(cfg, dist=dist,
                                            ctx_factory=ctx_factory,
                                            quant_telemetry=True),
                           donate_argnums=(3,))
        chunk_t = jax.jit(make_chunk_prefill_step(cfg, dist=dist,
                                                  ctx_factory=ctx_factory,
                                                  quant_telemetry=True),
                          donate_argnums=(4,))

    def make_requests():
        rng = np.random.RandomState(args.seed)
        shared = (rng.randint(10, cfg.vocab_size, size=args.prompt_len // 2)
                  if args.prefix_cache else np.zeros(0, np.int64))
        return [Request(rid=i,
                        prompt=np.concatenate(
                            [shared,
                             rng.randint(10, cfg.vocab_size,
                                         size=args.prompt_len - len(shared))]
                        ).astype(np.int64),
                        max_new_tokens=(args.skew if args.skew and i % 2
                                        else args.new_tokens),
                        priority=(args.priority if args.priority and i % 2
                                  else 0))
                for i in range(args.requests)]

    def init_cache(batch, paged, scheduler, kv_bits=None):
        kvb = args.kv_bits if kv_bits is None else kv_bits
        if not paged:
            return tfm.init_cache(cfg, batch, args.max_len, dtype=dtype,
                                  kv_bits=kvb)
        if scheduler == "static":
            # fully mapped identity table (dense-equivalent paging; the
            # static loop has no pool to grow from)
            return tfm.init_cache(cfg, batch, args.max_len, dtype=dtype,
                                  kv_bits=kvb, paged=True,
                                  block_size=args.block_size)
        return tfm.init_cache(cfg, batch, args.max_len, dtype=dtype,
                              kv_bits=kvb, paged=True,
                              block_size=args.block_size,
                              num_blocks=num_blocks, mapped=False)

    copy_block = jax.jit(tfm.cache_copy_block, donate_argnums=(0,))
    if args.swap_blocks:
        from repro.runtime.steps import make_swap_steps
        _swap_out, _swap_in = make_swap_steps()
        # swap_out keeps the cache alive (no donation); swap_in updates the
        # arena in place
        swap_out = jax.jit(_swap_out)
        swap_in = jax.jit(_swap_in, donate_argnums=(0,))
    else:
        swap_out = swap_in = None

    def run(scheduler, requests, paged=None, chunk=0, prefix=None,
            over_commit=None, kv_bits=None, tel=None):
        paged = args.paged_kv if paged is None else paged
        prefix = ((args.prefix_cache if prefix is None else prefix)
                  and paged and scheduler == "continuous")
        oc = ((args.over_commit if over_commit is None else over_commit)
              and paged and scheduler == "continuous")
        pool = None
        if paged and scheduler == "continuous":
            pool = BlockPool(num_blocks, args.block_size, args.batch_slots,
                             nb_lane)
        armed = tel is not None and tel.quant is not None
        a_step, d_step = (admit_t, decode_t) if armed else (admit, decode)
        c_step = chunk_t if armed else chunk_step
        return serve(prefill, a_step, d_step,
                     lambda b: init_cache(b, paged, scheduler,
                                          kv_bits=kv_bits), params,
                     requests, scheduler=scheduler,
                     batch_slots=args.batch_slots,
                     max_len=args.max_len, block_pool=pool,
                     chunk_step=c_step if (chunk or prefix or oc)
                     else None,
                     prefill_chunk=chunk or None,
                     radix_cache=RadixCache(args.block_size) if prefix
                     else None,
                     write_caps=tfm.attn_write_caps(
                         cfg, args.max_len, args.block_size) if pool
                     else None,
                     ring_tokens=ring_tokens if pool else None,
                     copy_block_fn=copy_block if prefix else None,
                     over_commit=oc,
                     swap_out_fn=swap_out if oc else None,
                     swap_in_fn=swap_in if oc else None,
                     decode_ratio=args.decode_ratio
                     if (chunk or prefix or oc) else 1,
                     telemetry=tel)

    requests = make_requests()
    if args.async_serve:
        import time
        from repro.runtime import AsyncServer
        from repro.runtime.engine import make_engine
        eng = make_engine(cfg, params, batch_slots=args.batch_slots,
                          prompt_pad_len=args.prompt_len,
                          max_len=args.max_len, dtype=dtype,
                          kv_bits=args.kv_bits, ctx_factory=ctx_factory,
                          dist=dist)
        t0 = time.perf_counter()
        with AsyncServer(eng) as srv:
            streams = [srv.submit(r.prompt, r.max_new_tokens, rid=r.rid)
                       for r in requests]
            for r, s in zip(requests, streams):
                r.tokens_out = s.result(timeout=600)
                r.done = True
        wall = time.perf_counter() - t0
        total = sum(len(r.tokens_out) for r in requests)
        tp_note = (f", tp={args.tp} over {len(jax.devices())} devices"
                   if args.tp > 1 else "")
        print(f"[serve:async] {total} tokens from {len(requests)} streamed "
              f"requests, {wall:.2f}s ({total / max(wall, 1e-9):.1f} tok/s), "
              f"engine traces {eng.trace_counts}{tp_note}")
        if args.parity:
            for sched in ("static", "continuous"):
                sync_reqs = make_requests()
                run(sched, sync_reqs)
                pairs = list(zip(requests, sync_reqs))
                if args.kv_bits == 4:
                    matched = sum(1 for r, b in pairs
                                  for x, y in zip(r.tokens_out, b.tokens_out)
                                  if x == y)
                    tot = sum(min(len(r.tokens_out), len(b.tokens_out))
                              for r, b in pairs)
                    print(f"[parity] async engine vs {sched} scheduler: "
                          f"{matched}/{tot} greedy tokens match "
                          f"({matched / max(tot, 1):.1%}) — int4 drift "
                          f"reported, not asserted")
                    continue
                bad = [r.rid for r, b in pairs
                       if list(r.tokens_out) != list(b.tokens_out)]
                if bad:
                    raise SystemExit(
                        f"[parity] FAIL: request ids {bad} diverge between "
                        f"the async engine and the {sched} scheduler")
                print(f"[parity] OK: async engine and {sched} scheduler "
                      f"emit identical greedy tokens for all "
                      f"{len(requests)} requests")
        return None
    if args.warmup:
        run(args.scheduler, make_requests(), chunk=args.prefill_chunk)
    stats = run(args.scheduler, requests, chunk=args.prefill_chunk,
                tel=telemetry)
    if args.paged_kv and args.scheduler == "continuous":
        paged_note = (f", blocks {stats.blocks_in_use}/{num_blocks} "
                      f"(frag {stats.block_fragmentation:.0%}, "
                      f"block-size {args.block_size})")
    elif args.paged_kv:
        paged_note = f", paged identity-mapped (block-size {args.block_size})"
    else:
        paged_note = ""
    chunk_note = (f", chunked prefill ({stats.chunk_steps} chunk steps @ "
                  f"<= {args.prefill_chunk} tokens)"
                  if args.prefill_chunk else "")
    prefix_note = (f", prefix-cache hits {stats.prefix_hit_tokens} tokens "
                   f"(rate {stats.prefix_hit_rate:.0%}, "
                   f"{stats.prefill_tokens_saved} prefill tokens saved, "
                   f"peak {stats.shared_blocks} shared blocks)"
                   if args.prefix_cache else "")
    oc_note = (f", over-commit: {stats.preemptions} preemptions "
               f"({stats.swapped_blocks} blocks swapped, "
               f"{stats.recomputed_tokens} tokens recomputed), "
               f"queue-wait {stats.queue_wait_steps} steps"
               if args.over_commit else "")
    print(f"[serve:{args.scheduler}] {stats.tokens_generated} tokens, "
          f"{stats.decode_steps} decode steps, "
          f"{stats.prefill_calls} prefills, {stats.wall_s:.2f}s "
          f"({stats.tokens_per_s:.1f} tok/s), "
          f"slot-utilization {stats.slot_utilization:.0%}, "
          f"peak kv-cache {stats.cache_bytes / 1024:.0f} KiB "
          f"(kv-bits {args.kv_bits}{paged_note}{chunk_note}{prefix_note}"
          f"{oc_note})")
    if args.over_commit:
        for tier in sorted(stats.tier_latency, reverse=True):
            t = stats.tier_latency[tier]
            print(f"[tier {tier}] {t.requests} requests, first-token "
                  f"p50/p99 {t.first_token_p50:.0f}/{t.first_token_p99:.0f} "
                  f"steps, inter-token p50/p99 {t.inter_token_p50:.1f}/"
                  f"{t.inter_token_p99:.1f} steps")

    if telemetry is not None:
        if telemetry.tracer is not None:
            telemetry.tracer.dump(args.trace)
            spans = telemetry.tracer.request_spans()
            retired = sum(1 for s in spans.values() if s["retired"])
            print(f"[trace] {len(telemetry.tracer.events)} events, "
                  f"{retired}/{len(spans)} requests retired -> {args.trace}")
            for ph, h in sorted(
                    telemetry.tracer.latency_histograms().items()):
                print(f"[trace] {ph}: n={h['n']} p50 {h['p50']:.2f}ms "
                      f"p95 {h['p95']:.2f}ms p99 {h['p99']:.2f}ms")
        if telemetry.metrics is not None:
            if args.trace:
                mpath = args.trace + ".metrics.jsonl"
                with open(mpath, "w") as f:
                    f.write(telemetry.metrics.jsonl())
                print(f"[metrics] {len(telemetry.metrics.snapshots)} "
                      f"snapshots -> {mpath}")
            print(telemetry.metrics.prometheus_text(), end="")
        if telemetry.quant is not None:
            rep = telemetry.quant.report()
            sites = rep["sites"]
            print(f"[quant-health] {len(sites)} sites over "
                  f"{rep['steps_observed']} telemetry steps")
            ranked = sorted(sites.items(),
                            key=lambda kv: -kv[1]["clip_fraction"])
            for s, d in ranked[:10]:
                print(f"[quant-health] {s}: clip {d['clip_fraction']:.4%} "
                      f"({d['clipped']}/{d['total']}), amax "
                      f"{d['observed_amax']:.4f} / range "
                      f"{d['calibrated_range']:.4f} "
                      f"(ratio {d['amax_ratio']:.2f})")
            for name, st in sorted(rep["kv_scales"].items()):
                print(f"[quant-health] {name}: n={st['n']} "
                      f"min {st['min']:.3e} p50 {st['p50']:.3e} "
                      f"p99 {st['p99']:.3e} max {st['max']:.3e}")
    if args.verify:
        stats.replayed_logits = _verify(
            args, cfg, params, requests, dist=dist, ctx_factory=ctx_factory,
            dtype=dtype, admit=admit, chunk_step=chunk_step, decode=decode)
    if args.stats_json:
        import json
        with open(args.stats_json, "w") as f:
            json.dump(stats.to_json(), f, indent=2, default=str)
        print(f"[stats] ServeStats -> {args.stats_json}")

    if args.parity:
        def compare(tag, b_reqs, ok_msg):
            # At kv-bits 4 the dynamic per-slot int4 grids round-trip
            # prefill cache reads approximately (no exact bit-exactness
            # guarantee across serving configurations), so drift is
            # quantified instead of asserted; kv 8/16 stay exact.
            mismatch = [r.rid for r, b in zip(requests, b_reqs)
                        if r.tokens_out != b.tokens_out]
            if args.kv_bits == 4:
                matched = sum(
                    1 for r, b in zip(requests, b_reqs)
                    for x, y in zip(r.tokens_out, b.tokens_out) if x == y)
                total = sum(min(len(r.tokens_out), len(b.tokens_out))
                            for r, b in zip(requests, b_reqs))
                ok = len(requests) - len(mismatch)
                print(f"[parity] {tag}: {matched}/{total} greedy tokens "
                      f"match ({matched / max(total, 1):.1%}), "
                      f"{ok}/{len(requests)} requests identical — int4 "
                      f"dynamic per-slot grids round-trip prefill reads "
                      f"approximately, so drift is reported, not asserted")
                return
            if mismatch:
                raise SystemExit(f"[parity] FAIL: request ids {mismatch} "
                                 f"diverge between {tag}")
            print(f"[parity] OK: {ok_msg}")

        other = ("static" if args.scheduler == "continuous"
                 else "continuous")
        other_reqs = make_requests()
        run(other, other_reqs)
        compare(f"{args.scheduler} vs {other} schedulers", other_reqs,
                f"{args.scheduler} and {other} schedulers emit identical "
                f"greedy tokens for all {len(requests)} requests")
        if args.prefill_chunk:
            unchunked_reqs = make_requests()
            run(args.scheduler, unchunked_reqs)
            compare("chunked vs unchunked prefill", unchunked_reqs,
                    f"chunked (<= {args.prefill_chunk} tokens) and "
                    f"unchunked prefill emit identical greedy tokens "
                    f"for all {len(requests)} requests")
        if args.paged_kv:
            dense_reqs = make_requests()
            run(args.scheduler, dense_reqs, paged=False,
                chunk=args.prefill_chunk)
            compare("paged vs dense caches", dense_reqs,
                    f"paged and dense caches emit identical greedy "
                    f"tokens for all {len(requests)} requests "
                    f"(kv-bits {args.kv_bits})")
        if args.prefix_cache:
            unshared_reqs = make_requests()
            run(args.scheduler, unshared_reqs, chunk=args.prefill_chunk,
                prefix=False)
            compare("prefix-shared vs unshared serving", unshared_reqs,
                    f"prefix-shared and unshared serving emit identical "
                    f"greedy tokens for all {len(requests)} requests "
                    f"(kv-bits {args.kv_bits})")
        if args.over_commit:
            # preempted == unpreempted: the same requests served with
            # worst-case reservations (FIFO backpressure, no preemption)
            # must emit identical greedy tokens
            unpreempted_reqs = make_requests()
            run(args.scheduler, unpreempted_reqs, chunk=args.prefill_chunk,
                over_commit=False)
            compare("preempted (over-commit) vs unpreempted serving",
                    unpreempted_reqs,
                    f"preempted (over-commit, {stats.preemptions} "
                    f"preemptions) and unpreempted serving emit identical "
                    f"greedy tokens for all {len(requests)} requests "
                    f"(kv-bits {args.kv_bits})")
        if args.kv_bits == 4:
            # int4 vs int8 is lossy by construction — quantify the drift
            # (token match rate) rather than asserting exact equality
            int8_reqs = make_requests()
            run(args.scheduler, int8_reqs, chunk=args.prefill_chunk,
                kv_bits=8)
            matched = sum(
                1 for r, o in zip(requests, int8_reqs)
                for t4, t8 in zip(r.tokens_out, o.tokens_out) if t4 == t8)
            total = sum(min(len(r.tokens_out), len(o.tokens_out))
                        for r, o in zip(requests, int8_reqs))
            exact = sum(1 for r, o in zip(requests, int8_reqs)
                        if r.tokens_out == o.tokens_out)
            print(f"[parity] int4 vs int8 KV cache drift: "
                  f"{matched}/{total} greedy tokens match "
                  f"({matched / max(total, 1):.1%}), "
                  f"{exact}/{len(requests)} requests identical end-to-end")
    return stats


if __name__ == "__main__":
    main()
