"""Pallas TPU kernels for the paper's quantization hot-spots.

Module map (which kernel serves which paper equation):

  peg_quant      — fused per-embedding-group quantize(-dequantize): eq. (5).
                   ``peg_fake_quant`` simulates; ``peg_quantize`` emits the
                   int8 payload (deployment).
  int8_matmul    — s8xs8->s32 MXU matmuls. ``int8_matmul`` is the per-tensor
                   fixed-point product of eq. (3) (asymmetric activations via
                   the zero-point colsum correction); ``int8_matmul_peg``
                   fuses the per-group accumulator re-scalings of eq.
                   (4)->(5) into the K-loop. Both carry the fused deployment
                   EPILOGUE (bias + activation + optional re-quantize) so
                   integer FFN chains keep int8 in HBM end-to-end.
  fused_ln_quant — LayerNorm / RMSNorm + quantize in one VPU pass (the
                   Fig.-4 rewriting: quantizer directly after the norm).
                   ``*_fake_quant`` simulates; ``*_quantize`` emits int8 and
                   feeds ``int8_matmul[_peg]`` directly.
  int8_attend_decode — fused decode attention over the int8 KV cache
                   (serving hot path). Covers the paper's Fig.-1 attention
                   quantization sites in true fixed point: the q/k sites
                   become the int8 payloads themselves (q on the calibrated
                   site grid with an in-kernel zero-point correction, k/v as
                   the per-head per-slot symmetric cache), ``softmax_in``
                   fake-quants the soft-capped logits in-kernel, and
                   ``softmax_out`` the normalized probabilities (two-pass
                   schedule: the S grid is walked twice because the online-
                   softmax denominator only exists after the last chunk).
                   Halves decode-time cache HBM bytes vs bf16.
  paged_attend_decode — block-PAGED twins of the decode attention paths
                   (``paged_attend_decode`` bf16/f32,
                   ``paged_int8_attend_decode`` int8 with the same Fig.-1
                   site treatment / eq.-(3)-style zero-point corrections as
                   int8_attend_decode). Each lane walks only the pages it
                   has written (``walk_blocks``), several pages a compute
                   block (128 tokens of a bf16/f32 arena, one page of an
                   int8 arena); the block table rides as a scalar-prefetch
                   operand so each page's double-buffered copy targets the
                   lane's *physical* arena page, and cell validity is
                   DERIVED from (logical index, q_pos) — stale cells of
                   reallocated pages are unreadable by construction. This
                   is the deployment payoff squared: int8 halves bytes per
                   token, paging makes bytes and work proportional to live
                   tokens (runtime/block_pool.py).

Simulate vs deploy: the ``*_fake_quant`` variants back ``Mode.APPLY`` / QAT
(f32 in, f32 out — quantization error only); the emitting variants back
``Mode.DEPLOY`` (repro.core.deploy), where activations travel between
kernels as int8 and scales are traced operands (no recompile per
calibration / per scanned layer).

ops.py exposes jit'd wrappers (interpret mode on CPU, Mosaic on TPU) that
also handle batched (B, T, D) inputs and ragged row counts via padding;
ref.py holds the pure-jnp oracles used by tests/test_kernels.py."""
from repro.kernels import ops, ref
