"""The yardstick's arithmetic: chip peaks and the operations and bytes a
call needs, from the algorithm's shapes (never from a compiler's count).

Peaks: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

from bench.model import head_dim

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def linears(m: dict, groups: int):
    """The int8 linears of one layer on the W8A8 path, in call order:
    (name, K, N, input groups, output bytes per element, extra f32 input
    read per row). Projections and the down projection take per-tensor
    inputs (``int8_matmul``); gate and up take PEG groups
    (``int8_matmul_peg``). Up emits f32, gate reads it (``mul``) and emits
    requantized int8; the others emit f32."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    qd, kvd = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return [("wq", d, qd, 1, 4, 0), ("wk", d, kvd, 1, 4, 0),
            ("wv", d, kvd, 1, 4, 0), ("wo", qd, d, 1, 4, 0),
            ("w_up", d, f, groups, 4, 0), ("w_gate", d, f, groups, 1, f),
            ("w_down", f, d, 1, 4, 0)]


def matmul_cost(m: dict, groups: int, rows: int):
    """(ops, bytes) of every int8 linear of every layer for one call over
    ``rows`` input rows: 2 M K N operations; bytes read and written once
    each (int8 weight, int8 input, output, the f32 ``mul`` operand and
    the int32 per-group column sums). Block padding is not counted."""
    ops = nbytes = 0
    for _, k, n, g, out_b, extra in linears(m, groups):
        ops += 2 * rows * k * n
        nbytes += k * n + rows * k + rows * n * out_b + rows * extra * 4 \
            + g * n * 4
    L = m["num_layers"]
    return ops * L, nbytes * L


def compute_peak(peaks: dict, path: str) -> float:
    """Operations per second of the unit the path's linears run on: int8
    on the W8A8 path, bf16 on the bf16 path."""
    return peaks["int8_ops"] if path == "w8a8" else peaks["bf16_flops"]


def kv_token_bytes(m: dict, kv_bits: int = 8) -> int:
    """Bytes the paged decode kernel reads for one cached token in one
    layer: with an int8 cache, int8 k and v, an f32 scale per kv head for
    each and an int32 position; with a bf16 cache, bf16 k and v (the
    kernel derives positions from the block table)."""
    kv, hd = m["num_kv_heads"], head_dim(m)
    if kv_bits == 16:
        return 2 * kv * hd * 2
    return 2 * kv * hd + 2 * kv * 4 + 4


def attend_cost(m: dict, contexts, kv_bits: int = 8):
    """(ops, bytes) of the paged decode attention over every layer for
    lanes attending over ``contexts`` keys each: QK and PV are 4 n H hd
    operations; the live cache is read once."""
    H, hd, L = m["num_heads"], head_dim(m), m["num_layers"]
    n = sum(contexts)
    return 4 * n * H * hd * L, n * kv_token_bytes(m, kv_bits) * L


def model_ops(m: dict, new_tokens: int, keys_seen: int, emitted: int):
    """Model operations of a call: 2 x the layers' linear parameters for
    each of ``new_tokens`` live tokens, attention over the ``keys_seen``
    (query, key) pairs summed over those tokens (QK and PV, 4 H hd per
    pair and layer), and the head for the ``emitted`` tokens only."""
    d, f, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], head_dim(m)
    body = 2 * L * (d * H * hd * 2 + 2 * d * KV * hd + 3 * d * f)
    return body * new_tokens + 4 * H * hd * L * keys_seen \
        + 2 * d * v * emitted


def packed_weight_bytes(m: dict, groups: int) -> int:
    """HBM bytes of the parameters the deploy packing leaves: per layer,
    each linear's int8 payload, f32 scale and int32 per-group column sums,
    and the two bf16 norm vectors; the bf16 embedding, final norm and,
    untied, the bf16 head."""
    L, d, v = m["num_layers"], m["d_model"], m["vocab_size"]
    per_layer = sum(k * n + 4 + g * n * 4
                    for _, k, n, g, _, _ in linears(m, groups)) + 2 * d * 2
    total = L * per_layer + v * d * 2 + d * 2
    if not m["tie_embeddings"]:
        total += d * v * 2
    return total
