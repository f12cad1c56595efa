"""The yardstick's arithmetic: chip peaks and the operations and bytes a
call needs, from the algorithm's shapes (never from a compiler's count),
layer by layer as the configuration's architecture describes its layers
(``layers`` in bench/archs/).

Peaks: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

import numpy as np

from bench.model import arch

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


def matmul_cost(m: dict, groups: int, rows: int):
    """(ops, bytes) of every int8 linear of every layer for one call over
    ``rows`` input rows: 2 M K N operations; bytes read and written once
    each (int8 weight, int8 input, output, the f32 ``mul`` operand and
    the int32 per-group column sums, ``groups`` of them on a PEG-grouped
    input). Block padding is not counted."""
    ops = nbytes = 0
    for layer in arch(m).layers(m):
        for _, k, n, peg, out_b, extra in layer["linears"]:
            g = groups if peg else 1
            ops += 2 * rows * k * n
            nbytes += k * n + rows * k + rows * n * out_b \
                + rows * extra * 4 + g * n * 4
    return ops, nbytes


def compute_peak(peaks: dict, path: str) -> float:
    """Operations per second of the unit the path's linears run on: int8
    on the W8A8 path, bf16 on the bf16 path."""
    return peaks["int8_ops"] if path == "w8a8" else peaks["bf16_flops"]


def kv_token_bytes(layer: dict, kv_bits: int = 8) -> int:
    """Bytes the paged decode kernel reads for one cached token in one
    layer (an entry of ``layers``): with an int8 cache, int8 k and v, an
    f32 scale per kv head for each and an int32 position; with a bf16
    cache, bf16 k and v (the kernel derives positions from the block
    table)."""
    kv, hd = layer["kv_heads"], layer["head_dim"]
    if kv_bits == 16:
        return 2 * kv * hd * 2
    return 2 * kv * hd + 2 * kv * 4 + 4


def _keys_read(contexts, window) -> int:
    """Keys a layer attends over for queries with these ``contexts``
    (keys up to and including each query): all of them, or with a
    sliding window the last ``window``."""
    c = np.asarray(contexts, np.int64)
    return int(np.minimum(c, window).sum() if window else c.sum())


def attend_cost(m: dict, contexts, kv_bits: int = 8):
    """(ops, bytes) of the paged decode attention over every layer for
    lanes attending over ``contexts`` keys each (within each layer's
    window): QK and PV are 4 n H hd operations; the live cache is read
    once."""
    ops = nbytes = 0
    for layer in arch(m).layers(m):
        n = _keys_read(contexts, layer["window"])
        ops += 4 * n * layer["heads"] * layer["head_dim"]
        nbytes += n * kv_token_bytes(layer, kv_bits)
    return ops, nbytes


def model_ops(m: dict, contexts, emitted: int):
    """Model operations of a call: 2 x each layer's linear multiply-adds
    (K N of each of its ``linears``) for every live token taken in, one
    per entry of ``contexts`` (its keys up to and including itself),
    attention over those keys within each layer's window (QK and PV, 4 H
    hd per (query, key) pair and layer), and the head for the ``emitted``
    tokens only."""
    ops = 2 * m["d_model"] * m["vocab_size"] * emitted
    for layer in arch(m).layers(m):
        macs = sum(k * n for _, k, n, *_ in layer["linears"])
        ops += 2 * macs * len(contexts) \
            + 4 * layer["heads"] * layer["head_dim"] \
            * _keys_read(contexts, layer["window"])
    return ops
