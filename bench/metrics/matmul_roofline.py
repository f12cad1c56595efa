"""The int8 linears' share (%) of their roofline: the least time of the
window's ``int8_matmul``/``int8_matmul_peg`` work (bench/costs.py, from
shapes) over those kernels' device time in the trace."""
from bench import costs, trace

KERNELS = r"int8_matmul"


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    device = trace.kernel_seconds(rec.trace, KERNELS)
    if device <= 0:
        return None
    least = 0.0
    for c in rec.calls:
        rows = c["shape"][0] * c["shape"][1]
        ops, nbytes = costs.matmul_cost(rec.model, rec.groups, rows)
        least += max(ops / rec.peaks["int8_ops"],
                     nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / device
