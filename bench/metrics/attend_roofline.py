"""The paged decode attention's share (%) of its roofline: least time
of the window's decode calls (the live cache read once, bench/costs.py)
over the kernel's device time in the trace. The kernel is
``paged_int8_attend_decode`` over an int8 cache, ``paged_attend_decode``
over a bf16 one; the least time takes the operations at the peak of the
path's linears, which the decode kernels never beat."""
from bench import costs, trace

KERNELS = r"^paged_(int8_)?attend_decode"


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    device = trace.kernel_seconds(rec.trace, KERNELS)
    if device <= 0:
        return None
    peak = costs.compute_peak(rec.peaks, rec.path)
    least = 0.0
    for c in rec.calls:
        if c["kind"] != "decode":
            continue
        ctx = [int(p) + 1 for p in c["pos"].ravel() if p >= 0]
        ops, nbytes = costs.attend_cost(rec.model, ctx, rec.kv_bits)
        least += max(ops / peak, nbytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least / device
