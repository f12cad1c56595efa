"""Model operations of the window's work over (window x the peak of the
unit the path's linears run on: int8 on W8A8, bf16 on bf16), in %:
linears and attention over the live context for every prompt and decode
token the window's calls took in, the head for every token they emitted
(bench/costs.py)."""
import numpy as np

from bench import costs


def read(rec):
    if not rec.calls or rec.peaks is None:
        return None
    contexts = np.concatenate([c["pos"][c["pos"] >= 0] + 1
                               for c in rec.calls])
    ops = costs.model_ops(rec.model, contexts, rec.tokens)
    span = rec.window[1] - rec.window[0]
    return 100.0 * ops / (span * costs.compute_peak(rec.peaks, rec.path))
