"""Share (%) of lane x decode-step cells that held a live request."""
import numpy as np


def read(rec):
    live = [float(np.mean(c["pos"] >= 0)) for c in rec.calls
            if c["kind"] == "decode"]
    return 100.0 * float(np.mean(live)) if live else None
