"""Median time to first token (ms) of the requests due in the window,
from their due time; one with no first token by the close counts as
(close - due). A window holds a few such requests at today's speed (3 in
danube3-4b-w8a8.chat), too few for an end-to-end median, so it is read
here."""
import numpy as np


def read(rec):
    return 1e3 * float(np.median(rec.ttft)) if rec.ttft else None
