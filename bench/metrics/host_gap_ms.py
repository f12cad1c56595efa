"""Mean host time (ms) from the return of one model call to the start of
the next: scheduler work and the greedy read-back."""


def read(rec):
    gaps = [b["t0"] - a["t1"] for a, b in zip(rec.calls, rec.calls[1:])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
