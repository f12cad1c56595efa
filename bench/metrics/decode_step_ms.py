"""Mean decode call (ms), host clock, ending when its outputs are ready."""


def read(rec):
    d = [c["t1"] - c["t0"] for c in rec.calls if c["kind"] == "decode"]
    return 1e3 * sum(d) / len(d) if d else None
