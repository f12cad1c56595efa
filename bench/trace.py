"""Reduction of a profiler trace to the numbers the benchmark reports.

``extract`` reads the ``.xplane.pb`` a traced run writes into a small
dict: the device operations (line "XLA Ops" of each TPU plane) and the
host spans the harness annotates (names starting with ``bench:``), all on
the trace's clock in nanoseconds. ``reduce`` works on that dict alone, so
a recorded one (bench/testdata/) checks it without a chip.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
OPS_LINE = "XLA Ops"
# control flow whose event spans the ops of its body: not an op of its own
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
HOST_PREFIX = "bench:"


def op_name(event_name: str) -> str:
    """The HLO instruction's name (``int8_matmul_peg.29``) of a TPU op
    event, whose name is the instruction's whole text."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def extract(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[op_name(ev.name), ev.start_ns, ev.duration_ns]
                            for ev in line.events]
            out["device"][plane.name] = ops
        else:
            for line in plane.lines:
                out["host"] += [[ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events
                                if ev.name.startswith(HOST_PREFIX)]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def window_of(ex: dict, seconds: float) -> Tuple[float, float]:
    """[open, close] in trace ns: the start of the ``bench:window`` span
    and ``seconds`` after it."""
    spans = [s for s in ex["host"] if s[0] == HOST_PREFIX + "window"]
    if not spans:
        raise ValueError("trace holds no bench:window span")
    t0 = spans[0][1]
    return t0, t0 + seconds * 1e9


def reduce(ex: dict, seconds: float, top: int = 10) -> dict:
    """Busy time (union of device-op intervals, averaged over the device
    planes), window length, seconds per op name, and the longest idle gaps
    named by the host span they fall in, all clipped to the window.
    Control-flow events (``CONTAINER``) only enclose their body's ops and
    are left out."""
    lo, hi = window_of(ex, seconds)
    busy, per_op, gaps = [], {}, []
    host = sorted((s[1], s[1] + s[2], s[0]) for s in ex["host"]
                  if s[0] != HOST_PREFIX + "window")
    for ops in ex["device"].values():
        iv = []
        for name, start, dur in ops:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a or CONTAINER.match(name):
                continue
            iv.append((a, b))
            per_op[name] = per_op.get(name, 0.0) + (b - a) / 1e9
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_at(host, (a + b) / 2)))
    n = max(len(busy), 1)
    by_host: Dict[str, float] = {}
    for g, name in gaps:
        by_host[name] = by_host.get(name, 0.0) + g / 1e9 / n
    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / n for k, v in per_op.items()},
        "idle_by_host_s": by_host,
        "device_ops": sorted(([k, v / n] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[name, g / 1e9] for g, name in
                      sorted(gaps, key=lambda x: -x[0])[:top]],
    }


def _host_at(host, t) -> str:
    """Name of the innermost (latest-starting) host span holding ``t``."""
    name = "host:other"
    for a, b, n in host:
        if a > t:
            break
        if b >= t:
            name = n[len(HOST_PREFIX):]
    return name


def kernel_seconds(red: dict, pattern: str) -> float:
    """Device seconds in the window of the ops whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in red["op_s"].items() if rx.search(k))
