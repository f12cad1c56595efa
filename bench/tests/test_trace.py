"""The trace reduction (bench/trace.py) on a synthetic trace with known
answers, and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _synthetic():
    # window: 1.0 s from t = 1e9 ns; two device planes
    ms = 1_000_000
    return {"device": {
        "/device:TPU:0": [["fusion.1", 900 * ms, 200 * ms],     # clipped
                          ["int8_matmul", 1200 * ms, 100 * ms],
                          ["int8_matmul", 1250 * ms, 100 * ms],  # overlap
                          ["other", 1900 * ms, 300 * ms]],       # clipped
        "/device:TPU:1": [["int8_matmul", 1000 * ms, 500 * ms]]},
        "host": [["bench:window", 1000 * ms, 1000 * ms],
                 ["bench:decode", 1000 * ms, 400 * ms],
                 ["bench:host", 1400 * ms, 600 * ms]]}


def test_busy_union_idle_and_kernel_sums():
    red = trace.reduce(_synthetic(), 1.0)
    assert red["window_s"] == pytest.approx(1.0)
    # plane 0 busy: [1.0,1.1] + [1.2,1.35] + [1.9,2.0] = 0.35; plane 1: 0.5
    assert red["busy_s"] == pytest.approx((0.35 + 0.5) / 2)
    # per-op sums are averaged over the planes, overlaps counted per op
    assert trace.kernel_seconds(red, "int8_matmul") == pytest.approx(
        (0.2 + 0.5) / 2)
    assert red["op_s"]["fusion.1"] == pytest.approx(0.1 / 2)
    # the longest gap, plane 0's [1.35, 1.9], lies in the host span
    assert red["idle_gaps"][0][0] == "host"
    assert red["idle_gaps"][0][1] == pytest.approx(0.55)
    total_idle = sum(red["idle_by_host_s"].values())
    assert total_idle == pytest.approx(1.0 - red["busy_s"])


def test_no_window_span_is_an_error():
    ex = _synthetic()
    ex["host"] = ex["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(ex, 1.0)


def _sweep_busy(ops, lo, hi):
    """Busy ns by a sweep over +1/-1 boundary events (another algorithm
    than the reduction's interval merge)."""
    ev = []
    for _, start, dur in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            ev += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(ev, key=lambda e: (e[0], -e[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("path", sorted(DATA.glob("trace_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    ex, seconds = rec["trace"], rec["seconds"]
    red = trace.reduce(ex, seconds)
    lo, hi = trace.window_of(ex, seconds)
    (ops,) = ex["device"].values()
    ops = [op for op in ops if not trace.CONTAINER.match(op[0])]
    assert red["busy_s"] == pytest.approx(_sweep_busy(ops, lo, hi) / 1e9,
                                          rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    # every op's clipped time lands in exactly one name's sum
    clipped = sum(max(0, min(s + d, hi) - max(s, lo)) for _, s, d in ops)
    assert sum(red["op_s"].values()) == pytest.approx(clipped / 1e9,
                                                      rel=1e-9)
    # busy + idle = window; the longest gap is no longer than the idle sum
    idle = sum(red["idle_by_host_s"].values())
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-9)
    assert red["idle_gaps"][0][1] <= idle + 1e-12
