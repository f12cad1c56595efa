"""A ``--trace 1`` run end to end on the CPU (toy widths): the host-clock
per-layer readers find their calls, and the result carries the traced
window and the breakdown. (Device-trace metrics need the chip.)"""
import io

from bench import harness
from bench.tests import tiny


def test_traced_run_reports_per_layer_metrics():
    cell = tiny.cell()
    r = harness.run(cell, 7, 4.0, True, t_start=0.0, require_tpu=False,
                    log=io.StringIO())
    got = r["metrics"]
    for name in ("compile_s", "lane_occupancy", "host_gap_ms",
                 "decode_step_ms", "chunk_step_ms", "ttft_p50_window_ms"):
        assert name in got and got[name]["value"] >= 0, name
    assert 0 < got["lane_occupancy"]["value"] <= 100
    assert "gen_tokens_per_s" not in got       # end-to-end: untraced runs
    assert r["device"]["window_s"] == 4.0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(r)[-1] == "checks"
