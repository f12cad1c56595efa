"""A clean run passes the check, and the control reads far above the
program, at a size the CPU holds (toy widths, Pallas kernels in interpret
mode). Under a W8A8 cell the control is the plain reference on int4 grids
in the program's place; under a bf16 cell, the program's own W8A8 path."""
import io

from bench import control
from bench.tests import tiny


def test_clean_run_passes_and_control_reads_above():
    r = control.measure(tiny.cell(), 11, 4.0, t_start=0.0,
                        require_tpu=False, log=io.StringIO())
    limit = tiny.LIMITS["w8a8"]
    assert r["correct"] is True
    assert r["checked"] >= tiny.PARAMS["check"]["min_checked"]
    assert r["program"] <= limit
    # int4 is the step below the configuration's int8 (the w4a8 reading,
    # int4 weights alone, is reported beside it, not held to the limit)
    assert r["control"]["w4a4"] > limit


def test_bf16_clean_run_passes_and_the_w8a8_path_reads_above():
    limit = tiny.LIMITS["bf16"]
    clean = control.measure(tiny.cell("bf16"), 11, 4.0, t_start=0.0,
                            require_tpu=False, log=io.StringIO())
    assert clean["correct"] is True
    assert clean["program"] <= limit
    # the bf16 cell served by the program's W8A8 path, held to its limit
    int8 = tiny.cell("bf16", conf=tiny.cell("w8a8")["conf"])
    r = control.measure(int8, 11, 4.0, t_start=0.0, require_tpu=False,
                        log=io.StringIO())
    assert r["correct"] is False
    assert r["program"] > limit
