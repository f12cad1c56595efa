"""Readings that set the check's limit: over several seeds, the widest gap
of the program's served tokens (the lower reading) and of the control, the
plain reference computed on the grids of the step below the
configuration's precision in the program's place, on the same prompts and
served tokens (the upper reading). Runs at the cell's own size with a
short window:

  python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

``--config <name>`` serves the cell with another configuration of
bench/configs/ in the program's place, such as the program's own W8A8
path under a bf16 cell: its widest gap is then a control reading too.
Prints one JSON line per seed. Not part of the benchmark's runs.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

# (weight bits, input bits) of the control's linears, by the
# configuration's precision path: int4 is the step below int8 (int4
# weights with int8 inputs is the repository's own --weight-bits 4 path,
# the step that tempts); int8 is the step below bf16
CONTROLS = {"w8a8": {"w4a4": (4, 4), "w4a8": (4, 8)},
            "bf16": {"w8a8": (8, 8)}}


def measure(cell, seed, seconds, *, t_start, require_tpu=True, **kw):
    """One seed: the run's result and the control's gaps beside it."""
    from bench import harness
    readings = {}

    controls = CONTROLS[cell["conf"]["precision"]["path"]]

    def control(m, weights, seqs, max_len):
        for name, bits in controls.items():
            g = harness.gaps_against_reference(m, weights, seqs, max_len,
                                               bits=bits)
            readings[name] = float(max(g)) if g else None
    result = harness.run(cell, seed, seconds, False, t_start=t_start,
                         require_tpu=require_tpu, extra_check=control, **kw)
    return {"seed": seed, "config": cell["conf"]["name"],
            "correct": result["correct"],
            "program": result["checks"]["max_gap_sd"]["value"],
            "checked": result["checks"]["served_tokens_checked"]["value"],
            "control": readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--config", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    if args.config:
        from bench import model as bmodel
        cell["conf"] = bmodel.load_config(ROOT / "bench" / "configs"
                                          / f"{args.config}.json")
    t = T_START
    for s in args.seeds.split(","):
        print(json.dumps(measure(cell, int(s), args.seconds, t_start=t)),
              flush=True)
        gc.collect()
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
