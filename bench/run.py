"""Run one benchmark cell once and print its result as the last line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (from a profiler trace and the
traced run's host spans). Without a TPU, or with fewer chips than the
cell needs, it exits non-zero and prints no result. JAX's persistent
compilation cache is kept in ``.jax_cache/`` at the checkout's root
unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
