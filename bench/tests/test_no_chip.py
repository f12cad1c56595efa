"""A run with no TPU fails and prints no result."""
import json
import os
import subprocess
import sys

from bench.tests import tiny


def test_no_tpu_exits_non_zero_with_no_result():
    bench = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         bench["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
    assert "no TPU" in p.stderr
