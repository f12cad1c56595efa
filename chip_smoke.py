"""Chip smoke test: serve h2o-danube3-4b at its published widths on a TPU.

Drives the README's main serving path — engine -> continuous scheduler ->
paged KV cache -> deploy kernels — through ``repro.launch.serve.main``, all
phases in this one process (a chip belongs to one process at a time), with
random weights made from a seed:

  (a) bf16 weights, continuous scheduler, paged KV cache (blocks of 16),
      chunked prefill (128): 8 requests x 256 prompt tokens x 32 new
      tokens on 8 slots, max-len 1024;
  (b) the same traffic with W8A8 PTQ deployed on the int8 kernels and an
      int8 KV cache (``--quantize --deploy-int8 --kv-bits 8``).

Each phase is gated by serve's own checks: every request gets its tokens;
the served requests' cached prefill + decode logits match an un-cached
forward; for (b) every per-layer linear is packed, the int8 cache is
engaged, and int8 == fake-quant and int8 cache == float cache within their
tolerances. Any failed check exits non-zero without the result line.

  python chip_smoke.py                # (a) and (b) on one chip
  python chip_smoke.py --four-chips   # (a) at --tp 4 against --tp 1 only

The last line of stdout is the result, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``. The tokens/s printed earlier is a smoke figure, not a
metric: one short run, nothing held steady.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = ["--arch", "h2o-danube3-4b", "--scheduler", "continuous",
           "--paged-kv", "--block-size", "16", "--prefill-chunk", "128",
           "--requests", "8", "--prompt-len", "256", "--new-tokens", "32",
           "--batch-slots", "8", "--max-len", "1024", "--warmup", "--verify"]
PHASES = {"a (bf16)": TRAFFIC,
          "b (int8 deploy, int8 kv)": TRAFFIC + ["--quantize", "--deploy-int8",
                                                 "--kv-bits", "8"]}


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def _peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is None:
        return "not reported"
    return f"{peak} B ({peak / 2**30:.2f} GiB)"


def _run_phase(serve, jax, name, argv, compile_s):
    before = compile_s[0]
    t0 = time.perf_counter()
    print(f"[chip_smoke] phase {name}: serve {' '.join(argv)}", flush=True)
    stats = serve.main(argv)
    print(f"[chip_smoke] phase {name}: {time.perf_counter() - t0:.1f} s "
          f"wall, {compile_s[0] - before:.1f} s compiling; smoke figure "
          f"(not a metric): {stats.tokens_per_s:.1f} tok/s after warm-up; "
          f"peak_bytes_in_use so far {_peak_bytes(jax)}", flush=True)
    gc.collect()
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase (a) at --tp 4 over four chips against "
                         "--tp 1 on one of them, and compare their logits")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        from repro.launch import serve
    except ImportError as e:
        return _fail(f"cannot import the repository's serving code ({e})")

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX runs on {devices[0].platform}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        return _fail(f"{need} chips needed, {len(devices)} visible")
    print(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}",
          flush=True)
    compile_s = [0.0]

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    try:
        if args.four_chips:
            logits = {tp: _run_phase(serve, jax, f"a (bf16) --tp {tp}",
                                     PHASES["a (bf16)"] + ["--tp", str(tp)],
                                     compile_s).replayed_logits
                      for tp in (4, 1)}
            # serve's bf16 bound: the sharded matmuls sum their partial
            # products in another order, and bf16 activations round the
            # difference into flips that compound over the layers, as
            # between cached and un-cached logits
            serve.gate("chip_smoke", f"--tp 4 vs --tp 1 replayed logits "
                       f"{logits[1].shape}",
                       serve.rel_errors(logits[1], logits[4]), serve.TOL_BF16)
        else:
            for name, phase_argv in PHASES.items():
                _run_phase(serve, jax, name, phase_argv, compile_s)
    except SystemExit as e:
        if e.code in (0, None):
            return _fail("serve exited early")
        return _fail(f"serve check failed: {e.code}")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
