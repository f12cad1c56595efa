"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

The system under test is the repository's serving path on the precision
path the configuration names (``precision.path``): ``w8a8`` is built as
``launch/serve.py --quantize --deploy-int8 --kv-bits 8 --paged-kv
--prefill-chunk N --scheduler continuous`` builds it (calibration,
``build_deploy`` packing, ``serving_steps``), ``bf16`` as the same command
without the quantization flags (bf16 weights and paged bf16 KV cache).
Either is driven through ``repro.runtime.serve_loop.serve``. The weights
are the benchmark's (bench/model.py); the traffic is a closed loop
(bench/window.py) over a request list from bench/traffic.py.
"""
from __future__ import annotations

import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from bench import costs, model as bmodel, reference, traffic
from bench import trace as btrace
from bench.window import Stamps, Window, WindowClosed, percentile, summarize

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# after the close the loop runs on (untimed) until the finished requests
# hold the check's served tokens, for at most a minute
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a cell names, found by name: its BENCHMARK.json entry,
    configuration, traffic mix, cell parameters and metric entries."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])

    def here(metric):
        return "workloads" not in metric or name in metric["workloads"]
    return {
        "workload": wl,
        "conf": bmodel.load_config(root / conf_entry["file"]),
        "mix": traffic.load_mix(BENCH / "traffic" / f"{wl['traffic']}.json"),
        "params": json.loads((BENCH / "cells" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, {len(devices)} visible")
    return devices


class Recorder:
    """Wraps the step callables handed to ``serve``. Untraced it only tells
    the window that a step is due, which closes it once its time is up and
    ends the loop after the drain. Traced it also waits for each
    call's outputs, annotates the call on the profiler's clock, and keeps
    its host times and the live positions it was given."""

    def __init__(self, window: Window, traced: bool):
        self.window, self.traced = window, traced
        self.calls = []
        self._between = None

    def wrap(self, kind: str, fn: Callable) -> Callable:
        import jax

        def call(params, tokens, positions, *rest):
            self.window.check(step=True)
            if not self.traced:
                return fn(params, tokens, positions, *rest)
            self._end_between()
            t0 = self.window.clock()
            with jax.profiler.TraceAnnotation(f"bench:{kind}"):
                out = jax.block_until_ready(
                    fn(params, tokens, positions, *rest))
            t1 = self.window.clock()
            self.calls.append({"kind": kind, "t0": t0, "t1": t1,
                               "shape": tuple(positions.shape),
                               "pos": np.asarray(positions)})
            self._between = jax.profiler.TraceAnnotation("bench:host")
            self._between.__enter__()
            return out
        return call

    def _end_between(self):
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None


class CompileLog:
    """Host times of JAX's tracing and compilation events
    (``jax.monitoring``), to report set-up compile time and to prove that
    nothing compiles inside the window."""

    def __init__(self, clock=time.perf_counter):
        self.clock, self.events = clock, []

    def _on(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.events.append((self.clock(), event, duration))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def backend_seconds(self, before: float) -> float:
        return sum(d for t, e, d in self.events if t < before
                   and e == "/jax/core/compile/backend_compile_duration")

    def lowered_inside(self, lo: float, hi: float):
        """Lowerings and compilations between ``lo`` and ``hi`` (a retrace
        that finds its program already compiled does neither)."""
        return [e for t, e, _ in self.events if lo <= t <= hi and e in (
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")]


def build_program(conf: dict, cp: dict, seed: int):
    """The served program for ``seed``: the benchmark's weights handed to
    the program, on the bf16 path as they are, on the W8A8 path calibrated
    and packed as ``launch/serve.py`` does."""
    import jax.numpy as jnp
    from repro.core import build_deploy, deploy
    from repro.launch import serve as lserve
    from repro.launch.mesh import make_serving_mesh
    from repro.parallel import make_dist

    m, prec = conf["model"], conf["precision"]
    cfg = bmodel.arch(m).model_config(conf)
    dist = make_dist(make_serving_mesh(1))
    shardings = bmodel.weight_shardings(m, dist)
    params = bmodel.weights(m, seed, shardings)
    if prec["path"] == "bf16":
        _, _, decode, chunk = lserve.serving_steps(cfg, dist, None)
        return SimpleNamespace(cfg=cfg, params=params, decode=decode,
                               chunk=chunk, dtype=jnp.bfloat16, kv_bits=16,
                               shardings=shardings)
    if prec["path"] != "w8a8":
        raise ValueError(f"unknown precision path {prec['path']!r}")
    args = lserve.build_parser().parse_args([
        "--arch", conf["arch"], "--scheduler", "continuous", "--quantize",
        "--deploy-int8", "--kv-bits", str(prec["kv_bits"]),
        "--paged-kv", "--block-size", str(cp["block_size"]),
        "--prefill-chunk", str(cp["prefill_chunk"]),
        "--batch-slots", str(cp["lanes"]), "--max-len", str(cp["max_len"]),
        "--num-blocks", str(cp["num_blocks"]),
        "--prompt-len", str(cp["calibration_len"])])
    pol, state = lserve.calibrate(args, cfg, params)
    params, acts = build_deploy(cfg, params, pol, state)
    n_packed, n_total = deploy.count_packed(params)
    if n_packed != n_total:
        raise RuntimeError(f"{n_total - n_packed} of {n_total} linears left "
                           f"unpacked")
    ctx_factory = lserve.deploy_ctx_factory(pol, state, acts)
    _, _, decode, chunk = lserve.serving_steps(cfg, dist, ctx_factory)
    return SimpleNamespace(cfg=cfg, params=params, decode=decode, chunk=chunk,
                           dtype=jnp.bfloat16, kv_bits=args.kv_bits,
                           shardings=shardings)


def serve_requests(prog, cp: dict, requests, decode, chunk):
    import jax
    from repro.models import transformer as tfm
    from repro.runtime import BlockPool, serve
    cfg, bs, L = prog.cfg, cp["block_size"], cp["max_len"]
    pool = BlockPool(cp["num_blocks"], bs, cp["lanes"],
                     tfm.paged_lane_blocks(cfg, L, bs))

    def init_cache(b):
        # one program on the device: built eagerly, the layers' arenas and
        # their stacked copies are on the chip at once
        return jax.jit(lambda: tfm.init_cache(
            cfg, b, L, dtype=prog.dtype, kv_bits=prog.kv_bits, paged=True,
            block_size=bs, num_blocks=cp["num_blocks"], mapped=False))()
    return serve(None, None, decode, init_cache, prog.params, requests,
                 scheduler="continuous", batch_slots=cp["lanes"], max_len=L,
                 block_pool=pool, chunk_step=chunk,
                 prefill_chunk=cp["prefill_chunk"],
                 write_caps=tfm.attn_write_caps(cfg, L, bs),
                 ring_tokens=tfm.paged_ring_tokens(cfg, L, bs))


def warm_up(prog, cp: dict, vocab: int):
    """Compile what the window runs, on a throw-away request list at the
    cell's lanes and chunk width: the chunk step (first and later chunks),
    the greedy read-back, and the decode step with each placement of the
    block table it is handed (uploaded afresh after the pool grew a lane,
    or passed on from the previous step's output): prompts of one chunk
    plus one block, so the first decode grows a block and the second
    does not."""
    from repro.runtime import Request
    rng = np.random.default_rng(0)
    n = cp["prefill_chunk"] + cp["block_size"]
    reqs = [Request(rid=i, prompt=rng.integers(10, vocab, n).astype(np.int32),
                    max_new_tokens=3) for i in range(cp["lanes"])]
    serve_requests(prog, cp, reqs, prog.decode, prog.chunk)


def sample_for_check(times, quotas, clients: int, seed: int,
                     target_tokens: int, max_seqs: int, lengths):
    """Requests the check replays: the longest finished one (prompt plus
    output) and others drawn from the seed until ``target_tokens`` served
    tokens, favouring requests admitted into freed lanes (index >=
    clients)."""
    done = [k for k, (ts, q) in enumerate(zip(times, quotas)) if len(ts) == q]
    if not done:
        return []
    rng = np.random.default_rng([seed, 1])
    longest = max(done, key=lambda k: (lengths[k] + quotas[k], -k))
    later = [k for k in done if k >= clients and k != longest]
    first = [k for k in done if k < clients and k != longest]
    order = [longest] + list(rng.permutation(later).astype(int)) \
        + list(rng.permutation(first).astype(int))
    out, n = [], 0
    for k in order:
        if n >= target_tokens or len(out) >= max_seqs:
            break
        out.append(k)
        n += quotas[k]
    return out


def gaps_against_reference(m: dict, weights, seqs, max_len: int,
                           bits=None):
    """For each (prompt, tokens) in ``seqs``: the reference's logits over
    prompt + tokens (padded to ``max_len``); at each position that
    predicts one of ``tokens``, the gap between the reference's best logit
    and that of the token the program served (``bits`` None) or that the
    reference at ``bits`` puts first, in units of the reference logits'
    standard deviation there. Returns the gaps."""
    import jax.numpy as jnp
    out = []
    for prompt, toks in seqs:
        full = np.zeros(max_len, np.int32)
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        full[:len(seq)] = seq
        P, N = len(prompt), len(toks)
        ref = reference.logits(m, weights, full)[P - 1:P - 1 + N]
        if bits is None:
            chosen = jnp.asarray(toks, jnp.int32)
        else:
            low = reference.logits(m, weights, full, bits)[P - 1:P - 1 + N]
            chosen = jnp.argmax(low, axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        out += list(np.asarray((best - got) / jnp.std(ref, axis=-1)))
    return out


def _load_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def run(cell: dict, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True,
        fault: Optional[Callable] = None, log=sys.stderr,
        keep_trace: Optional[Callable] = None,
        extra_check: Optional[Callable] = None) -> dict:
    """One run; returns the result dict (the line ``run.py`` prints).
    ``fault`` (tests only) wraps each step callable — ``fault(kind, fn)``
    — underneath the harness; ``keep_trace`` receives the extracted
    trace; ``extra_check(model, weights, seqs, max_len)`` runs beside the
    check on the same weights and sampled requests (bench/control.py)."""
    import jax

    wl, conf, mix, cp = (cell["workload"], cell["conf"], cell["mix"],
                         cell["params"])
    devices = check_devices(wl["chips"]) if require_tpu else jax.devices()
    dev = devices[0]
    peaks = costs.peaks_for(dev.device_kind) if require_tpu else None
    m = conf["model"]
    if mix["clients"] != cp["lanes"]:
        raise ValueError("a closed loop of C clients runs on C lanes")

    def say(msg):
        print(f"[bench {time.perf_counter() - t_start:7.1f}s] {msg}",
              file=log, flush=True)

    def setup():
        prog = build_program(conf, cp, seed)
        say("program built (weights, calibration, packing)")
        warm_up(prog, cp, m["vocab_size"])
        say("warmed up")
        return prog
    with CompileLog() as compiles:
        out = _measure(cell, seed, seconds, traced, setup, compiles, t_start,
                       peaks, dev, say, fault, keep_trace, extra_check)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=log)
    return out


def _measure(cell, seed, seconds, traced, setup, compiles, t_start, peaks,
             dev, say, fault, keep_trace, extra_check):
    """Set-up, the window, the check and the result line of ``run``. The
    program built by ``setup()`` is referenced here alone, so it is freed
    before the reference runs."""
    import jax

    wl, conf, mix, cp = (cell["workload"], cell["conf"], cell["mix"],
                         cell["params"])
    m = conf["model"]
    prog = setup()

    chk = cp["check"]
    window = Window(mix["clients"], seconds,
                    drain_tokens=chk["served_tokens"], drain_s=DRAIN_S)
    rec = Recorder(window, traced)
    decode, chunk = prog.decode, prog.chunk
    if fault is not None:
        decode, chunk = fault("decode", decode), fault("chunk", chunk)
    decode, chunk = rec.wrap("decode", decode), rec.wrap("chunk", chunk)

    from repro.runtime import Request
    gen = traffic.generate(mix, m["vocab_size"], seed)
    requests = [Request(rid=k, prompt=p, max_new_tokens=o,
                        tokens_out=Stamps(window, k, o))
                for k, (p, o) in enumerate(gen)]
    trace_dir, win_span = None, []
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)

        def on_open(t):
            win_span.append(jax.profiler.TraceAnnotation("bench:window"))
            win_span[0].__enter__()

        def on_close():
            # the trace covers the window; the drain after it is untraced
            rec._end_between()
            win_span[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
        window.on_open, window.on_close = on_open, on_close
    window.start()
    try:
        serve_requests(prog, cp, requests, decode, chunk)
        raise RuntimeError("the request list ran dry before the window "
                           "closed")
    except WindowClosed:
        pass
    rec._end_between()
    setup_s = window.open - t_start
    say(f"window closed ({window.close - window.open:.2f} s, set-up "
        f"{setup_s:.1f} s); drained {window.clock() - window.close:.1f} s "
        f"more")
    late = compiles.lowered_inside(window.open, window.close)
    if late:
        raise RuntimeError(f"compiled inside the window: {late}")
    red = None
    if traced:
        ex = btrace.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if keep_trace is not None:
            keep_trace(ex)
        red = btrace.reduce(ex, seconds)
        del ex
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    times = [r.tokens_out.times for r in requests]
    quotas = [r.max_new_tokens for r in requests]
    served = [list(r.tokens_out) for r in requests]
    prompts = [r.prompt for r in requests]
    summ = summarize(times, quotas, mix["clients"], window.ramp_start,
                     window.open, window.close)
    shardings = prog.shardings
    del prog, decode, chunk, requests
    gc.collect()

    picked = sample_for_check(times, quotas, mix["clients"], seed,
                              chk["served_tokens"], chk["max_requests"],
                              [len(p) for p in prompts])
    weights = bmodel.arch(m).plain_view(m, bmodel.weights(m, seed,
                                                         shardings))
    seqs = [(prompts[k], np.asarray(served[k])) for k in picked]
    gaps = gaps_against_reference(m, weights, seqs, cp["max_len"])
    if extra_check is not None:
        extra_check(m, weights, seqs, cp["max_len"])
    del weights
    max_gap = float(max(gaps)) if gaps else float("inf")
    checks = {"max_gap_sd": {"value": max_gap, "limit": chk["max_gap_sd"]},
              "served_tokens_checked": {"value": len(gaps),
                                        "limit": chk["min_checked"]}}
    correct = (max_gap <= chk["max_gap_sd"]
               and len(gaps) >= chk["min_checked"])
    say(f"check: {len(picked)} requests, {len(gaps)} served tokens")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": wl["chips"], "memory_peak_bytes": peak}
    metrics = {}
    if not traced:
        values = {
            "gen_tokens_per_s": summ["tokens"] / summ["seconds"],
            "itl_p95_ms": percentile(summ["gaps"], 95) * 1e3
            if summ["gaps"] else None,
            "setup_s": setup_s,
        }
        for e in cell["end_to_end"]:
            if values.get(e["name"]) is not None:
                metrics[e["name"]] = {"value": values[e["name"]],
                                      "unit": e["unit"]}
    else:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        prec = conf["precision"]
        r = SimpleNamespace(model=m, path=prec["path"],
                            groups=prec.get("peg_groups"),
                            kv_bits=prec["kv_bits"], peaks=peaks,
                            calls=[c for c in rec.calls
                                   if window.open <= c["t0"]
                                   and c["t1"] <= window.close],
                            window=(window.open, window.close),
                            tokens=summ["tokens"], ttft=summ["ttft"],
                            compile_setup_s=compiles.backend_seconds(
                                window.ramp_start),
                            trace=red)
        for e in cell["per_layer"]:
            v = _load_reader(e["name"]).read(r)
            if v is not None:
                metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    out = {"correct": bool(correct), "attempted": summ["attempted"],
           "failed": 0, "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    return out
