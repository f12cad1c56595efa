"""Serving observability tests (runtime/telemetry.py + scheduler wiring).

Coverage:

* Lifecycle conservation on a preempting over-commit stub run: every
  admitted request either retires or is preempted-and-resumed (admissions
  == resumes + 1 per request), span preemption counts reconcile exactly
  with ServeStats.preemptions, and per-phase event counts reconcile with
  decode_steps / prefill_calls.
* Chrome-trace export schema: the JSON is Perfetto-loadable trace-event
  format (M/X/i phases, µs timestamps, lane thread naming, per-residency
  request spans that never dangle).
* MetricsLogger cadence (due/emit dedup per step), JSONL round-trip, and
  Prometheus text rendering.
* Quant-health: quantizer.telemetry_stats against an independent numpy
  oracle of the calibrated grid (exact clip counts, amax, cal_range),
  QuantCtx.act emitting the same counters from inside jit, and
  QuantHealth's stacked-scan fan-out + max/sum merge semantics.
* Recompile guard: serving with the tracer + metrics enabled reuses the
  exact jitted admit/decode executables traced by an untraced run (the
  traced step signatures are unchanged — tracing is host-side only).
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Mode, QuantCtx, w8a8_policy
from repro.core.quantizer import (fake_quant, params_from_range,
                                  telemetry_stats)
from repro.runtime import (BlockPool, MetricsLogger, QuantHealth, Request,
                           ServeTelemetry, Tracer, serve_continuous)
from serve_testlib import golden as _golden
from serve_testlib import next_arr as _next_arr
from serve_testlib import onehot as _onehot

pytestmark = [pytest.mark.serve, pytest.mark.obs]


class Stub:
    """Deterministic next_token = (2 * tok + 1) % VOCAB (see
    serve_testlib), with the over-commit swap hooks so preemption paths
    are reachable."""

    def init_cache(self, batch):
        return {"kv": jnp.zeros((batch, 4), jnp.float32)}

    def admit(self, tokens, positions, admit_mask, cache):
        return _onehot(_next_arr(tokens)), cache

    def chunk(self, tokens, positions, reset_mask, cache):
        return _onehot(_next_arr(tokens)), cache

    def decode(self, tokens, pos, cache):
        return _onehot(_next_arr(tokens)), cache

    def swap_out(self, cache, ids):
        return {"blocks": jnp.zeros((int(ids.shape[0]), 1), jnp.float32)}

    def swap_in(self, cache, ids, payload):
        return cache


def _serve_oc(reqs, tel, *, swap=False, num_blocks=6):
    """Over-commit stub serve sized so the pool is below worst-case demand
    (preemptions happen); mirrors tests/test_preemption.py."""
    m = Stub()
    pool = BlockPool(num_blocks, 4, 2, 8)
    stats = serve_continuous(
        m.admit, m.decode, m.init_cache, reqs, batch_slots=2,
        block_pool=pool, chunk_fn=m.chunk, prefill_chunk=4,
        over_commit=True,
        swap_out_fn=m.swap_out if swap else None,
        swap_in_fn=m.swap_in if swap else None,
        telemetry=tel)
    return stats


def _oc_reqs():
    return [Request(rid=i, prompt=np.full(4, 3 + i, np.int32),
                    max_new_tokens=12) for i in range(4)]


class TestLifecycleConservation:
    @pytest.mark.parametrize("swap", [False, True])
    def test_spans_reconcile_with_serve_stats(self, swap):
        tel = ServeTelemetry.create(trace=True)
        reqs = _oc_reqs()
        stats = _serve_oc(reqs, tel, swap=swap)
        for r in reqs:
            assert r.tokens_out == _golden(r.prompt, 12)
        assert stats.preemptions > 0
        spans = tel.tracer.request_spans()
        assert sorted(spans) == [r.rid for r in reqs]
        for rid, s in spans.items():
            # conservation: every admission either retires or is
            # preempted-and-resumed; the final residency retires
            assert s["retired"], f"rid {rid} never retired"
            assert len(s["admits"]) == s["resumes"] + 1
            assert s["preempts"] == s["resumes"]
            assert s["enqueue_ts"] is not None
            assert s["enqueue_ts"] <= s["admits"][0][0] <= s["retire_ts"]
            assert [t for t, _ in s["admits"]] == sorted(
                t for t, _ in s["admits"])
        assert sum(s["preempts"] for s in spans.values()) \
            == stats.preemptions
        # phase/event counts reconcile with the scheduler's own counters
        names = [e.name for e in tel.tracer.events]
        assert names.count("decode_batch") == stats.decode_steps
        assert names.count("admit") + names.count("chunk") \
            - sum(len(s["admits"]) - s["resumes"]
                  for s in spans.values()) == stats.prefill_calls
        assert names.count("enqueue") == len(reqs)
        assert names.count("retire") == len(reqs)
        mode = "swap" if swap else "drop"
        preempts = [e for e in tel.tracer.events if e.name == "preempt"]
        assert preempts and all(e.args["mode"] == mode for e in preempts)
        if swap:
            assert any(e.name == "swap_out" for e in tel.tracer.events)
            assert any(e.name == "swap_in" for e in tel.tracer.events)
        hist = tel.tracer.latency_histograms()
        assert hist["decode_batch"]["n"] == stats.decode_steps
        assert all(h["p50"] <= h["p95"] <= h["p99"] for h in hist.values())

    def test_tokens_identical_with_and_without_tracing(self):
        traced = _oc_reqs()
        plain = _oc_reqs()
        _serve_oc(traced, ServeTelemetry.create(trace=True,
                                                metrics_every=2))
        _serve_oc(plain, None)
        for a, b in zip(traced, plain):
            assert a.tokens_out == b.tokens_out


class TestChromeTraceSchema:
    def test_trace_is_valid_chrome_trace_json(self, tmp_path):
        tel = ServeTelemetry.create(trace=True)
        _serve_oc(_oc_reqs(), tel, swap=True)
        path = tmp_path / "trace.json"
        tel.tracer.dump(str(path))
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        assert evs
        for e in evs:
            assert {"name", "ph", "pid"} <= set(e)
            assert e["ph"] in ("M", "X", "i")
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] > 0
            if e["ph"] == "i":
                assert e["s"] == "t" and "ts" in e
        # lane tracks are named and every request span sits on one
        named = {e["tid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        spans = [e for e in evs
                 if e["ph"] == "X" and e["name"].startswith("req")]
        assert spans
        assert all(e["tid"] in named and e["tid"] >= 1 for e in spans)
        # phase durations live on the steps track (tid 0)
        assert any(e["ph"] == "X" and e["tid"] == 0 for e in evs)


class TestMetrics:
    def test_due_fires_once_per_step(self):
        m = MetricsLogger(every=4)
        assert not m.due(3)
        assert m.due(4)
        m.emit(4, {"queue_depth": 1})
        assert not m.due(4)                      # same step: no re-emit
        assert m.due(8)
        assert not MetricsLogger(every=0).due(0)

    def test_snapshots_jsonl_and_prometheus(self):
        tel = ServeTelemetry.create(metrics_every=2)
        stats = _serve_oc(_oc_reqs(), tel)
        snaps = tel.metrics.snapshots
        assert snaps
        steps = [s["step"] for s in snaps]
        assert steps == sorted(set(steps))
        assert all(s % 2 == 0 for s in steps)
        assert {"queue_depth", "resident_lanes", "blocks_free",
                "refcount_total", "preemptions",
                "prefix_hit_rate"} <= set(snaps[0])
        lines = tel.metrics.jsonl().splitlines()
        assert len(lines) == len(snaps)
        last = json.loads(lines[-1])
        # the final snapshot lands on the last step divisible by `every`,
        # so its counters are a prefix of the final totals
        assert 0 < last["tokens_generated"] <= stats.tokens_generated
        assert last["preemptions"] <= stats.preemptions
        prom = tel.metrics.prometheus_text()
        assert "# TYPE serve_queue_depth gauge" in prom
        assert f"serve_tokens_generated {last['tokens_generated']:g}" in prom


class TestQuantHealthOracle:
    def _grid(self):
        pol = w8a8_policy()
        cfg = pol.act_config("x")
        qp = params_from_range(jnp.float32(-1.0), jnp.float32(1.0), cfg)
        return pol, cfg, qp

    def _oracle(self, x, qp, cfg):
        """Independent numpy recomputation of the calibrated grid."""
        s = max(float(qp.scale), np.finfo(np.float32).tiny)
        z = float(qp.zero_point)
        t = np.round(np.asarray(x, np.float64) / s) + z
        clipped = int(np.sum((t < cfg.qmin) | (t > cfg.qmax)))
        rng = max(abs(s * (cfg.qmin - z)), abs(s * (cfg.qmax - z)))
        return clipped, float(np.max(np.abs(x))), rng

    def test_telemetry_stats_matches_numpy_oracle(self):
        pol, cfg, qp = self._grid()
        x = np.asarray(
            jax.random.normal(jax.random.PRNGKey(0), (512,))) * 2.0
        vec = np.asarray(telemetry_stats(jnp.asarray(x), qp, cfg))
        clipped, amax, rng = self._oracle(x, qp, cfg)
        assert clipped > 0                       # range [-1,1] vs 2-sigma
        assert int(vec[0]) == clipped
        assert int(vec[1]) == x.size
        assert vec[2] == pytest.approx(amax, rel=1e-6)
        assert vec[3] == pytest.approx(rng, rel=1e-6)

    def test_ctx_act_emits_counters_from_inside_jit(self):
        pol, cfg, qp = self._grid()

        def f(x):
            ctx = QuantCtx(policy=pol, mode=Mode.APPLY,
                           act_state={"x": qp})
            ctx.telemetry = {}
            y = ctx.act("x", x)
            return y, ctx.telemetry

        x = jax.random.normal(jax.random.PRNGKey(1), (256,)) * 2.0
        y, tel = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(fake_quant(x, qp, cfg)))
        vec = np.asarray(tel["x"])
        clipped, amax, rng = self._oracle(np.asarray(x), qp, cfg)
        assert int(vec[0]) == clipped and clipped > 0
        assert int(vec[1]) == x.size

    def test_quant_health_fanout_and_merge(self):
        q = QuantHealth()
        stacked = np.asarray([[1, 10, 0.5, 1.0], [3, 10, 2.0, 1.0]],
                             np.float32)
        q.update({"layer/site": stacked, "head": np.asarray(
            [2, 20, 4.0, 2.0], np.float32)})
        q.update({"layer/site": stacked})        # counts sum, amax maxes
        rep = q.report()
        assert set(rep["sites"]) == {"layer0/site", "layer1/site", "head"}
        s1 = rep["sites"]["layer1/site"]
        assert s1["clipped"] == 6 and s1["total"] == 20
        assert s1["clip_fraction"] == pytest.approx(0.3)
        assert s1["observed_amax"] == 2.0
        assert s1["amax_ratio"] == pytest.approx(2.0)
        assert rep["sites"]["head"]["clip_fraction"] == pytest.approx(0.1)
        assert rep["steps_observed"] == 2


class TestRecompileGuard:
    def test_tracing_reuses_untraced_executables(self):
        """Tracing is host-side only: serving with the tracer + metrics on
        must not retrace or change the jitted step signatures — the traced
        run reuses the executables the untraced run compiled."""
        traces = {"admit": 0, "decode": 0}
        stub = Stub()

        def admit_fn(t, pm, m, c):              # jit-traceable stub LM
            traces["admit"] += 1
            return _onehot((2 * t + 1) % 32), c

        def decode_fn(t, p, c):
            traces["decode"] += 1
            return _onehot((2 * t + 1) % 32), c

        admit_j = jax.jit(admit_fn)
        decode_j = jax.jit(decode_fn)

        def run(tel):
            reqs = [Request(rid=i, prompt=np.asarray([3 + i, 5 + i]),
                            max_new_tokens=4) for i in range(3)]
            serve_continuous(admit_j, decode_j, stub.init_cache, reqs,
                             batch_slots=2, prompt_pad_len=2,
                             telemetry=tel)
            return reqs

        plain = run(None)
        assert traces == {"admit": 1, "decode": 1}
        traced = run(ServeTelemetry.create(trace=True, metrics_every=2))
        assert traces == {"admit": 1, "decode": 1}   # zero new traces
        for a, b in zip(plain, traced):
            assert a.tokens_out == b.tokens_out

    def test_named_scopes_add_no_retrace(self):
        """A real (reduced) model on the paged, chunked path: its steps
        carry the named scopes (attn/qkv, attn/attend, attn/kv_write,
        ffn, head) in their compiled ops' metadata, serving untraced then
        traced reuses the one trace of each step, and the greedy tokens
        are identical."""
        from repro.configs import get_config
        from repro.models import transformer as tfm
        from repro.runtime.steps import (make_chunk_prefill_step,
                                         make_decode_step)
        cfg = get_config("gemma2-2b").reduced()
        params = tfm.init_params(cfg, jax.random.PRNGKey(0), stacked=True,
                                 dtype=jnp.float32)
        traces = {"chunk": 0, "decode": 0}

        def counted(name, fn):
            def step(*args):
                traces[name] += 1
                return fn(*args)
            return jax.jit(step)

        chunk_j = counted("chunk", make_chunk_prefill_step(cfg))
        decode_j = counted("decode", make_decode_step(cfg))
        max_len, bs, lanes = 32, 4, 2
        nb = tfm.paged_lane_blocks(cfg, max_len, bs)

        def init(b):
            return tfm.init_cache(cfg, b, max_len, dtype=jnp.float32,
                                  paged=True, block_size=bs,
                                  num_blocks=lanes * nb, mapped=False)

        def run(tel):
            rng = np.random.RandomState(3)
            reqs = [Request(rid=i, prompt=rng.randint(
                1, cfg.vocab_size, size=n).astype(np.int32),
                max_new_tokens=q) for i, (n, q) in
                enumerate([(5, 4), (9, 3), (3, 5)])]
            serve_continuous(
                lambda *a: None,
                lambda t, p, c: decode_j(params, t, p, c), init, reqs,
                batch_slots=lanes, max_len=max_len,
                block_pool=BlockPool(lanes * nb, bs, lanes, nb),
                chunk_fn=lambda t, pm, m, c: chunk_j(params, t, pm, m, c),
                prefill_chunk=4,
                write_caps=tfm.attn_write_caps(cfg, max_len, bs),
                ring_tokens=tfm.paged_ring_tokens(cfg, max_len, bs),
                telemetry=tel)
            return [r.tokens_out for r in reqs]

        plain = run(None)
        assert traces == {"chunk": 1, "decode": 1}
        tel = ServeTelemetry.create(trace=True)
        assert run(tel) == plain
        assert traces == {"chunk": 1, "decode": 1}     # zero new traces
        names = {e.name for e in tel.tracer.events}
        assert {"chunk", "decode_batch", "dispatch", "readback", "table",
                "pool", "inputs", "emit", "retirement"} <= names
        cache = init(lanes)
        hlo = jax.jit(make_decode_step(cfg)).lower(
            params, jnp.zeros((lanes, 1), jnp.int32),
            jnp.zeros((lanes, 1), jnp.int32), cache).compile().as_text()
        for scope in ("/embed/", "/norm/", "/attn/qkv/", "/attn/attend/",
                      "/attn/kv_write/", "/attn/out/", "/ffn/", "/head/",
                      "/layers/"):
            assert scope in hlo, scope
        # the layer scan's own slicing of the stacked params and caches
        assert re.search(r'op_name="[^"]*/layers/while/body/'
                         r'dynamic_(update_)?slice"', hlo)

    def test_disabled_telemetry_returns_plain_step(self):
        """quant_telemetry=False hands back the ORIGINAL 2-output closure
        (not a wrapper), so existing jit caches keyed on it stay warm."""
        from repro.configs import get_config
        from repro.runtime.steps import make_admit_step, make_decode_step
        cfg = get_config("gemma2-2b").reduced()
        assert make_admit_step(cfg).__name__ == "admit"
        assert make_decode_step(cfg).__name__ == "decode"
        assert make_admit_step(cfg, quant_telemetry=True).__name__ \
            == "admit_t"
        assert make_decode_step(cfg, quant_telemetry=True).__name__ \
            == "decode_t"


class TestTracerUnit:
    def test_phase_timer_records_duration_and_args(self):
        tr = Tracer()
        with tr.phase("decode_batch", 3) as ph:
            ph.args["lanes"] = 2
        (e,) = tr.events
        assert e.name == "decode_batch" and e.step == 3
        assert e.dur >= 0.0 and e.args == {"lanes": 2}
        assert tr.latency_histograms()["decode_batch"]["n"] == 1

    def test_event_args_survive_export(self):
        tr = Tracer()
        tr.event("prefix_hit", 1, rid=7, lane=0, tokens=16)
        doc = tr.to_chrome_trace()
        (hit,) = [e for e in doc["traceEvents"]
                  if e["name"] == "prefix_hit"]
        assert hit["args"]["tokens"] == 16
        assert hit["args"]["rid"] == 7
        assert hit["tid"] == 1                   # lane 0 -> tid 1
        json.dumps(doc)                          # serializable end-to-end

    def test_span_nesting_args_and_step(self):
        """Spans nest by the scheduler thread's open spans (parent sid,
        inherited step), carry their args, and a phase is a span whose
        Chrome-trace event and latency histogram are those of before."""
        tr = Tracer()
        with tr.span("pool", 3, blocks=2):
            with tr.span("table", table_uploads=1):
                pass
        with tr.phase("decode_batch", 4) as ph:
            ph.args["lanes"] = 2
            with tr.span("dispatch"):
                pass
            with tr.span("readback"):
                pass
        by = {e.name: e for e in tr.events}
        assert [e.name for e in tr.events] == [
            "table", "pool", "dispatch", "readback", "decode_batch"]
        assert by["pool"].parent is None and by["pool"].step == 3
        assert by["pool"].args == {"blocks": 2}
        assert by["table"].parent == by["pool"].sid
        assert by["table"].step == 3 and by["table"].args == {
            "table_uploads": 1}
        for child in ("dispatch", "readback"):
            assert by[child].parent == by["decode_batch"].sid
            assert by[child].step == 4
        ph_ev = by["decode_batch"]
        assert by["dispatch"].dur + by["readback"].dur <= ph_ev.dur
        # the old phase: one X event on the steps track with its args
        doc = tr.to_chrome_trace()
        (x,) = [e for e in doc["traceEvents"]
                if e["name"] == "decode_batch"]
        assert x["ph"] == "X" and x["tid"] == 0
        assert x["args"]["lanes"] == 2 and x["args"]["step"] == 4
        assert x["dur"] == pytest.approx(ph_ev.dur * 1e6)
        hist = tr.latency_histograms()
        assert hist["decode_batch"]["n"] == 1
        assert set(hist) == {"decode_batch", "pool", "table", "dispatch",
                             "readback"}
        json.dumps(doc)

    def test_spans_enter_profiler_annotations(self, monkeypatch):
        """Each span holds a ``serve:<name>`` profiler annotation open for
        its duration, properly nested."""
        log = []

        class Recording:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
        tr = Tracer()
        with tr.phase("chunk", 1):
            with tr.span("readback"):
                pass
        assert log == [("enter", "serve:chunk"), ("enter", "serve:readback"),
                       ("exit", "serve:readback"), ("exit", "serve:chunk")]


class TestSchedulerSpans:
    def test_untraced_scheduler_enters_no_annotation(self, monkeypatch):
        """telemetry=None: no span and no profiler annotation is entered
        anywhere in the serving loop or the engine."""
        def refuse(*a, **kw):
            raise AssertionError("TraceAnnotation entered untraced")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        reqs = _oc_reqs()
        _serve_oc(reqs, None, swap=True)
        for r in reqs:
            assert r.tokens_out == _golden(r.prompt, 12)
        with pytest.raises(AssertionError, match="untraced"):
            _serve_oc(_oc_reqs(), ServeTelemetry.create(trace=True))

    @pytest.mark.parametrize("swap", [False, True])
    def test_spans_count_what_the_loop_did(self, swap, monkeypatch):
        """Each model-call span holds a dispatch and a readback span and is
        followed by one emit and one retirement span; every seating
        (resumes included) happens inside an admission span; the table
        spans count every re-upload of a dirty block table."""
        from repro.runtime import block_pool
        flips = []

        def set_dirty(self, v):
            if getattr(self, "_dirty", False) and not v:
                flips.append(1)
            self._dirty = v

        monkeypatch.setattr(block_pool.BlockPool, "dirty", property(
            lambda self: self._dirty, set_dirty), raising=False)
        tel = ServeTelemetry.create(trace=True)
        reqs = _oc_reqs()
        stats = _serve_oc(reqs, tel, swap=swap)
        ev = tel.tracer.events
        calls = [e for e in ev if e.name in ("chunk", "decode_batch")]
        assert len(calls) == stats.prefill_calls + stats.decode_steps
        for c in calls:
            kids = sorted(e.name for e in ev if e.parent == c.sid)
            assert kids == ["dispatch", "readback"], kids
        for name in ("emit", "retirement"):
            assert sum(e.name == name for e in ev) == len(calls), name
        admissions = [(e.ts, e.ts + e.dur) for e in ev
                      if e.name == "admission"]
        seated = [e.ts for e in ev if e.name in ("admit", "resume")
                  and e.dur == 0.0]
        assert len(seated) >= len(reqs)
        for t in seated:
            assert any(a <= t <= b for a, b in admissions), t
        assert sum(e.args["table_uploads"] for e in ev
                   if e.name == "table") == len(flips) > 0

    @pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                             ids=["float", "int8"])
    def test_decode_spans_count_the_attention_walk(self, cache_dtype):
        """Every ``decode_batch`` span carries ``attend_blocks``: the
        paged decode walk's count (``walk_blocks``) at the positions that
        step decoded, in one layer, summed over lanes — 128-token blocks
        over a float cache, one page a block over an int8 one."""
        from repro.kernels.paged_attend_decode import (pages_per_block,
                                                       walk_blocks)
        m = Stub()
        seen = []

        def decode(tokens, pos, cache):
            seen.append(np.asarray(pos)[:, 0].copy())
            return m.decode(tokens, pos, cache)

        bs, nb = 4, 40
        reqs = [Request(rid=i, prompt=np.full(n, 3 + i, np.int32),
                        max_new_tokens=12)
                for i, n in enumerate([130, 20, 7, 140])]
        tel = ServeTelemetry.create(trace=True)
        serve_continuous(
            m.admit, decode,
            lambda batch: {"kv": jnp.zeros((batch, 4), cache_dtype)},
            reqs, batch_slots=2, block_pool=BlockPool(2 * nb, bs, 2, nb),
            chunk_fn=m.chunk, prefill_chunk=32, telemetry=tel)
        spans = [e for e in tel.tracer.events if e.name == "decode_batch"]
        assert len(spans) == len(seen) > 0
        pages = pages_per_block(bs, cache_dtype == jnp.int8)
        assert pages == (1 if cache_dtype == jnp.int8 else 32)
        multi = 0
        for e, pos in zip(spans, seen):
            assert e.args["attend_blocks"] == int(np.sum(walk_blocks(
                pos, nb=nb, bs=bs, pages=pages)))
            # the same walk counted by hand: live pages, then blocks
            live = [min(nb, -(-(p + 1) // bs)) for p in pos if p >= 0]
            assert e.args["attend_blocks"] == sum(-(-n // pages)
                                                  for n in live)
            multi += e.args["attend_blocks"] > len(live)
        assert multi > 0        # some lane walked more than one block

    def test_spans_land_in_a_profiler_trace(self, tmp_path):
        """Traced serving under ``jax.profiler``: every span appears in the
        profile as a ``serve:<name>`` host event on the profiler's clock,
        each read-back inside a model-call phase."""
        from jax.profiler import ProfileData
        tel = ServeTelemetry.create(trace=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve_oc(_oc_reqs(), tel)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("serve:")]
        names = {n for n, _, _ in spans}
        assert names == {"serve:" + e.name for e in tel.tracer.events
                         if e.sid is not None}
        calls = [(a, b) for n, a, b in spans
                 if n in ("serve:chunk", "serve:decode_batch")]
        backs = [(a, b) for n, a, b in spans if n == "serve:readback"]
        assert len(backs) == len(calls) > 0
        for a, b in backs:
            assert any(ca <= a and b <= cb for ca, cb in calls)
