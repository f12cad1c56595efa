"""Serving observability: request-lifecycle tracing, metrics snapshots, and
quantization-health telemetry (docs/observability.md has the full guide).

Three independent collectors, bundled by :class:`ServeTelemetry` and threaded
through the scheduler as a single optional handle (``telemetry=None`` keeps
every hot-loop callsite a no-op):

- :class:`Tracer` — structured lifecycle events (enqueue, admit, prefix-hit,
  chunk, decode-batch, block grow, COW, preempt/swap/drop, resume,
  radix-evict, retire) with the scheduler step index plus a wall-clock
  timestamp, exported as Chrome-trace-event JSON (load the file in
  https://ui.perfetto.dev). Spans (the model-call phases admit/chunk/
  decode_batch/swap and the host work between calls: admission, pool,
  inputs, table, dispatch, readback, emit, retirement) are nested duration
  events on a "steps" track, and each also enters a
  ``jax.profiler.TraceAnnotation("serve:<name>")``, so a ``jax.profiler``
  trace shows it on the device ops' clock. Each request becomes a span on
  its lane's track, so the Perfetto timeline shows lane occupancy
  directly. Per-span latency histograms (p50/p95/p99) ride along.
- :class:`MetricsLogger` — periodic gauge snapshots (queue depth, resident
  lanes, free/evictable blocks, refcount totals, prefix hit rate,
  preemption counters) appended as JSON-lines, plus a final Prometheus
  text-format exposition.
- :class:`QuantHealth` — host-side aggregation of the fixed-shape
  ``[n_clipped, n_total, amax, cal_range]`` site vectors the jitted steps
  emit under ``quant_telemetry=True`` (see runtime/steps.py), keyed
  ``{layer}/site``, plus kv-cache scale distribution stats walked off the
  quantized cache pytree.

The tracer's event record is append-to-a-list cheap; everything expensive
(span assembly, percentile math, serialization) happens once at export.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional, TextIO, Tuple

import jax
import numpy as np

# Event names (the taxonomy in docs/observability.md). Phase events carry a
# duration; the rest are instants on the emitting request's lane track.
PHASES = ("admit", "chunk", "decode_batch", "swap_out", "swap_in")
EVENTS = ("enqueue", "admit", "prefix_hit", "chunk", "decode_batch",
          "block_grow", "cow", "preempt", "swap_out", "drop", "resume",
          "radix_evict", "retire")
# prefix of every span's name in a jax.profiler trace
ANNOTATION_PREFIX = "serve:"


@dataclasses.dataclass
class TraceEvent:
    """One lifecycle event. ``ts`` is seconds since tracer start (exported
    as µs); ``step`` is the scheduler's monotonic step index. Spans carry
    their own ``sid`` and the ``parent`` sid of the span enclosing them."""
    name: str
    step: int
    ts: float
    rid: Optional[int] = None      # request id, when request-scoped
    lane: Optional[int] = None     # decode lane (slot), when resident
    dur: float = 0.0               # seconds; > 0 only for spans
    args: Optional[Dict[str, Any]] = None
    sid: Optional[int] = None      # span id (spans only)
    parent: Optional[int] = None   # enclosing span's sid


def _percentiles(xs: List[float]) -> Dict[str, float]:
    if not xs:
        return {"n": 0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    a = np.asarray(xs, dtype=np.float64)
    p50, p95, p99 = np.percentile(a, [50, 95, 99])
    return {"n": int(a.size), "p50": float(p50), "p95": float(p95),
            "p99": float(p99), "mean": float(a.mean()),
            "max": float(a.max())}


class Tracer:
    """Low-overhead lifecycle event recorder with Chrome-trace export.

    Record with :meth:`event` (instant) and :meth:`span` (timed context
    manager; :meth:`phase` is the span around a jitted call). Spans nest:
    the parent of a span is the span open around it on the scheduler
    thread. The scheduler holds ``tracer=None`` when tracing is off, so the
    disabled path never constructs one of these.
    """

    def __init__(self) -> None:
        self.events: List[TraceEvent] = []
        self._t0 = time.perf_counter()
        self._phase_s: Dict[str, List[float]] = {p: [] for p in PHASES}
        self._open: List["_PhaseTimer"] = []     # spans open, outermost first
        self._sids = 0

    # -- recording ---------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._t0

    def event(self, name: str, step: int, *, rid: Optional[int] = None,
              lane: Optional[int] = None, **args: Any) -> None:
        self.events.append(TraceEvent(name, step, self.now(), rid=rid,
                                      lane=lane, args=args or None))

    def span(self, name: str, step: Optional[int] = None,
             **args: Any) -> "_PhaseTimer":
        """A timed span, used as a context manager: on exit it records a
        duration event with ``args`` (the caller may add to ``.args``
        inside). ``step`` None takes the enclosing span's step."""
        if step is None:
            step = self._open[-1].step if self._open else 0
        return _PhaseTimer(self, name, step, args)

    def phase(self, name: str, step: int) -> "_PhaseTimer":
        return self.span(name, step)

    def _end_phase(self, name: str, step: int, t_start: float,
                   dur: float, args: Optional[Dict[str, Any]],
                   sid: Optional[int] = None,
                   parent: Optional[int] = None) -> None:
        self.events.append(TraceEvent(name, step, t_start, dur=dur,
                                      args=args, sid=sid, parent=parent))
        self._phase_s.setdefault(name, []).append(dur)

    # -- analysis ----------------------------------------------------------
    def latency_histograms(self) -> Dict[str, Dict[str, float]]:
        """Per-phase step-latency percentiles, in milliseconds."""
        return {p: _percentiles([s * 1e3 for s in xs])
                for p, xs in self._phase_s.items() if xs}

    def request_spans(self) -> Dict[int, Dict[str, Any]]:
        """Reconstruct per-request lifecycles from the event list.

        Returns {rid: {enqueue_ts, admits, lanes, preempts, resumes,
        retire_ts, retired}} — the reconciliation surface test_telemetry.py
        checks against ServeStats.
        """
        spans: Dict[int, Dict[str, Any]] = {}

        def rec(rid):
            return spans.setdefault(rid, {
                "enqueue_ts": None, "admits": [], "lanes": [],
                "preempts": 0, "resumes": 0, "retire_ts": None,
                "retired": False})

        for e in self.events:
            if e.rid is None:
                continue
            r = rec(e.rid)
            if e.name == "enqueue":
                r["enqueue_ts"] = e.ts
            elif e.name in ("admit", "resume"):
                r["admits"].append((e.ts, e.lane))
                if e.lane is not None and e.lane not in r["lanes"]:
                    r["lanes"].append(e.lane)
                if e.name == "resume":
                    r["resumes"] += 1
            elif e.name in ("preempt", "swap_out", "drop"):
                if e.name == "preempt":
                    r["preempts"] += 1
            elif e.name == "retire":
                r["retire_ts"] = e.ts
                r["retired"] = True
        return spans

    # -- export ------------------------------------------------------------
    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace event format (Perfetto-loadable).

        pid 1 / tid 0 is the scheduler "steps" track carrying phase duration
        events; each decode lane gets its own tid (lane + 1) carrying the
        request spans plus request-scoped instants. Timestamps are µs.
        """
        out: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "serve"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "steps"}},
        ]
        lanes_seen = set()
        for e in self.events:
            if e.lane is not None and e.lane not in lanes_seen:
                lanes_seen.add(e.lane)
                out.append({"name": "thread_name", "ph": "M", "pid": 1,
                            "tid": e.lane + 1,
                            "args": {"name": f"lane{e.lane}"}})
        # request spans: one "X" per residency (admit/resume -> preempt or
        # retire) on the lane track
        spans = self.request_spans()
        ends: Dict[int, List[Tuple[float, str]]] = {}
        for e in self.events:
            if e.rid is not None and e.name in ("preempt", "retire"):
                ends.setdefault(e.rid, []).append((e.ts, e.name))
        for rid, r in spans.items():
            rends = sorted(ends.get(rid, []))
            for ts, lane in r["admits"]:
                end = next(((t, n) for t, n in rends if t >= ts), None)
                if end is None or lane is None:
                    continue
                out.append({"name": f"req{rid}", "ph": "X", "pid": 1,
                            "tid": lane + 1, "ts": ts * 1e6,
                            "dur": max((end[0] - ts) * 1e6, 1.0),
                            "args": {"rid": rid, "end": end[1]}})
        for e in self.events:
            base = {"name": e.name, "pid": 1,
                    "ts": e.ts * 1e6, "args": dict(e.args or {})}
            base["args"]["step"] = e.step
            if e.parent is not None:
                base["args"]["parent"] = e.parent
            if e.sid is not None:
                base["args"]["sid"] = e.sid
            if e.rid is not None:
                base["args"]["rid"] = e.rid
            if e.dur > 0.0:                       # phase duration event
                base.update(ph="X", tid=0, dur=e.dur * 1e6)
            else:                                 # instant
                base.update(ph="i", s="t",
                            tid=0 if e.lane is None else e.lane + 1)
            out.append(base)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


class _PhaseTimer:
    """One span of a :class:`Tracer`; use as a context manager. It times
    the host's time inside the ``with`` (a jitted call returns before the
    device finishes, so around a model call it covers the device time only
    when the caller waits for a result inside it, as the engine's greedy
    read-back does) and holds a ``serve:<name>`` profiler annotation open
    for as long."""

    def __init__(self, tracer: Tracer, name: str, step: int,
                 args: Optional[Dict[str, Any]] = None) -> None:
        self._tracer, self._name, self.step = tracer, name, step
        self.args: Dict[str, Any] = args or {}

    def __enter__(self) -> "_PhaseTimer":
        tr = self._tracer
        self._parent = tr._open[-1].sid if tr._open else None
        tr._sids += 1
        self.sid = tr._sids
        tr._open.append(self)
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self._name)
        self._annotation.__enter__()
        self._start = tr.now()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        dur = tr.now() - self._start
        self._annotation.__exit__(*exc)
        tr._open.pop()
        tr._end_phase(self._name, self.step, self._start, dur,
                      self.args or None, self.sid, self._parent)


class MetricsLogger:
    """Periodic scheduler gauge snapshots.

    ``emit(step, gauges)`` appends one JSON line per snapshot;
    :meth:`prometheus_text` renders the latest snapshot (plus counters) in
    Prometheus text exposition format for scrape-style consumption.
    """

    def __init__(self, every: int = 0,
                 sink: Optional[TextIO] = None) -> None:
        self.every = every
        self.sink = sink
        self.snapshots: List[Dict[str, Any]] = []
        self._last_step = -1

    def due(self, step: int) -> bool:
        """True at most once per scheduler step (a loop iteration without a
        model call leaves the step unchanged and must not re-emit)."""
        return (self.every > 0 and step % self.every == 0
                and step != self._last_step)

    def emit(self, step: int, gauges: Dict[str, Any]) -> None:
        self._last_step = step
        snap = {"step": step, "ts": time.time()}
        snap.update(gauges)
        self.snapshots.append(snap)
        if self.sink is not None:
            self.sink.write(json.dumps(snap) + "\n")

    def jsonl(self) -> str:
        return "".join(json.dumps(s) + "\n" for s in self.snapshots)

    def prometheus_text(self) -> str:
        """Latest snapshot as Prometheus gauges (serve_* namespace)."""
        if not self.snapshots:
            return ""
        latest = self.snapshots[-1]
        lines = []
        for k, v in latest.items():
            if k == "ts" or not isinstance(v, (int, float, np.integer,
                                               np.floating)):
                continue
            name = f"serve_{k}"
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {float(v):g}")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Quantization health
# ---------------------------------------------------------------------------

class QuantHealth:
    """Aggregates the per-site telemetry vectors the jitted steps emit.

    Each site vector is ``[n_clipped, n_total, amax, cal_range]`` (f32):
    counts accumulate by summing, ``amax``/``cal_range`` by max. Stacked
    scan sites arrive as (L, 4) arrays keyed ``layer/<site>`` and fan out
    to ``layer{i}/<site>``. Derived per-site metrics:

    - ``clip_fraction`` = n_clipped / n_total — the fraction of values
      landing ON or OUTSIDE the calibrated grid edges (paper §3: outliers
      past the fixed-point range are what break int8 transformers).
    - ``amax_ratio`` = observed amax / calibrated representable range —
      > 1 means live traffic exceeds what calibration saw.
    """

    def __init__(self) -> None:
        # site -> [clipped_sum, total_sum, amax_max, range_max]
        self.sites: Dict[str, np.ndarray] = {}
        self.kv_scale_stats: Dict[str, Dict[str, float]] = {}
        self.steps_observed = 0

    def update(self, telemetry: Optional[Dict[str, Any]]) -> None:
        """Fold one step's telemetry dict (host transfer happens here)."""
        if not telemetry:
            return
        self.steps_observed += 1
        for site, vec in telemetry.items():
            arr = np.asarray(vec, dtype=np.float64)
            if arr.ndim == 2:                     # stacked scan: (L, 4)
                for i in range(arr.shape[0]):
                    self._fold(site.replace("layer/", f"layer{i}/", 1)
                               if site.startswith("layer/")
                               else f"{site}[{i}]", arr[i])
            else:
                self._fold(site, arr)

    def _fold(self, site: str, vec: np.ndarray) -> None:
        cur = self.sites.get(site)
        if cur is None:
            self.sites[site] = vec.copy()
        else:
            cur[0] += vec[0]
            cur[1] += vec[1]
            cur[2] = max(cur[2], vec[2])
            cur[3] = max(cur[3], vec[3])

    def update_kv_scales(self, cache: Any) -> None:
        """Distribution stats over the quantized KV cache's per-slot scale
        leaves (``k_s``/``v_s`` on QuantKVCache / PagedQuantKVCache and the
        int4 subclasses). Zero-valued scales (unwritten slots) are
        excluded."""
        import jax
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
            keys = [getattr(p, "key", getattr(p, "name", None))
                    for p in path]
            tail = next((k for k in keys[::-1] if k in ("k_s", "v_s")), None)
            if tail is None:
                continue
            a = np.asarray(leaf, dtype=np.float64).ravel()
            a = a[a != 0.0]
            name = f"kv/{tail}"
            if a.size == 0:
                continue
            self.kv_scale_stats[name] = {
                "n": int(a.size), "min": float(a.min()),
                "max": float(a.max()), "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p99": float(np.percentile(a, 99)),
            }

    def report(self) -> Dict[str, Any]:
        per_site = {}
        for site, v in sorted(self.sites.items()):
            total = v[1]
            per_site[site] = {
                "clipped": int(v[0]), "total": int(total),
                "clip_fraction": float(v[0] / total) if total else 0.0,
                "observed_amax": float(v[2]),
                "calibrated_range": float(v[3]),
                "amax_ratio": float(v[2] / v[3]) if v[3] else 0.0,
            }
        return {"steps_observed": self.steps_observed, "sites": per_site,
                "kv_scales": self.kv_scale_stats}


@dataclasses.dataclass
class ServeTelemetry:
    """The one handle the scheduler threads around. Any member may be None;
    ``telemetry=None`` on the scheduler means fully disabled."""
    tracer: Optional[Tracer] = None
    metrics: Optional[MetricsLogger] = None
    quant: Optional[QuantHealth] = None

    @classmethod
    def create(cls, *, trace: bool = False, metrics_every: int = 0,
               quant: bool = False,
               metrics_sink: Optional[TextIO] = None) -> "ServeTelemetry":
        return cls(
            tracer=Tracer() if trace else None,
            metrics=MetricsLogger(metrics_every, metrics_sink)
            if metrics_every > 0 else None,
            quant=QuantHealth() if quant else None)
