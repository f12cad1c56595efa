"""The measured window of a closed loop, from the host times at which
tokens were emitted.

C clients each keep one request outstanding: the first C requests are
due when the ramp starts, and request k >= C is due when the (k - C)-th
completion happens (FIFO admission into C lanes is exactly that). The
window opens once each of the first C requests has its first token. Its
time is up ``seconds`` later; it closes at the first model call after
that, so the step in flight when time is up counts whole, its tokens and
its time, and a rate over the window has no step-sized jumps. After the
close the loop may run on, untimed, until the requests finished so far
hold enough served tokens for the check (``drain_tokens``), for at most
``drain_s`` seconds.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np


class WindowClosed(Exception):
    """Raised from the emission hook or a step wrapper once the window's
    close, and the drain after it, have passed: it ends the serving loop
    from outside."""


class Window:
    """Clock of one run: ramp start, open, deadline (open + seconds) and
    close, on ``clock``."""

    def __init__(self, clients: int, seconds: float,
                 clock=time.perf_counter, drain_tokens: int = 0,
                 drain_s: float = 0.0):
        self.clients = clients
        self.seconds = seconds
        self.clock = clock
        self.drain_tokens, self.drain_s = drain_tokens, drain_s
        self.ramp_start: Optional[float] = None
        self.open: Optional[float] = None
        self.deadline: Optional[float] = None
        self.close: Optional[float] = None
        self.finished_tokens = 0        # served tokens of finished requests
        self._waiting = set(range(clients))
        self.on_open = None             # called with the open time
        self.on_close = None            # called once, at the close

    def start(self) -> None:
        self.ramp_start = self.clock()

    def check(self, t: Optional[float] = None, step: bool = False) -> None:
        """``step``: called before a model call, which closes the window
        once its time is up; tokens stamped before it still count."""
        t = self.clock() if t is None else t
        if self.close is None:
            if not step or self.deadline is None or t <= self.deadline:
                return
            self.close = t
            if self.on_close is not None:
                self.on_close()
        if self.finished_tokens >= self.drain_tokens \
                or t > self.close + self.drain_s:
            raise WindowClosed

    def finished(self, n: int) -> None:
        """A request finished with ``n`` served tokens."""
        self.finished_tokens += n

    def token(self, index: int, n: int, t: float) -> None:
        """Request ``index`` emitted its ``n``-th token at ``t``."""
        if n == 1 and self.open is None and index in self._waiting:
            self._waiting.discard(index)
            if not self._waiting:
                self.open = t
                self.deadline = t + self.seconds
                if self.on_open is not None:
                    self.on_open(t)
        self.check(t)


class Stamps(list):
    """A request's ``tokens_out``: stamps the host time of every append
    (one per emitted token, after the step's result is on the host) and
    tells the window when the request's ``quota`` is reached."""

    def __init__(self, window: Window, index: int, quota: int = 0):
        super().__init__()
        self.window, self.index, self.quota = window, index, quota
        self.times: List[float] = []

    def append(self, tok) -> None:
        t = self.window.clock()
        super().append(tok)
        self.times.append(t)
        if len(self) == self.quota:
            self.window.finished(self.quota)
        self.window.token(self.index, len(self), t)


def summarize(times: List[List[float]], quotas: List[int], clients: int,
              ramp_start: float, open_t: float, close_t: float) -> dict:
    """Window arithmetic over per-request emission times (in queue order)
    and output quotas: tokens in the window, due times, censored time to
    first token, inter-token gaps, attempted requests."""
    tokens = sum(1 for ts in times for t in ts if open_t <= t <= close_t)
    done = sorted((ts[-1], k) for k, (ts, q) in enumerate(zip(times, quotas))
                  if len(ts) == q)
    due = [ramp_start] * min(clients, len(times))
    due += [t for t, _ in done][:max(len(times) - clients, 0)]
    ttft, attempted = [], 0
    for k, d in enumerate(due):
        if not open_t <= d <= close_t:
            continue
        attempted += 1
        ts = times[k]
        first = ts[0] if ts and ts[0] <= close_t else close_t
        ttft.append(first - d)
    gaps = [b - a for ts in times for a, b in zip(ts, ts[1:])
            if open_t <= a and b <= close_t]
    return {"tokens": tokens, "seconds": close_t - open_t,
            "attempted": attempted, "ttft": ttft, "gaps": gaps,
            "completed": [k for _, k in done]}


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))
