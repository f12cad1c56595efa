"""Window arithmetic on synthetic timelines (bench/window.py)."""
import pytest

from bench.window import Stamps, Window, WindowClosed, percentile, summarize


def test_due_times_follow_completions_and_ttft_is_censored():
    # 2 clients; ramp at 0, window [1, 5]
    times = [[0.5, 1.5, 2.0],       # req 0 completes at 2.0 -> req 2 due
             [1.0, 3.0],            # req 1 completes at 3.0 -> req 3 due
             [2.5, 2.7, 6.0],       # due 2.0, first token 2.5
             [],                    # due 3.0, no token by the close
             []]                    # due 6.0 (3rd completion), after it
    quotas = [3, 2, 3, 4, 4]
    s = summarize(times, quotas, 2, 0.0, 1.0, 5.0)
    assert s["attempted"] == 2
    assert s["ttft"] == pytest.approx([0.5, 2.0])     # 2.5-2.0, 5.0-3.0
    assert s["tokens"] == 6                           # stamps in [1, 5]
    assert s["seconds"] == 4.0
    assert sorted(s["gaps"]) == pytest.approx([0.2, 0.5, 2.0])
    assert s["completed"] == [0, 1, 2]


def test_requests_due_before_the_window_are_not_attempted():
    times = [[0.1, 0.2], [0.3, 5.0], [0.4, 0.5]]
    s = summarize(times, [2, 2, 2], 1, 0.0, 1.0, 4.0)
    # req 1 due at 0.2 and req 2 due at 0.5, both before the open
    assert s["attempted"] == 0 and s["ttft"] == []


def test_gap_p95_over_every_gap():
    gaps = [0.1] * 95 + [1.0] * 5
    times = [[0.0]]
    for g in gaps:
        times[0].append(times[0][-1] + g)
    s = summarize(times, [len(times[0])], 1, 0.0, 0.0, 1e9)
    assert len(s["gaps"]) == 100
    assert percentile(s["gaps"], 95) == pytest.approx(0.1 + 0.05 * 0.9)


def test_window_opens_on_the_first_token_of_every_client_and_closes():
    now = [0.0]
    w = Window(2, 1.0, clock=lambda: now[0])
    w.start()
    a, b, late = Stamps(w, 0), Stamps(w, 1), Stamps(w, 5)
    now[0] = 0.4
    a.append(7)
    late.append(1)                  # not one of the first clients
    assert w.open is None
    now[0] = 0.6
    b.append(8)
    assert (w.open, w.deadline, w.close) == (0.6, 1.6, None)
    now[0] = 1.6
    a.append(9)                     # at the deadline: still inside
    w.check(step=True)
    now[0] = 1.7
    b.append(10)                    # the step in flight past the deadline
    assert w.close is None
    now[0] = 1.75
    with pytest.raises(WindowClosed):
        w.check(step=True)          # the next step closes the window
    assert w.close == 1.75
    assert list(b) == [8, 10] and b.times == [0.6, 1.7]
    with pytest.raises(WindowClosed):
        w.check()


def test_window_drains_until_finished_requests_hold_enough_tokens():
    now = [0.0]
    w = Window(1, 1.0, clock=lambda: now[0], drain_tokens=3, drain_s=5.0)
    closed = []
    w.on_close = lambda: closed.append(now[0])
    w.start()
    a, b = Stamps(w, 0, quota=2), Stamps(w, 1, quota=2)
    a.append(1)                     # opens at 0: time is up at 1
    now[0] = 1.5
    a.append(2)                     # finished: 2 tokens, fewer than 3
    w.check(step=True)              # closes at the next step
    assert closed == [1.5] and w.finished_tokens == 2
    b.append(3)
    now[0] = 2.0
    with pytest.raises(WindowClosed):
        b.append(4)                 # finished: 4 tokens, enough
    assert closed == [1.5]
    w2 = Window(1, 1.0, clock=lambda: now[0], drain_tokens=99, drain_s=5.0)
    now[0] = 0.0
    Stamps(w2, 0, quota=9).append(1)
    now[0] = 1.1
    w2.check(step=True)             # closes at 1.1
    now[0] = 6.0
    w2.check()                      # still draining
    now[0] = 6.2
    with pytest.raises(WindowClosed):
        w2.check()                  # at most drain_s past the close
