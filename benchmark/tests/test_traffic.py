"""The generator's arithmetic: arrivals, and latency from the due time
with held sheds and resends inside it."""

import pytest

from benchmark.stats import nearest_rank
from benchmark.traffic_gen import Caller, Request, arrival_times


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Nack(Exception):
    def __init__(self, code, retry_after):
        super().__init__(code)
        self.code, self.retry_after = code, retry_after


class ScriptedRpc:
    """Nacks the first ``sheds`` sends with ``retry_after``, then answers
    after ``service_s`` of clock."""

    def __init__(self, clock, sheds, retry_after, service_s,
                 code="overloaded"):
        self.clock, self.sheds = clock, sheds
        self.retry_after, self.service_s, self.code = (
            retry_after, service_s, code)
        self.sends = 0

    def request(self, method, params, timeout=None):
        assert method == "catchup"
        self.sends += 1
        if self.sends <= self.sheds:
            self.clock.t += 0.001
            raise Nack(self.code, self.retry_after)
        self.clock.t += self.service_s
        return {"docs": {d: ["h", 1] for d in params["docs"]}}


def test_latency_counts_from_due_time_with_resends_inside():
    clock = FakeClock()
    rpc = ScriptedRpc(clock, sheds=2, retry_after=0.25, service_s=0.04)
    caller = Caller(rpc, Nack, clock=clock, sleep=clock.sleep)
    caller.t0 = clock()
    clock.t += 0.5           # the generator is 0.5 s late for this one
    req = caller.send(Request(["d1"], due=0.1), deadline_s=10)
    assert req.error is None and req.sends == 3 and req.sheds == 2
    assert req.sent == pytest.approx(0.5)
    # due at 0.1; sent at 0.5; two nacks (1 ms each) held 0.25 s each;
    # the answer takes 40 ms: done at 0.5 + 2 * 0.251 + 0.04
    assert req.done == pytest.approx(0.5 + 2 * 0.251 + 0.04)
    assert req.latency == pytest.approx(req.done - 0.1)


def test_a_request_fails_only_past_its_deadline_or_on_another_nack():
    clock = FakeClock()
    rpc = ScriptedRpc(clock, sheds=100, retry_after=1.0, service_s=0.01)
    caller = Caller(rpc, Nack, clock=clock, sleep=clock.sleep)
    caller.t0 = clock()
    req = caller.send(Request(["d1"], due=0.0), deadline_s=3.5)
    assert req.error == "nack overloaded" and req.sheds == 3
    rpc = ScriptedRpc(clock, sheds=1, retry_after=0.1, service_s=0.01,
                      code="shuttingDown")
    caller = Caller(rpc, Nack, clock=clock, sleep=clock.sleep)
    caller.t0 = clock()
    req = caller.send(Request(["d1"], due=0.0), deadline_s=30)
    assert req.error == "nack shuttingDown" and req.sheds == 0


def test_an_answer_below_the_head_fails_its_documents():
    """A stale (degraded) answer is not a catch-up: the configuration
    promises every document at its head."""
    from benchmark import harness

    tail = [(seq, "c0", seq - 1, 0, {}) for seq in range(1, 97)]
    req = Request(["d1", "d2"], due=0.0)
    req.answer = {"lane": "degraded", "deviceDocs": 0, "platform": "tpu",
                  "docs": {"d1": ["h1", 90]}, "degraded": ["d1"]}
    verdict = harness.check_window(
        [req], lambda d: tail, service=None, cfg={}, platform="tpu",
        check=lambda blobs, ops, seq: None)
    assert verdict["compared"]["wrong_docs"] == 1
    assert verdict["compared"]["skipped_docs"] == 1
    assert verdict["failed_docs"] == 2 and verdict["docs_fresh"] == 0
    assert "d1: seq 90, head 96" in verdict["reasons"]


def test_arrivals_are_one_fixed_draw_for_every_seed():
    a = arrival_times(720, 30.0, arrival_seed=7)
    assert len(a) == 720 and a[0] == 0.0 and max(a) < 30.0
    assert a == sorted(a) and a == arrival_times(720, 30.0, arrival_seed=7)
    assert a != arrival_times(720, 30.0, arrival_seed=8)
    gaps = [y - x for x, y in zip(a, a[1:])]
    # exponential gaps: the mean is 30/720 s and the spread is as wide
    assert 0.5 < (sum(gaps) / len(gaps)) / (30.0 / 720) < 1.5
    assert max(gaps) > 4 * (30.0 / 720)


def test_closed_loop_ends_at_the_first_answer_after_the_window():
    clock = FakeClock()
    rpc = ScriptedRpc(clock, sheds=0, retry_after=0, service_s=4.0)
    caller = Caller(rpc, Nack, clock=clock, sleep=clock.sleep)
    reqs, ran_out = caller.closed_loop([f"d{i}" for i in range(40)], 4,
                                       seconds=10.0, deadline_s=60)
    assert not ran_out and len(reqs) == 3 and reqs[-1].done == 12.0
    reqs, ran_out = caller.closed_loop([f"d{i}" for i in range(8)], 4,
                                       seconds=10.0, deadline_s=60)
    assert ran_out and len(reqs) == 2


def test_open_loop_sends_on_schedule_whatever_is_outstanding():
    import threading
    import time

    lock = threading.Lock()
    inflight = {"now": 0, "max": 0}

    class SlowRpc:
        def request(self, method, params, timeout=None):
            with lock:
                inflight["now"] += 1
                inflight["max"] = max(inflight["max"], inflight["now"])
            time.sleep(0.2)
            with lock:
                inflight["now"] -= 1
            return {"docs": {d: ["h", 1] for d in params["docs"]}}

    due = [0.0, 0.01, 0.02, 0.03, 0.04]
    reqs = Caller(SlowRpc(), Nack).open_loop(
        [f"d{i}" for i in range(5)], due, 1, deadline_s=5,
        connections=[SlowRpc() for _ in range(8)])
    assert inflight["max"] >= 4
    assert all(r.latency >= 0.2 for r in reqs)


def test_nearest_rank():
    xs = list(range(1, 101))
    assert nearest_rank(xs, 50) == 50 and nearest_rank(xs, 95) == 95
    assert nearest_rank([3.0], 95) == 3.0
    assert nearest_rank([5, 1, 4, 2, 3], 50) == 3
