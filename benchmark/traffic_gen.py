"""The one traffic generator: closed and open loops over the catch-up RPC.

A traffic file (``benchmark/traffic/<name>.json``) gives its parameters;
this module reads no other input.  Every request names documents that
have not been caught up, in an order drawn from the seed, so every
request folds (no cache tier is turned off, and none is hit).

- ``"loop": "closed"`` — one caller; each request names
  ``docs_per_request`` documents and is sent when the previous answer
  has arrived.  The window ends at the first answer after ``seconds``,
  or when the corpus runs out.
- ``"loop": "open"`` — independent users; ``rate_per_s`` requests a
  second, each due at a time fixed before the window opens, sent on a
  connection of its own (one of ``connections``) whether or not earlier
  ones have been answered.  The arrivals are one fixed Poisson draw
  (from ``arrival_seed``) scaled to fill ``seconds``: every seed gets the
  same requests at the same times, on other documents.

A request is timed from when it was due to when its final answer
arrived.  An ``overloaded`` nack is held for its ``retryAfter`` and the
request resent, as the shed client of the storm scenario does; that wait
is latency.  A request fails only when no answer comes before
``deadline_s`` after it was due.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import queue
import random
import time


@dataclasses.dataclass
class Request:
    docs: list
    due: float                 # seconds after the window opened
    sent: float = None         # first send, same clock
    done: float = None         # final answer (or give-up), same clock
    sends: int = 0
    sheds: int = 0
    answer: dict = None
    error: str = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def arrival_times(n: int, seconds: float, arrival_seed: int) -> list:
    """``n`` due times in ``[0, seconds)``: exponential gaps drawn from
    ``arrival_seed`` alone and scaled so the ``n`` gaps span ``seconds``.
    Every run's seed gets these same arrivals: the order of the gaps sets
    the bursts, and with it the tail, so the seed changes only which
    documents are asked for."""
    fixed = random.Random(arrival_seed)
    gaps = [fixed.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


class Caller:
    """Sends catch-up requests through ``rpc`` (a client connection's
    ``request(method, params, timeout=...)``), timing everything on one
    monotonic clock."""

    def __init__(self, rpc, nack_error, annotate=None, clock=None,
                 sleep=None) -> None:
        self.rpc = rpc
        self.nack_error = nack_error
        self.annotate = annotate
        self.clock = clock or time.perf_counter
        self.sleep = sleep or time.sleep
        self.t0 = None

    def now(self) -> float:
        return self.clock() - self.t0

    def send(self, req: Request, deadline_s: float, rpc=None) -> Request:
        """One request to its final answer, holding and resending on
        ``overloaded``; over ``rpc`` (a connection) where given."""
        rpc = self.rpc if rpc is None else rpc
        req.sent = self.now()
        give_up = req.due + deadline_s
        while True:
            req.sends += 1
            left = give_up - self.now()
            if left <= 0:
                req.error = "deadline"
                break
            try:
                if self.annotate is not None:
                    with self.annotate("benchmark.catchup_rpc"):
                        req.answer = rpc.request(
                            "catchup", {"docs": req.docs}, timeout=left)
                else:
                    req.answer = rpc.request(
                        "catchup", {"docs": req.docs}, timeout=left)
                break
            except self.nack_error as exc:
                hold = float(getattr(exc, "retry_after", 0.0) or 0.0)
                if getattr(exc, "code", None) != "overloaded" \
                        or self.now() + hold >= give_up:
                    req.error = f"nack {getattr(exc, 'code', None)}"
                    break
                req.sheds += 1
                self.sleep(hold)
            except Exception as exc:  # a failed catch-up, counted as such
                req.error = f"{type(exc).__name__}: {exc}"
                break
        req.done = self.now()
        return req

    def closed_loop(self, doc_order: list, per_request: int,
                    seconds: float, deadline_s: float) -> tuple:
        """(requests, corpus_ran_out)."""
        self.t0 = self.clock()
        reqs, i = [], 0
        while True:
            if i >= len(doc_order):
                return reqs, True
            req = Request(doc_order[i:i + per_request], self.now())
            i += len(req.docs)
            reqs.append(self.send(req, deadline_s))
            if self.now() >= seconds:
                return reqs, False

    def open_loop(self, doc_order: list, due: list, per_request: int,
                  deadline_s: float, connections: list) -> list:
        """Each request goes out on a connection of its own, taken from
        ``connections`` for as long as it is outstanding (the server
        answers one request at a time per connection, as one user's
        client does), so up to ``len(connections)`` are outstanding."""
        if len(due) * per_request > len(doc_order):
            raise ValueError(f"{len(due)} requests of {per_request} "
                             f"documents need more than the corpus's "
                             f"{len(doc_order)} uncaught documents")
        reqs = [Request(doc_order[k * per_request:(k + 1) * per_request], t)
                for k, t in enumerate(due)]
        idle = queue.SimpleQueue()
        for rpc in connections:
            idle.put(rpc)

        def one(req):
            rpc = idle.get()
            try:
                return self.send(req, deadline_s, rpc)
            finally:
                idle.put(rpc)

        with concurrent.futures.ThreadPoolExecutor(
                max_workers=len(connections),
                thread_name_prefix="benchmark-client") as pool:
            self.t0 = self.clock()
            futures = []
            for req in reqs:
                wait = req.due - self.now()
                if wait > 0:
                    self.sleep(wait)
                futures.append(pool.submit(one, req))
            for f in futures:
                f.result()
        return reqs
