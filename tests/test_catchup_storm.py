"""Catch-up storms (ISSUE 15): adaptive admission, degraded-mode
serving, the catchup fault seams, per-client relay flow control, and
the storm scenario family that drives herd joins through the REAL
catchup path.

The directed pins here complement the scenario-level matrices in
tests/test_scenarios.py (catchup-storm rides the same smoke / replay /
parity / 10⁵ grids as every family):

- AdmissionController: load-derived retry_after pacing, virtual-time
  lease occupancy, measured-cost EMA — all off an injected clock.
- The warm priority lane bypasses the fold semaphore; N concurrent
  catch-ups of one document cost ONE admission slot (join ≠ fold).
- Shed clients honor the load-derived retry_after through RetryPolicy
  under VirtualClock and still converge.
- Degraded-mode serving answers the stored summary at an older
  ref_seq; loading from it + the durable tail is byte-identical to a
  fresh fold (convergence is never weakened).  Gated by
  Catchup.DegradedServe.
- catchup.fail / catchup.slow fire deterministically and take the real
  recovery paths.
- The front door's broadcast relay is per-client budget-bounded: a
  laggard saturates its own queue and is demoted (existing contract);
  control frames bypass the budget.
- slow tier: the TCP front door at 10⁴ real connections (PR 10's
  "unexplored" corner) with per-connection memory bounds.
"""

import dataclasses
import random
import socket
import struct
import threading
import time

import pytest

from fluidframework_tpu.drivers import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Loader
from fluidframework_tpu.protocol.messages import NackError
from fluidframework_tpu.service.catchup import CatchupService
from fluidframework_tpu.service.orderer import LocalOrderingService
from fluidframework_tpu.service.retry import RetryPolicy
from fluidframework_tpu.service.server import (AdmissionController,
                                               OrderingServer)
from fluidframework_tpu.testing.faults import (FaultInjector, FaultPlan,
                                               FaultPoint)
from fluidframework_tpu.testing.load import VirtualClock
from fluidframework_tpu.utils.telemetry import (ConfigProvider,
                                                LockedCounterSet,
                                                MonitoringContext)


class _Session:
    tenant = None


def _mc(**settings):
    return MonitoringContext(config=ConfigProvider(settings))


def _service_with_doc(doc="doc", sets=3, summarize_at_head=False):
    """A LocalOrderingService holding one map-channel document with an
    attach summary and ``sets`` ops of durable tail; optionally a fresh
    summary AT the head (the fully-warm shape)."""
    service = LocalOrderingService()
    loader = Loader(LocalDocumentServiceFactory(service))

    def build(rt):
        ds = rt.create_datastore("ds")
        ds.create_channel("map-tpu", "kv")

    client = loader.create(doc, "alice", build)
    kv = client.runtime.get_datastore("ds").get_channel("kv")
    for k in range(sets):
        kv.set(f"k{k}", k)
    client.drain()
    client.close()
    if summarize_at_head:
        ro = loader.resolve(doc)
        service.storage.upload(doc, ro.runtime.summarize(),
                               ro.runtime.ref_seq)
        ro.close()
    return service, loader


def _append_op(service, doc="doc", client="w", key="late", value=9):
    """Stamp one more durable map-set (JOIN + OP) past whatever summary
    exists — the 'tail grew since the stored summary' shape."""
    from fluidframework_tpu.protocol.messages import (MessageType,
                                                      RawOperation)
    from fluidframework_tpu.runtime.op_pipeline import BATCH_WIRE_VERSION

    ep = service.endpoint(doc)
    ep.connect(client)
    head = service.oplog.head(doc)
    ep.submit(RawOperation(
        client_id=client, client_seq=1, ref_seq=head,
        type=MessageType.OP,
        contents={"type": "groupedBatch", "v": BATCH_WIRE_VERSION,
                  "ops": [{"clientSeq": 1, "refSeq": head, "ds": "ds",
                           "channel": "kv",
                           "contents": {"kind": "set", "key": key,
                                        "value": value}}]}))
    ep.disconnect(client)


# --- AdmissionController -------------------------------------------------------


def test_admission_retry_after_scales_with_backlog_and_clamps():
    clock = VirtualClock()
    ctl = AdmissionController(2, clock=clock, retry_floor=0.1,
                              retry_cap=3.0, cost_init=0.5)
    verdict, t1 = ctl.admit()
    assert verdict == "admit"
    verdict, _t2 = ctl.admit()
    assert verdict == "admit"
    # full: consecutive overflows deepen the backlog estimate and pace
    # retries further out — monotonic, floor/cap-clamped
    holds = []
    for _ in range(8):
        verdict, retry_after = ctl.admit()
        assert verdict in ("shed", "degrade")
        holds.append(retry_after)
    assert holds == sorted(holds)
    assert holds[0] >= 0.1
    assert holds[-1] <= 3.0
    assert holds[-1] > holds[0]
    # a freed slot resets the streak
    ctl.release(t1)
    verdict, _tok = ctl.admit()
    assert verdict == "admit"
    assert ctl.snapshot()["shed_streak"] == 0


def test_admission_lease_hold_occupies_virtual_time():
    clock = VirtualClock()
    ctl = AdmissionController(1, clock=clock, cost_init=0.1)
    _v, token = ctl.admit()
    ctl.release(token, hold=2.0)  # modeled fold duration: 2s of clock
    assert ctl.admit()[0] in ("shed", "degrade")  # still occupied
    clock.sleep(2.5)
    verdict, _tok = ctl.admit()  # lease expired on the clock
    assert verdict == "admit"


def test_admission_cost_ema_tracks_measured_cost():
    clock = VirtualClock()
    ctl = AdmissionController(1, clock=clock, cost_init=0.2)
    _v, token = ctl.admit()
    clock.sleep(4.0)  # the fold "ran" 4 virtual seconds
    ctl.release(token)
    assert ctl.snapshot()["cost_ema"] > 1.0  # 0.5*0.2 + 0.5*~4


# --- the warm priority lane ----------------------------------------------------


def test_warm_requests_bypass_fold_admission():
    service, _loader = _service_with_doc(summarize_at_head=True)
    server = OrderingServer(service, catchup_max_inflight=1,
                            clock=VirtualClock())
    # saturate the fold lane: the one slot is leased out
    verdict, _token = server.admission_control.admit()
    assert verdict == "admit"
    out = server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert out["lane"] == "warm"
    assert "doc" in out["docs"]
    snap = server.admission.snapshot()
    assert snap["catchup.warm"] == 1
    assert snap["catchup.requests"] == 0  # never entered the fold lane
    assert snap["catchup.shed"] == 0


def test_single_flight_herd_costs_one_admission_slot(monkeypatch):
    """THE satellite pin: N concurrent catch_up calls on one document
    cost ONE admission slot — followers ride the single-flight join in
    the warm lane (a join is not a fold)."""
    service, _loader = _service_with_doc(sets=4)
    server = OrderingServer(service, catchup_max_inflight=4)
    entered = threading.Event()
    release = threading.Event()
    real_cpu = CatchupService._cpu_fold

    def slow_cpu(self, work):
        entered.set()
        assert release.wait(timeout=30)
        return real_cpu(self, work)

    monkeypatch.setattr(CatchupService, "_cpu_fold", slow_cpu)
    monkeypatch.setattr(CatchupService, "_device_plan",
                        lambda self, work: None)
    results = []
    errors = []

    def call():
        try:
            results.append(
                server._dispatch(_Session(), "catchup", {"docs": ["doc"]}))
        except BaseException as exc:  # surfaced via the errors list
            errors.append(exc)

    leader = threading.Thread(target=call)
    leader.start()
    assert entered.wait(timeout=30)  # the flight is registered
    followers = [threading.Thread(target=call) for _ in range(3)]
    for f in followers:
        f.start()
    time.sleep(0.2)  # followers reach the single-flight join
    release.set()
    leader.join(timeout=30)
    for f in followers:
        f.join(timeout=30)
    assert not errors
    assert len(results) == 4
    handles = {tuple(r["docs"]["doc"]) for r in results}
    assert len(handles) == 1  # everyone served the leader's one fold
    snap = server.admission.snapshot()
    assert snap["catchup.admitted"] == 1
    assert snap["catchup.warm"] == 3
    assert snap["catchup.shed"] == 0


# --- shed pacing × RetryPolicy -------------------------------------------------


def test_shed_retry_after_honored_by_retry_policy_under_virtual_clock():
    """A shed client waits the server's load-derived retry_after (via
    RetryPolicy's nack hold) on the SAME virtual clock the admission
    controller measures with — once the blocking lease expires, the
    retry admits and the fold serves."""
    clock = VirtualClock()
    service, _loader = _service_with_doc(sets=3)
    server = OrderingServer(service, catchup_max_inflight=1, clock=clock)
    _v, token = server.admission_control.admit()
    server.admission_control.release(token, hold=1.5)  # occupied 1.5s
    counters = LockedCounterSet()
    out = RetryPolicy(max_attempts=6, budget=60.0).run(
        lambda: server._dispatch(_Session(), "catchup", {"docs": ["doc"]}),
        operation="storm catchup",
        sleep=clock.sleep,
        rng=random.Random(0),
        counters=counters,
    )
    assert out["lane"] == "fold"
    snap = server.admission.snapshot()
    assert snap["catchup.shed"] >= 1
    assert counters.get("retry.nack_holds") >= 1
    assert counters.get("retry.retries") >= 1


# --- degraded-mode serving -----------------------------------------------------


def test_degraded_serving_after_sustained_overload_converges():
    """Sustained overload serves the STORED summary at an older
    ref_seq; a client loading that summary plus the durable tail lands
    byte-identical to the fresh fold — freshness weakened, convergence
    untouched."""
    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.runtime.registry import default_registry

    service, loader = _service_with_doc(sets=2, summarize_at_head=True)
    _append_op(service)  # grow the tail PAST the stored summary
    server = OrderingServer(
        service, catchup_max_inflight=1, clock=VirtualClock(),
        mc=_mc(**{"Catchup.DegradeAfter": 0}))
    _v, _token = server.admission_control.admit()  # saturate; never freed
    out = server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert out["lane"] == "degraded"
    assert out["degraded"] == ["doc"]
    handle, ref_seq = out["docs"]["doc"]
    assert ref_seq < service.oplog.head("doc")  # genuinely stale
    snap = server.admission.snapshot()
    assert snap["catchup.degraded"] == 1
    assert snap["catchup.degraded_docs"] == 1
    # convergence: stored summary + durable tail == full fresh state
    rt = ContainerRuntime(default_registry())
    rt.load(service.storage.read(handle))
    for msg in service.oplog.get("doc", from_seq=ref_seq):
        rt.process(msg)
    check = loader.resolve("doc")
    assert rt.summarize().digest() == check.runtime.summarize().digest()
    check.close()


def test_degraded_serve_gate_off_sheds_instead():
    service, _loader = _service_with_doc(sets=2, summarize_at_head=True)
    _append_op(service)
    server = OrderingServer(
        service, catchup_max_inflight=1, clock=VirtualClock(),
        mc=_mc(**{"Catchup.DegradeAfter": 0,
                  "Catchup.DegradedServe": "off"}))
    server.admission_control.admit()
    with pytest.raises(NackError) as exc_info:
        server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert exc_info.value.code == "overloaded"
    snap = server.admission.snapshot()
    assert snap["catchup.degraded"] == 0
    assert snap["catchup.shed"] == 1


def test_retry_after_counter_sums_the_pacing_handed_out():
    """``catchup.retry_after_s`` is the sum of the retryAfter values on
    the nacks callers received — the hold the pacing put on them —
    while the counter balance stays exact."""
    service, _loader = _service_with_doc(sets=2)
    server = OrderingServer(
        service, catchup_max_inflight=1, clock=VirtualClock(),
        mc=_mc(**{"Catchup.DegradedServe": "off"}))
    server.admission_control.admit()  # the one slot, never freed
    held = []
    for _ in range(5):
        with pytest.raises(NackError) as exc_info:
            server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
        assert exc_info.value.code == "overloaded"
        held.append(exc_info.value.retry_after)
    assert held == sorted(held) and held[-1] > held[0]  # the streak paces
    snap = server.admission.snapshot()
    assert snap["catchup.retry_after_s"] == pytest.approx(sum(held))
    assert snap["catchup.shed"] == 5
    assert snap["catchup.requests"] == (
        snap["catchup.admitted"] + snap["catchup.shed"]
        + snap["catchup.degraded"])
    assert snap["catchup.serve_s"] == 0  # no fold was served


def test_drain_retry_after_is_gate_configurable():
    server = OrderingServer(LocalOrderingService(),
                            mc=_mc(**{"Server.DrainRetryAfter": 2.5}))
    server.draining = True
    assert server._dispatch(_Session(), "ping", {}) == "pong"
    with pytest.raises(NackError) as exc_info:
        server._dispatch(_Session(), "has_document", {"doc": "d"})
    assert exc_info.value.code == "shuttingDown"
    assert exc_info.value.retry_after == 2.5


# --- the catchup fault seams ---------------------------------------------------


def test_catchup_fail_releases_slot_and_caller_retries():
    service, _loader = _service_with_doc(sets=3)
    injector = FaultInjector(FaultPlan(seed=1, points=(
        FaultPoint("catchup.fail", "fail", at=1),
    )))
    server = OrderingServer(service, catchup_max_inflight=1,
                            clock=VirtualClock(), faults=injector)
    with pytest.raises(OSError):
        server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    # the admission lease was released by the finally, no flight is
    # stranded, and the immediate retry serves
    assert server.admission_control.snapshot()["inflight"] == 0
    assert server._catchup.cache._flights == {}
    out = server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert out["lane"] == "fold"
    assert injector.snapshot() == {"catchup.fail:fail": 1}
    assert injector.unfired() == []


def test_catchup_slow_raises_measured_cost_and_pacing():
    clock = VirtualClock()
    service, _loader = _service_with_doc(sets=3)
    injector = FaultInjector(FaultPlan(seed=1, points=(
        FaultPoint("catchup.slow", "delay", at=1, arg=3.0),
    )))
    server = OrderingServer(service, catchup_max_inflight=1, clock=clock,
                            faults=injector)
    out = server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert out["lane"] == "fold"
    assert injector.snapshot() == {"catchup.slow:delay": 1}
    # the injected delay registered in the measured-cost EMA...
    assert server.admission_control.snapshot()["cost_ema"] > 1.0
    # ...and the next overload's pacing reflects the slower tier (grow
    # the tail so the request needs a fold, then saturate the one slot)
    _append_op(service)
    server.admission_control.admit()
    with pytest.raises(NackError) as exc_info:
        server._dispatch(_Session(), "catchup", {"docs": ["doc"]})
    assert exc_info.value.retry_after > 1.0


def test_catchup_sites_validate_and_chaos_harness_rejects_them(tmp_path):
    FaultPoint("catchup.slow", "delay", at=1, arg=0.5).validate()
    FaultPoint("catchup.fail", "fail").validate()
    with pytest.raises(ValueError):
        FaultPoint("catchup.slow", "fail").validate()
    from fluidframework_tpu.testing.load import (ChaosLoadSpec,
                                                 run_chaos_load)
    spec = ChaosLoadSpec(
        seed=1, shards=2, docs=2, clients_per_doc=1, steps=10,
        plan=FaultPlan(seed=1, points=(
            FaultPoint("catchup.fail", "fail"),
        )))
    with pytest.raises(ValueError, match="catchup"):
        run_chaos_load(spec)


# --- the storm scenario (10³ tier-1 smoke of the acceptance run) ---------------


def test_storm_smoke_converges_balances_and_replays():
    """The 10⁴ acceptance run at smoke scale: herd joins through the
    REAL catchup path survive with the admission counters balancing
    exactly (admitted + shed + degraded = requests), every shed and
    degraded client converges byte-identical to the never-shed oracle,
    the catchup fault seams fire, and the whole run — counters
    included — replays bit-identically."""
    from fluidframework_tpu.testing.scenarios import (build_scenario,
                                                      oracle_spec,
                                                      run_swarm)

    spec = build_scenario("catchup-storm", seed=3, clients=800, docs=8,
                          shards=4)
    result = run_swarm(spec)
    storm = result.storm
    assert storm["served"] == storm["requests"] > 0
    assert storm["shed"] > 0 or storm["degraded"] > 0, \
        "the storm must actually overload the fold lane"
    assert storm["warm"] > 0, "the warm priority lane must serve"
    admission = storm["admission"]
    assert admission["catchup.requests"] == (
        admission["catchup.admitted"] + admission["catchup.shed"]
        + admission["catchup.degraded"])
    assert result.fault_counts.get("catchup.slow:delay", 0) >= 1
    assert result.fault_counts.get("catchup.fail:fail", 0) >= 1
    assert storm["latency_p99_ticks"] <= 64.0
    # never-shed oracle: byte-identical state
    oracle = run_swarm(oracle_spec(spec, result))
    assert oracle.storm["shed"] == 0 and oracle.storm["degraded"] == 0
    assert result.sampled_digests == oracle.sampled_digests
    assert result.per_doc_head == oracle.per_doc_head
    # replay bit-identity, storm counters included
    assert run_swarm(spec).identity() == result.identity()


# --- front-door relay flow control ---------------------------------------------


class _FakeSock:
    """A socket double for PumpConnection: accepts every byte."""

    def __init__(self):
        self.sent = []

    def getpeername(self):
        return ("test", 0)

    def send(self, view):
        self.sent.append(bytes(view))
        return len(view)

    def shutdown(self, how):
        pass

    def close(self):
        pass


class _FakePump:
    """Pump double: flushing is EXPLICIT (`drain(conn)`), which is the
    event-loop model's laggard — a connection whose kernel buffer has
    not accepted its bytes yet is simply one the loop has not drained."""

    def mark_dirty(self, conn):
        pass

    def drop(self, conn):
        conn.close()


def _frontdoor_shell(tmp_path, relay_budget):
    """A FrontDoor OBJECT (never started — no processes, no sockets):
    the relay fan-out and demotion paths are plain methods on it."""
    from fluidframework_tpu.service.frontdoor import FrontDoor

    return FrontDoor(str(tmp_path / "fd"), n_shards=1, spawn="thread",
                     relay_budget=relay_budget)


def test_relay_budget_demotes_laggard_without_collateral(tmp_path):
    from fluidframework_tpu.service.framepump import PumpConnection

    fd = _frontdoor_shell(tmp_path, relay_budget=300)
    pump = _FakePump()
    # the healthy reader gets a roomy budget (a burst may momentarily
    # outpace the loop's flush passes); the stalled one a tight 300 B
    fast = PumpConnection(_FakeSock(), pump, relay_budget=1 << 20)
    slow = PumpConnection(_FakeSock(), pump, relay_budget=300)
    for s in (fast, slow):
        s.subscribed.add("doc")
    fd._subs["doc"] = [fast, slow]
    frame = {"v": 1, "event": "op", "doc": "doc", "msg": {"pad": "x" * 80}}
    for _ in range(12):
        fd._relay_event(frame)  # slow is never flushed: a stopped reader
    # the laggard was demoted from this doc's fan-out, once
    assert fd.counters.get("fd.relay_demotions") == 1
    assert slow not in fd._subs["doc"]
    assert fast in fd._subs["doc"]
    # its queued bytes stayed bounded: budget + the priority demote frame
    assert slow.pending_bytes() < 300 + 200
    # the fast client sees every frame once the loop flushes it,
    # unstalled by the laggard
    assert fast.flush()
    assert len(fast.sock.sent) == 12
    # the laggard's reader returns: its bounded queue drains and the
    # DEMOTED notice arrives (first — it jumped the queue)
    assert slow.flush()
    assert slow.relay_pending() == 0 and slow.pending_bytes() == 0
    assert b'"demoted"' in slow.sock.sent[0]
    fast.close()
    slow.close()


def test_relay_priority_frames_bypass_budget():
    from fluidframework_tpu.service.framepump import PumpConnection

    conn = PumpConnection(_FakeSock(), _FakePump(), relay_budget=64)
    assert conn.relay(b"x" * 60)  # first frame: queued, charged
    assert not conn.relay(b"y" * 60)  # budget exhausted, un-drained
    conn.relay_priority(b"z" * 60)  # control frame still enqueues
    assert conn.pending_bytes() > 64
    assert conn.relay_pending() == 60  # only relay() charges the budget
    assert conn.flush()
    assert conn.relay_pending() == 0 and conn.pending_bytes() == 0
    # priority frame jumped the queue: z drained before x
    assert conn.sock.sent == [b"z" * 60, b"x" * 60]
    conn.close()


def test_frontdoor_stats_roll_up_admission_and_relay(tmp_path):
    """Satellite pin: the supervisor stats() view aggregates every
    shard's admission counters (storm/degrade included) and reports the
    relay flow-control health — not just per-shard snapshots."""
    from fluidframework_tpu.service.frontdoor import FrontDoor

    fd = FrontDoor(str(tmp_path / "fd"), n_shards=2,
                   spawn="thread").start()
    try:
        stats = fd.stats()
        for key in ("catchup.requests", "catchup.admitted",
                    "catchup.shed", "catchup.degraded", "catchup.warm"):
            assert key in stats["admission"], key
        assert stats["relay"]["sessions"] == 0
        assert stats["relay"]["budget_per_session"] == 4 << 20
        assert "fd.relay_demotions" in stats["counters"]
    finally:
        fd.close()


# --- the TCP front door at 10⁴ real connections (slow tier) --------------------


_LEN = struct.Struct(">I")


def _ping(sock):
    import json as _json

    payload = _json.dumps(
        {"v": 1, "id": 1, "method": "ping", "params": {}}).encode()
    sock.sendall(_LEN.pack(len(payload)) + payload)
    header = b""
    while len(header) < 4:
        header += sock.recv(4 - len(header))
    (length,) = _LEN.unpack(header)
    body = b""
    while len(body) < length:
        body += sock.recv(length - len(body))
    return _json.loads(body)


def _proc_rss_kb(pid: int) -> int:
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    return 0


@pytest.mark.slow
def test_tcp_front_door_10k_connections():
    """PR 10 left the TCP front door 'unexplored' at 10⁴+ real
    connections.  Pin accept/connect behavior (every connection
    accepted and answering) and the per-connection SERVER memory bound
    — the asyncio single-server shape, run as its own process exactly
    like a deployment (and so each side's fd budget holds one end)."""
    import resource
    import subprocess
    import sys as _sys

    conns = 10_000
    need = conns + 2048
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard < need:
        pytest.skip(f"fd hard limit {hard} < {need}")
    if soft < need:
        resource.setrlimit(resource.RLIMIT_NOFILE, (need, hard))
    proc = subprocess.Popen(
        [_sys.executable, "-m", "fluidframework_tpu.service.server",
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    socks = []
    try:
        line = proc.stdout.readline()
        assert "listening on" in line, line
        port = int(line.rsplit(":", 1)[1])
        rss_before = _proc_rss_kb(proc.pid)
        for _ in range(conns):
            socks.append(socket.create_connection(("127.0.0.1", port),
                                                  timeout=30))
        # every 100th connection answers (sampling keeps the wall
        # bounded; accept correctness is covered by the connects)
        for s in socks[::100] + [socks[0], socks[-1]]:
            assert _ping(s)["result"] == "pong"
        per_conn_kb = (_proc_rss_kb(proc.pid) - rss_before) / conns
        # an order-of-magnitude tripwire, not a microbenchmark: the
        # asyncio session state must stay in the tens of KB
        assert per_conn_kb < 100.0, f"{per_conn_kb:.1f} KB per connection"
        # the listener still accepts beyond 10⁴
        extra = socket.create_connection(("127.0.0.1", port), timeout=30)
        assert _ping(extra)["result"] == "pong"
        extra.close()
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        proc.kill()
        proc.wait(timeout=10)


@pytest.mark.slow
def test_frontdoor_accepts_two_thousand_connections(tmp_path):
    """The routing front door is thread-per-connection: pin accept
    behavior and responsiveness at 2×10³ concurrent clients (its
    documented scale ceiling sits below the asyncio server's)."""
    from fluidframework_tpu.service.frontdoor import FrontDoor

    fd = FrontDoor(str(tmp_path / "fd"), n_shards=1,
                   spawn="thread").start()
    socks = []
    try:
        for _ in range(2000):
            socks.append(socket.create_connection(
                ("127.0.0.1", fd.port), timeout=30))
        for s in socks[::50] + [socks[0], socks[-1]]:
            assert _ping(s)["result"] == "pong"
        assert fd.stats()["relay"]["sessions"] == 2000
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        fd.close()
