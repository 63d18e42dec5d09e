"""One run of one cell: set up, measure a window, check it, report it.

A cell ``<config>.<traffic>`` is found through ``BENCHMARK.json``: its
configuration file (the deployment: document count, tail length, corpus
generator, channel, reference family) and ``traffic/<traffic>.json``
(the mix the one generator in ``traffic_gen`` reads).  Each metric, end
to end or per layer, is read by ``metrics/<metric name>.py``.  Adding a
cell, a mix, a configuration or a metric is adding files and entries.

The run, in one process:

1. set-up (``served``): build the corpus from the seed into an
   in-process ``LocalOrderingService``, start an ``OrderingServer`` on
   port 0, connect a ``NetworkDocumentServiceFactory``, and warm up with
   the cell's own traffic on documents the window never asks for, until
   a warm-up request meets no new program;
2. the window (``measure``): the traffic, through the ``catchup`` RPC,
   with the profiler on when ``trace`` is set;
3. after it: the chip's peak memory, the counters, and the comparison of
   every summary answered in the window with the plain reference at the
   document's head;
4. the result: earlier lines say how the run went; the compared numbers
   close standard error; the last line of standard output is the JSON
   result.

``sweep.py`` drives the same set-up and windows at other rates.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import logging
import os
import random
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
COMPILE_LOGGER = "jax._src.interpreters.pxla"


def log(msg: str) -> None:
    print(msg, flush=True)


# -- discovery ---------------------------------------------------------------


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def metric_reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or not os.path.exists(path):
        raise KeyError(f"no reader {path} for metric {name!r}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks_for(device_kind: str, table: dict = None) -> dict:
    if table is None:
        with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
            table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (have {sorted(table)})")
    return table[device_kind]


# -- compile accounting ------------------------------------------------------


class CompileMeter(logging.Handler):
    """Backend compiles, their seconds, and persistent-cache hits, as JAX
    reports them, while entered; and the name and shapes of each program
    JAX prepares (``jax_log_compiles``), compiled or loaded."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.compiles = 0
        self.compile_sec = 0.0
        self.cache_hits = 0
        self.programs: list = []

    def emit(self, record) -> None:
        message = record.getMessage()
        if message.startswith("Compiling "):
            self.programs.append(message[len("Compiling "):][:160])

    def _on_duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compile_sec += duration_secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._logged = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger(COMPILE_LOGGER).addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        logging.getLogger(COMPILE_LOGGER).removeHandler(self)
        jax.config.update("jax_log_compiles", self._logged)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_sec, self.cache_hits


class GcMeter:
    """The interpreter's garbage collections while entered: how many of
    each generation, and the longest pause."""

    def __init__(self) -> None:
        self.counts = [0, 0, 0]
        self.longest = 0.0
        self._started = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
        else:
            self.counts[info["generation"]] += 1
            self.longest = max(self.longest,
                               time.perf_counter() - self._started)

    def __enter__(self) -> "GcMeter":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def report(self) -> str:
        return (f"generations 0/1/2: {self.counts[0]}/{self.counts[1]}/"
                f"{self.counts[2]}, longest pause {self.longest}s")


def setup_jax_cache() -> None:
    """Keep every compiled program in ``<checkout>/.jax_cache``, a fixed
    path, so that only a cell's first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the run -----------------------------------------------------------------


def _device_line(devices) -> dict:
    import jax
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    kind = devices[0].device_kind
    log(f"device: platform {devices[0].platform}, kind {kind}, count "
        f"{len(devices)}, jax {jax.__version__}, libtpu {libtpu}")
    return {"platform": devices[0].platform, "kind": kind,
            "count": len(devices)}


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def _answer_ok(req) -> bool:
    return req.error is None and req.answer is not None


def _warm_up(caller, order: list, traffic: dict, meter, deadline_s: float,
             platform: str) -> tuple:
    """Send the cell's own requests, each on documents the window never
    asks for, until one compiles nothing (programs loaded from the
    persistent cache do not count as compiles): ``(docs used, requests,
    compile snapshot delta, quiet)``."""
    from .traffic_gen import Request

    spec = traffic["warmup"]
    per = spec["docs_per_request"]
    used = n = 0
    c0 = meter.snapshot()
    caller.t0 = caller.clock()
    while True:
        before = meter.snapshot()
        req = caller.send(Request(order[used:used + per], caller.now()),
                          deadline_s)
        used += per
        n += 1
        if not _answer_ok(req) or req.answer["platform"] != platform:
            raise RuntimeError(f"warm-up request {n} failed: {req.error} "
                               f"{(req.answer or {}).get('platform')}")
        after = meter.snapshot()
        quiet = after[0] == before[0]
        if (quiet and n >= spec["min_requests"]) \
                or n >= spec["max_requests"]:
            break
    c1 = meter.snapshot()
    log(f"warm-up programs prepared: {meter.programs}")
    return used, n, (c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2]), quiet


def request_order(doc_ids: list, seed: int) -> list:
    """The documents in the order requests will name them, shuffled by
    the seed."""
    order = list(doc_ids)
    random.Random(seed).shuffle(order)
    return order


def _channel_blobs(tree, channel: dict) -> dict:
    node = tree.get(f".datastores/{channel['ds']}/{channel['id']}")
    return {k: v.content for k, v in node.children.items()}


def check_window(reqs, reference_tail, service, cfg: dict, platform: str,
                 check) -> dict:
    """Every document asked for in the window against the reference at
    the document's head: the configuration promises a fold at the head.
    ``reference_tail(doc)`` makes the document's tail afresh from the
    seed: the reference never reads an object the program was given."""
    counts = {"wrong_docs": 0, "skipped_docs": 0, "unanswered_docs": 0,
              "off_chip_docs": 0}
    failed_docs = set()
    reasons = []
    ops_folded = docs_fresh = summary_bytes = 0
    for req in reqs:
        if not _answer_ok(req):
            counts["unanswered_docs"] += len(req.docs)
            failed_docs.update(req.docs)
            reasons.append(f"{req.docs[0]}: {req.error}")
            continue
        ans = req.answer
        if ans["lane"] == "fold" and ans["deviceDocs"] \
                and ans["platform"] != platform:
            counts["off_chip_docs"] += len(req.docs)
            failed_docs.update(req.docs)
        for doc in req.docs:
            if doc not in ans["docs"]:
                counts["skipped_docs"] += 1
                failed_docs.add(doc)
                continue
            handle, seq = ans["docs"][doc]
            tail = reference_tail(doc)
            head = tail[-1][0]
            why = None if seq == head else f"seq {seq}, head {head}"
            if why is None:
                blobs = _channel_blobs(service.storage.read(handle),
                                       cfg["channel"])
                why = check(blobs, tail, seq)
                if why is None:
                    ops_folded += len(tail)
                    docs_fresh += 1
                    summary_bytes += sum(len(b) for b in blobs.values())
            if why is not None:
                counts["wrong_docs"] += 1
                failed_docs.add(doc)
                reasons.append(f"{doc}: {why}")
    return {"compared": counts, "failed_docs": len(failed_docs),
            "reasons": reasons, "ops_folded": ops_folded,
            "docs_fresh": docs_fresh, "summary_bytes": summary_bytes}


def _lanes(reqs) -> dict:
    lanes = {}
    for req in reqs:
        if _answer_ok(req):
            lane = req.answer["lane"]
            lanes[lane] = lanes.get(lane, 0) + 1
    return lanes


def _sum_answers(reqs, key: str) -> int:
    return sum(int(req.answer.get(key, 0)) for req in reqs
               if _answer_ok(req))


def _delta(after: dict, before: dict) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)}


@contextlib.contextmanager
def served(cfg: dict, traffic: dict, seed: int, platform: str, meter):
    """Set-up: the corpus from the seed in an in-process ordering
    service, a server on port 0 with the configuration's gates, a client
    connection (and, for an open
    loop, one per user), and warm shapes.  Yields the namespace the
    windows run on; drains and seals the server on the way out."""
    from fluidframework_tpu.drivers.network_driver import (
        NetworkDocumentServiceFactory,
    )
    from fluidframework_tpu.protocol.messages import NackError
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.server import OrderingServer
    from fluidframework_tpu.utils.telemetry import (
        ConfigProvider,
        MonitoringContext,
    )

    from .corpus import generator
    from .corpus.envelope import seed_store
    from .traffic_gen import Caller

    split = {}
    t = time.perf_counter()
    gen = generator(cfg["corpus"])
    doc_ids = [f"{cfg['name']}-{i}" for i in range(cfg["docs"])]
    service = LocalOrderingService()
    seed_store(service, doc_ids,
               [gen(seed, i, cfg["tail_ops"]) for i in range(len(doc_ids))],
               cfg["channel"])
    order = request_order(doc_ids, seed)
    split["corpus_s"] = time.perf_counter() - t
    log(f"corpus: {len(doc_ids)} {cfg['family']} documents x "
        f"{cfg['tail_ops']} ops from seed {seed}, "
        f"{len(doc_ids) * cfg['tail_ops']} ops in all")

    t = time.perf_counter()
    srv = OrderingServer(service, port=0, mc=MonitoringContext(
        config=ConfigProvider(cfg.get("server_gates", {}))))
    srv.start_in_thread()
    factory = NetworkDocumentServiceFactory(port=srv.port)
    split["server_s"] = time.perf_counter() - t
    users = []
    try:
        t = time.perf_counter()
        caller = Caller(factory._rpc, NackError)
        used, n_warm, compiles, quiet = _warm_up(
            caller, order, traffic, meter, float(traffic["deadline_s"]),
            platform)
        split["warmup_s"] = time.perf_counter() - t
        log(f"warm-up: {n_warm} requests, {used} documents, "
            f"{compiles[0]} backend compiles taking {compiles[1]}s, "
            f"{compiles[2]} persistent-cache hits, last request "
            f"{'compiled nothing' if quiet else 'compiled'}")
        if traffic["loop"] == "open":
            users = [NetworkDocumentServiceFactory(port=srv.port)
                     for _ in range(traffic["connections"])]
        yield SimpleNamespace(
            cfg=cfg, seed=seed, gen=gen, doc_ids=doc_ids, service=service,
            srv=srv, factory=factory, users=users, order=order, used=used,
            meter=meter, split=split, nack_error=NackError)
    finally:
        for user in [factory] + users:
            user.close()
        import asyncio

        asyncio.run_coroutine_threadsafe(
            srv.drain_and_seal(timeout=10), srv.loop).result(timeout=60)


def measure(s, traffic: dict, seconds: float, trace: bool) -> dict:
    """One window of ``traffic`` over the documents set-up has not used,
    with the profiler on where ``trace`` is set.  Returns the requests and
    what the counters, the compile meter and the collector saw; the
    documents it asked for are used up."""
    import jax

    from . import trace_reduce
    from .traffic_gen import Caller, arrival_times

    docs = s.order[s.used:]
    per = traffic["docs_per_request"]
    deadline_s = float(traffic["deadline_s"])
    if traffic["loop"] == "open":
        due = arrival_times(max(1, round(traffic["rate_per_s"] * seconds)),
                            seconds, traffic["arrival_seed"])
    # The corpus and the warm-up leave millions of long-lived objects; a
    # full collection that walks them holds the interpreter for a second
    # or more.  Freeze them, as a server does after loading its state.
    gc.freeze()
    catchup = s.srv._catchup
    stage0 = dict(catchup.pipeline_stage)
    server0 = s.srv.admission.snapshot()
    c0 = s.meter.snapshot()
    programs0 = len(s.meter.programs)
    trace_dir = annotate = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profiler_options())
        annotate = jax.profiler.TraceAnnotation
    caller = Caller(s.factory._rpc, s.nack_error, annotate=annotate)
    ran_out = False
    pauses = GcMeter()
    try:
        window = (annotate("benchmark.window") if annotate
                  else contextlib.nullcontext())
        with window, pauses:
            if traffic["loop"] == "closed":
                reqs, ran_out = caller.closed_loop(docs, per, seconds,
                                                   deadline_s)
            else:
                reqs = caller.open_loop(docs, due, per, deadline_s,
                                        [u._rpc for u in s.users])
    finally:
        if trace:
            jax.profiler.stop_trace()
    gc.unfreeze()
    s.used += sum(len(r.docs) for r in reqs)
    c1 = s.meter.snapshot()
    return {
        "reqs": reqs, "ran_out": ran_out, "left": len(docs) - sum(
            len(r.docs) for r in reqs),
        "window_s": max(r.done for r in reqs),
        "stage": _delta(catchup.pipeline_stage, stage0),
        "server": _delta(s.srv.admission.snapshot(), server0),
        "compiles": (c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2]),
        "programs": s.meter.programs[programs0:], "pauses": pauses,
        "trace_dir": trace_dir,
    }


def _reduce_trace(trace_dir: str) -> dict:
    from . import trace_reduce

    try:
        path = next(
            os.path.join(d, f) for d, _s, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb"))
        events = trace_reduce.events_from_xplane(path)
        starts = [e[1] for evs in events[0].values() for e in evs]
        log(f"trace events: {len(events[1])} host, {len(starts)} device "
            f"on {sorted(events[0])}; device events span "
            f"{(min(starts) - events[2][0]) / 1e9 if starts else None}s "
            f"to {(max(starts) - events[2][0]) / 1e9 if starts else None}"
            f"s of the {(events[2][1] - events[2][0]) / 1e9}s window")
        return trace_reduce.reduce_events(*events)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def log_window(win: dict) -> None:
    """The earlier lines that say how a window went."""
    reqs = win["reqs"]
    lateness = sorted(r.sent - r.due for r in reqs)
    compiles = win["compiles"]
    log(f"window: {win['window_s']}s, {len(reqs)} requests, "
        f"{sum(len(r.docs) for r in reqs)} documents; corpus ran out: "
        f"{'yes' if win['ran_out'] else 'no'} ({win['left']} uncaught "
        f"documents left)")
    log(f"lanes: answers {_lanes(reqs)}; server counters "
        f"{dict(sorted(win['server'].items()))}")
    skipped = sum(len(r.answer.get("skipped", ())) for r in reqs
                  if _answer_ok(r))
    log(f"client: sends {sum(r.sends for r in reqs)}, sheds held and "
        f"resent {sum(r.sheds for r in reqs)}, failed requests "
        f"{sum(1 for r in reqs if not _answer_ok(r))}, skipped documents "
        f"{skipped}")
    log(f"compiles inside the window: {compiles[0]} backend compiles "
        f"taking {compiles[1]}s, {compiles[2]} persistent-cache hits; "
        f"programs prepared {win['programs']}")
    log(f"garbage collections inside the window: {win['pauses'].report()}")
    log(f"generator lateness (s): max {lateness[-1]}, p95 "
        f"{lateness[max(0, int(0.95 * len(lateness)) - 1)]}, median "
        f"{lateness[len(lateness) // 2]}")
    log("pipeline busy over the window (s): " + ", ".join(
        f"{k} {v}" for k, v in sorted(win["stage"].items())))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", t_start: float = None,
             overrides: dict = None, peaks_table: dict = None,
             fault: str = None) -> dict:
    """One run; returns the result object (also printed last).
    ``overrides`` ({"config": {...}, "traffic": {...}}) and
    ``peaks_table`` serve the CPU rehearsals; ``fault`` plants one of
    ``faults.FAULTS`` for the control and the fault tests."""
    t_start = time.perf_counter() if t_start is None else t_start
    found = find_cell(load_spec(), workload)
    cfg = dict(found["config"], **(overrides or {}).get("config", {}))
    traffic = dict(found["traffic"], **(overrides or {}).get("traffic", {}))

    import jax

    from . import faults

    check = importlib.import_module(
        f"{__package__}.reference.{cfg['family']}_ref").check
    devices = jax.devices()[:found["cell"]["chips"]]
    device = _device_line(devices)
    peaks = peaks_for(device["kind"], peaks_table)

    with CompileMeter() as meter, faults.planted(fault), \
            served(cfg, traffic, seed, platform, meter) as s:
        setup_s = time.perf_counter() - t_start
        s.split["total_s"] = setup_s
        log("set-up split (s): " + ", ".join(
            f"{k} {v}" for k, v in s.split.items()))
        win = measure(s, traffic, seconds, trace)
        memory_peak = _memory_peak(devices)

    reduced = None
    if trace:
        reduced = _reduce_trace(win["trace_dir"])
    reqs = win["reqs"]
    log_window(win)
    if reduced is not None:
        log(f"trace: busy {reduced['busy_s']}s of {reduced['window_s']}s; "
            f"device ops {reduced['device_ops'][:3]}; idle gaps "
            f"{reduced['idle_gaps'][:3]}")

    t = time.perf_counter()
    n_docs_win = sum(len(r.docs) for r in reqs)
    index = {d: i for i, d in enumerate(s.doc_ids)}
    verdict = check_window(
        reqs, lambda d: s.gen(seed, index[d], cfg["tail_ops"]), s.service,
        cfg, platform, check)
    log(f"reference check: {n_docs_win} documents in "
        f"{time.perf_counter() - t}s, {verdict['docs_fresh']} at their "
        f"head and equal; first faults {verdict['reasons'][:3]}")

    run = {
        "setup_s": setup_s, "window_s": win["window_s"],
        "latencies_s": [r.latency for r in reqs],
        "requests": len(reqs),
        "docs_attempted": n_docs_win,
        "ops_folded": verdict["ops_folded"],
        "docs_fresh": verdict["docs_fresh"],
        "summary_bytes": verdict["summary_bytes"],
        "answers": {k: _sum_answers(reqs, k) for k in
                    ("cpuDocs", "fallbackChannels")},
        "server": win["server"], "stage": win["stage"], "trace": reduced,
        "peaks": peaks,
    }
    wanted = found["per_layer"] if trace else found["end_to_end"]
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"failed documents by kind: {verdict['compared']}")
    limits = cfg["limits"]
    compared = {"failed_docs": {"value": verdict["failed_docs"],
                                "limit": limits["failed_docs"]}}
    correct = n_docs_win > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": n_docs_win,
              "failed": verdict["failed_docs"], "metrics": metrics,
              "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result
