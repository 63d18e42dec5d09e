"""Spans and counters inside the served catch-up path.

- ``utils.telemetry.span``: accumulates, nests, closes on an exception,
  adds without lost updates from many threads, and never imports JAX;
- a served catch-up through ``OrderingServer`` fills the catch-up stage
  keys and the server's float-second counters, and a CPU profiler capture
  of it holds the server and pipeline spans on its host plane;
- a document routed to the oracle makes ``stage["fallback"]`` positive,
  before the pack and after the fold, in both kernel families;
- spans are per request, chunk or call: a catch-up of 8 documents and
  one of 64 (one chunk each) record the same spans;
- the served path's jitted programs carry stable module names.
"""

import asyncio
import collections
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import bench
from fluidframework_tpu.utils import telemetry
from fluidframework_tpu.utils.telemetry import (
    ConfigProvider,
    LockedCounterSet,
    MonitoringContext,
    span,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    entry and exit by name."""

    events: list = []

    def __init__(self, name, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        _StubAnnotation.events.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _StubAnnotation.events.append(("exit", self.name))

    @staticmethod
    def is_enabled():
        return True

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def stub_annotation(monkeypatch):
    import jax.profiler

    _StubAnnotation.events = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _StubAnnotation)
    return _StubAnnotation


# -- the primitive --------------------------------------------------------------


def test_span_accumulates_nests_and_closes_on_exception(stub_annotation):
    acc: dict = {}
    with span("outer", acc, "a"):
        with span("inner", acc, "b"):
            pass
        with span("inner", acc, "b") as inner:
            inner.set(docs=3)
    assert acc["a"] >= acc["b"] > 0
    before = acc["a"]
    with pytest.raises(ValueError):
        with span("outer", acc, "a"):
            raise ValueError("the section failed")
    assert acc["a"] > before
    assert stub_annotation.events == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("enter", "inner"), ("exit", "inner"), ("exit", "outer"),
        ("enter", "outer"), ("exit", "outer")]
    # the key defaults to the name; a CounterSet is bumped
    with span("pipeline.pack", acc):
        pass
    assert acc["pipeline.pack"] > 0
    counters = LockedCounterSet("catchup.serve_s")
    with span("catchup.serve", counters, "catchup.serve_s"):
        pass
    assert counters.get("catchup.serve_s") > 0
    with span("no aggregate") as bare:
        assert bare.recording
    assert "no aggregate" not in acc


def test_span_adds_without_lost_updates_across_threads(monkeypatch):
    """Pack and extract threads add to the same stage keys: each span
    here lasts exactly one tick of a per-thread fake clock, so any lost
    read-modify-write shows as a short total."""
    ticks = threading.local()

    class _Clock:
        @staticmethod
        def perf_counter():
            ticks.t = getattr(ticks, "t", 0.0) + 1.0
            return ticks.t

    monkeypatch.setattr(telemetry, "time", _Clock)
    acc: dict = {}
    n_threads, per_thread = 16, 2000
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with span("pipeline.extract", acc, "extract"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert acc["extract"] == n_threads * per_thread


def test_span_never_imports_jax():
    code = (
        "import sys\n"
        "from fluidframework_tpu.utils.telemetry import span\n"
        "acc = {}\n"
        "with span('catchup.serve', acc, 'serve', rid=1) as s:\n"
        "    s.set(verdict='admit')\n"
        "assert acc['serve'] >= 0\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# -- the served path ------------------------------------------------------------


def _serve(doc_count: int, ops: int = 16):
    """An ``OrderingServer`` on a fresh corpus (single-device fold) and a
    client connection: ``(server, factory, doc ids)``."""
    from fluidframework_tpu.drivers.network_driver import (
        NetworkDocumentServiceFactory,
    )
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.server import OrderingServer

    service = LocalOrderingService()
    doc_ids = bench.build_catchup_corpus(service, doc_count, ops)
    srv = OrderingServer(service, port=0, mc=MonitoringContext(
        config=ConfigProvider({"Catchup.Mesh": "off"})))
    srv.start_in_thread()
    return srv, NetworkDocumentServiceFactory(port=srv.port), doc_ids


def _stop(srv, factory) -> None:
    factory.close()
    asyncio.run_coroutine_threadsafe(
        srv.drain_and_seal(timeout=10), srv.loop).result(timeout=60)


CATCHUP_STAGES = ("serial_wait", "prepare", "assemble", "publish")
SERVER_SECONDS = ("catchup.queued_s", "catchup.serve_s",
                  "catchup.retry_after_s")


def test_served_catchup_fills_stage_keys_and_server_counters():
    srv, factory, doc_ids = _serve(8)
    try:
        answer = factory._rpc.request("catchup", {"docs": doc_ids},
                                      timeout=300)
        stage = dict(srv._catchup.pipeline_stage)
        server = srv.admission.snapshot()
    finally:
        _stop(srv, factory)
    assert answer["lane"] == "fold" and len(answer["docs"]) == 8
    for key in CATCHUP_STAGES:
        assert key in stage, key
    for key in ("prepare", "assemble", "publish", "pack", "device_wait",
                "extract"):
        assert stage[key] > 0, key
    assert stage["fallback"] == 0.0  # seeded: no document left the device
    for key in SERVER_SECONDS:
        assert key in server, key
    assert server["catchup.queued_s"] > 0
    assert server["catchup.serve_s"] >= stage["serial_wait"] \
        + stage["device_wait"]
    assert server["catchup.retry_after_s"] == 0  # nothing was shed
    assert server["catchup.requests"] == server["catchup.admitted"] == 1


def _host_span_names(trace_dir) -> set:
    from jax.profiler import ProfileData

    names = set()
    for d, _s, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                data = ProfileData.from_file(os.path.join(d, f))
                for plane in data.planes:
                    if plane.name.startswith("/host:"):
                        for line in plane.lines:
                            names.update(e.name for e in line.events)
    return names


def test_cpu_profiler_capture_holds_the_served_spans(tmp_path):
    import jax

    srv, factory, doc_ids = _serve(4)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            factory._rpc.request("catchup", {"docs": doc_ids}, timeout=300)
        finally:
            jax.profiler.stop_trace()
    finally:
        _stop(srv, factory)
    names = _host_span_names(tmp_path)
    for name in ("catchup.serve", "catchup.admit", "catchup.respond",
                 "catchup.serial_wait", "catchup.prepare",
                 "catchup.assemble", "catchup.publish", "pipeline.pack",
                 "pipeline.dispatch", "pipeline.device_wait",
                 "pipeline.download", "pipeline.extract"):
        assert name in names, (name, sorted(n for n in names
                                            if "." in n)[:40])


def test_span_count_does_not_grow_with_documents(stub_annotation):
    """8 and 64 documents, each one chunk: the same spans, by name and
    number — nothing on the served path is per document."""
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    counts = []
    for n in (8, 64):
        service = LocalOrderingService()
        doc_ids = bench.build_catchup_corpus(service, n, 16)
        svc = CatchupService(service, mesh=None)
        stub_annotation.events = []
        svc.catch_up(doc_ids, upload=False)
        assert svc.pipeline_stats.get("fallback_docs", 0) == 0
        counts.append(collections.Counter(
            name for kind, name in stub_annotation.events
            if kind == "enter"))
    assert counts[0] == counts[1]
    once = {name for name, n in counts[0].items() if n == 1}
    assert {"pipeline.pack", "catchup.serial_wait"} <= once


# -- host fallbacks -------------------------------------------------------------


def _removers_past_cap_doc(doc_id: str):
    """One insert, then more clients removing the same text from the same
    view than the device fold has overlap slots for (the winner plus
    ``OV_SLOT_CAP``), so the extractor takes the oracle after the fold."""
    from fluidframework_tpu.ops.mergetree_kernel import (
        OV_SLOT_CAP,
        MergeTreeDocInput,
    )
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    ops = [SequencedMessage(seq=1, client_id="c0", client_seq=1, ref_seq=0,
                            min_seq=0, type=MessageType.OP,
                            contents={"kind": "insert", "pos": 0,
                                      "text": "abcdef"})]
    for k in range(OV_SLOT_CAP + 2):
        ops.append(SequencedMessage(
            seq=2 + k, client_id=f"c{k + 1}", client_seq=1, ref_seq=1,
            min_seq=0, type=MessageType.OP,
            contents={"kind": "remove", "start": 1, "end": 4}))
    return MergeTreeDocInput(doc_id=doc_id, ops=ops, final_seq=ops[-1].seq,
                             final_msn=0)


def test_post_fold_fallback_is_timed_in_stage():
    from fluidframework_tpu.ops.mergetree_kernel import (
        oracle_fallback_summary,
    )
    from fluidframework_tpu.ops.pipeline import pipelined_mergetree_replay

    docs = [_removers_past_cap_doc("fb")] + [bench.synth_doc(i, 16)
                                              for i in range(3)]
    stage: dict = {}
    stats: dict = {}
    out = pipelined_mergetree_replay(docs, stage=stage, stats=stats)
    assert stats["fallback_docs"] == 1
    assert stats["fallback_overflow"] == 1  # the route's own counter
    assert stage["fallback"] > 0
    assert stage["extract"] >= stage["fallback"]  # counted inside it
    assert out[0].digest() == oracle_fallback_summary(docs[0]).digest()


def test_pre_pack_fallback_is_timed_in_stage():
    from fluidframework_tpu.ops.batching import partition_replay

    stage: dict = {}
    stats: dict = {}
    out = partition_replay(
        list(range(6)), known_fallback=lambda d: "odd" if d % 2 else None,
        fallback_fn=lambda d: -d, batch_fn=lambda b: [d * 10 for d in b],
        stats=stats, stage=stage)
    assert out == [0, -1, 20, -3, 40, -5]
    assert stats == {"fallback_docs": 3, "fallback_odd": 3}
    assert stage["fallback"] > 0


def test_tree_fallbacks_are_timed_in_stage():
    """The tree family routes revive and multi-id moves off before the
    pack and MAX_DEPTH overflows after the fold: both land in
    ``fallback``."""
    from fluidframework_tpu.ops.tree_pipeline import pipelined_tree_replay
    from tools.bench_kernels import synth_tree_messages, tree_doc, tree_shape

    docs = [tree_doc(i, synth_tree_messages(i, 40), 40) for i in range(10)]
    assert {"revive", "max_depth"} <= {tree_shape(i) for i in range(10)}
    stage: dict = {}
    stats: dict = {}
    pipelined_tree_replay(docs, chunk_docs=8, stage=stage, stats=stats)
    assert stats.get("fallback_revive", 0) >= 1
    assert stats.get("fallback_max_depth", 0) >= 1
    assert stage["fallback"] > 0


# -- program names --------------------------------------------------------------


def _mt_chunk():
    from fluidframework_tpu.ops.mergetree_kernel import (
        _export_flags,
        narrow_ops_for_upload,
        narrow_state_for_upload,
        pack_mergetree_batch,
    )

    state, ops, meta = pack_mergetree_batch(
        [bench.synth_doc(i, 16) for i in range(4)])
    flags = _export_flags(meta)
    return (narrow_state_for_upload(state, meta),
            narrow_ops_for_upload(ops, meta),
            np.asarray(meta["doc_base"], np.int32), meta, flags)


def _lower_mergetree(start: str, digest: bool):
    from fluidframework_tpu.ops.mergetree_kernel import (
        _export_cold_fn,
        _export_warm_fn,
    )

    state, ops, doc_base, meta, (i16, ob, ov, i8, props) = _mt_chunk()
    sequential = bool(meta.get("sequential"))
    if start == "cold":
        fn = _export_cold_fn(int(meta["_S"]), i16, ob, ov, i8,
                             sequential, props, digest=digest)
        return fn.lower(ops, doc_base)
    fn = _export_warm_fn(i16, ob, ov, i8, sequential, props,
                         digest=digest)
    return fn.lower(state, ops, doc_base)


def _tree_chunk():
    from fluidframework_tpu.ops.tree_kernel import pack_tree_batch
    from tools.bench_kernels import synth_tree_messages, tree_doc

    return pack_tree_batch([tree_doc(i, synth_tree_messages(i, 12), 12)
                            for i in (0, 1)])


def _lower_tree(digest: bool):
    from fluidframework_tpu.ops.tree_pipeline import _tree_aux, \
        _tree_export_fn

    state, edits, meta = _tree_chunk()
    n_nodes, n_cont = _tree_aux(meta, digest)
    return _tree_export_fn(digest).lower(state, edits, n_nodes, n_cont)


def _lower_splice(group: str):
    from fluidframework_tpu.ops import tree_pipeline
    from fluidframework_tpu.ops.device_cache import _splice_jit
    from fluidframework_tpu.ops.mergetree_kernel import MTOps
    from fluidframework_tpu.ops.tree_kernel import TreeEdits

    if group == "mtops":
        _state, planes, _base, _meta, _flags = _mt_chunk()
        tuple_type = MTOps
    else:
        state, edits, _meta = _tree_chunk()
        tuple_type, planes = {
            "treeedits": (TreeEdits, edits),
            "treenodeplanes": (tree_pipeline._TreeNodePlanes,
                               tree_pipeline._group(
                                   tree_pipeline._TreeNodePlanes, state)),
            "treecontplanes": (tree_pipeline._TreeContPlanes,
                               tree_pipeline._group(
                                   tree_pipeline._TreeContPlanes, state)),
        }[group]
    rows = tuple_type(*(np.asarray(p)[:, :1] for p in planes))
    d = np.asarray(planes[0]).shape[0]
    zeros = np.zeros((d,), np.int32)
    return _splice_jit(tuple_type).lower(planes, rows, zeros, zeros)


def _lower_gather():
    from fluidframework_tpu.ops.mergetree_kernel import export_gather

    return export_gather.lower(np.zeros((8, 3), np.int32),
                               np.zeros((4,), np.int32))


PROGRAMS = {
    "mergetree_fold_export_cold": lambda: _lower_mergetree("cold", False),
    "mergetree_fold_export_digest_cold":
        lambda: _lower_mergetree("cold", True),
    "mergetree_fold_export_warm": lambda: _lower_mergetree("warm", False),
    "mergetree_fold_export_digest_warm":
        lambda: _lower_mergetree("warm", True),
    "tree_fold_export": lambda: _lower_tree(False),
    "tree_fold_export_digest": lambda: _lower_tree(True),
    "splice_mtops": lambda: _lower_splice("mtops"),
    "splice_treeedits": lambda: _lower_splice("treeedits"),
    "splice_treenodeplanes": lambda: _lower_splice("treenodeplanes"),
    "splice_treecontplanes": lambda: _lower_splice("treecontplanes"),
    "export_gather": _lower_gather,
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_served_programs_carry_stable_module_names(name):
    lowered = PROGRAMS[name]()
    first = lowered.as_text().splitlines()[0]
    assert first.startswith(f"module @jit_{name} "), first
    if "fold_export" in name:
        # named scopes label the ops inside (the tree family's export is
        # its final planes themselves: no op of its own)
        scoped = lowered.as_text(debug_info=True)
        scopes = ["fold"] + (["export"] if "mergetree" in name else []) \
            + (["digest"] if "digest" in name else [])
        for scope in scopes:
            assert f"/{scope}/" in scoped, scope
