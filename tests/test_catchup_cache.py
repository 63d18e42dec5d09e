"""Two-tier seq-anchored catch-up cache (ISSUE 3): LRU byte accounting,
epoch invalidation, single-flight, pack-cache suffix reuse, and the
determinism contract — cache-on results byte-identical to cache-off
across golden and fuzzed corpora."""

import threading

import numpy as np
import pytest

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.pipeline import PackCache, pipelined_mergetree_replay
from fluidframework_tpu.protocol.summary import SummaryStorage, SummaryTree
from fluidframework_tpu.service import LocalOrderingService, OpLog
from fluidframework_tpu.service.catchup import CatchupService
from fluidframework_tpu.service.catchup_cache import (
    CatchupResultCache,
    tree_nbytes,
)
from tests.test_service import _seed_string_doc


def _blob_tree(payload_bytes: int) -> SummaryTree:
    tree = SummaryTree()
    tree.add_blob("body", b"x" * payload_bytes)
    return tree


# --- tier 1: LRU / byte accounting -------------------------------------------


def test_lru_byte_bound_and_eviction_order():
    one = tree_nbytes(_blob_tree(1000))
    cache = CatchupResultCache(max_bytes=3 * one)
    for i in range(3):
        cache.insert(("e", f"d{i}"), _blob_tree(1000))
    assert len(cache) == 3 and cache.current_bytes == 3 * one
    # Touch d0 so d1 becomes least-recent, then overflow by one entry.
    assert cache.lookup(("e", "d0")) is not None
    cache.insert(("e", "d3"), _blob_tree(1000))
    assert cache.lookup(("e", "d1")) is None, "LRU must evict d1 first"
    assert cache.lookup(("e", "d0")) is not None
    assert cache.lookup(("e", "d3")) is not None
    stats = cache.stats()
    assert stats["evictions"] == 1 and stats["inserts"] == 4
    assert stats["bytes"] <= cache.max_bytes


def test_oversize_entry_never_admitted():
    cache = CatchupResultCache(max_bytes=400)
    cache.insert(("e", "small"), _blob_tree(10))
    cache.insert(("e", "huge"), _blob_tree(10_000))
    assert cache.lookup(("e", "huge")) is None
    # ...and it must not have evicted the resident entry to make room.
    assert cache.lookup(("e", "small")) is not None


def test_reinsert_same_key_replaces_bytes():
    cache = CatchupResultCache(max_bytes=1 << 20)
    cache.insert(("e", "d"), _blob_tree(1000))
    before = cache.current_bytes
    cache.insert(("e", "d"), _blob_tree(2000))
    assert len(cache) == 1
    assert cache.current_bytes == before + 1000  # replaced, not added


def test_epoch_invalidation_drops_only_stale_generations():
    cache = CatchupResultCache()
    cache.insert(("old", "d0"), _blob_tree(10))
    cache.insert(("old", "d1"), _blob_tree(10))
    cache.insert(("new", "d0"), _blob_tree(10))
    assert cache.invalidate_epoch("new") == 2
    assert cache.lookup(("old", "d0")) is None
    assert cache.lookup(("new", "d0")) is not None
    assert cache.stats()["invalidations"] == 2


# --- tier 1: single-flight ----------------------------------------------------


def test_single_flight_leader_publishes_to_waiters():
    cache = CatchupResultCache()
    key = ("e", "doc")
    status, _tree = cache.begin(key)
    assert status == "lead"
    got = []
    waiter = threading.Thread(target=lambda: got.append(cache.join(key)))
    waiter.start()
    tree = _blob_tree(10)
    published = cache.finish(key, tree)
    waiter.join(timeout=10)
    assert [f.tree for f in got] == [tree]
    assert published.handle == tree.digest()  # digested once, at publish
    assert cache.stats()["waits"] == 1
    # the published entry is now a plain hit, handle included
    status, fold = cache.begin(key)
    assert status == "hit" and fold.tree is tree \
        and fold.handle == published.handle


def test_single_flight_abandon_unblocks_waiters():
    cache = CatchupResultCache()
    key = ("e", "doc")
    assert cache.begin(key)[0] == "lead"
    got = []
    waiter = threading.Thread(target=lambda: got.append(cache.join(key)))
    waiter.start()
    cache.abandon(key)
    waiter.join(timeout=10)
    assert got == [None], "abandon must wake waiters empty-handed"
    assert cache.lookup(key) is None


def test_join_timeout_returns_none_when_leader_never_finishes():
    """The bounded-wait contract (fluidrace, ISSUE 4): a leader that died
    without finish/abandon must not hang a follower — join(timeout)
    returns None once the budget elapses."""
    import time

    cache = CatchupResultCache()
    key = ("e", "doc")
    assert cache.begin(key)[0] == "lead"  # ...and the leader "crashes"
    t0 = time.monotonic()
    assert cache.join(key, timeout=0.1) is None
    assert time.monotonic() - t0 < 10
    assert cache.stats()["waits"] == 1


def test_join_timeout_pop_is_identity_guarded():
    """A timed-out waiter removes the flight it actually waited on —
    never a fresh leader's flight that replaced it in the race window
    (popping that would degrade the herd's single-flight to N folds)."""
    import time

    cache = CatchupResultCache()
    key = ("e", "doc")
    assert cache.begin(key)[0] == "lead"
    got = []
    waiter = threading.Thread(
        target=lambda: got.append(cache.join(key, timeout=0.8)))
    waiter.start()
    time.sleep(0.1)
    # Simulate the race: the stale flight vanishes (crashed leader's
    # flight reaped) and a NEW leader begins before the timeout fires.
    with cache._lock:
        cache._flights.pop(key)
    assert cache.begin(key)[0] == "lead"
    fresh = cache._flights[key]
    waiter.join(timeout=10)
    assert got == [None]
    assert cache._flights.get(key) is fresh, \
        "live flight must survive a stale waiter's timeout"


def test_stale_timeout_reaper_does_not_wake_live_waiters():
    """The reap path sets the event ONLY for the flight it actually
    popped: when finish() has already popped the flight but not yet
    published, a timed-out waiter setting done would wake every other
    waiter to result=None on a successfully COMPLETED fold (they would
    all fall through and fold again, serialized)."""
    import time

    cache = CatchupResultCache()
    key = ("e", "doc")
    assert cache.begin(key)[0] == "lead"
    flight = cache._flights[key]
    got_timeout, got_result = [], []
    stale = threading.Thread(
        target=lambda: got_timeout.append(cache.join(key, timeout=0.3)))
    live = threading.Thread(
        target=lambda: got_result.append(cache.join(key, timeout=30)))
    stale.start()
    live.start()
    time.sleep(0.1)
    # finish() preempted mid-publish: flight popped, result not yet set
    with cache._lock:
        cache._flights.pop(key)
    stale.join(timeout=10)
    assert got_timeout == [None]
    assert not flight.done.is_set(), \
        "a guard-failed reaper must not wake the leader's other waiters"
    assert not got_result, "live waiter woken before the result exists"
    # the preempted finish() resumes: publish, then wake
    flight.result = "fold-result"
    flight.done.set()
    live.join(timeout=10)
    assert got_result == ["fold-result"]


def test_catch_up_survives_crashed_leader():
    """Service-level timeout fallback: a key left in flight forever (the
    leader thread was killed before its finally-abandon) times the
    follower out, the dead flight is abandoned, and the follower folds
    the document itself — with a byte-identical result."""
    import time

    service = LocalOrderingService()
    bench.build_catchup_corpus(service, 1, 12)
    svc = CatchupService(service, mesh=None)
    svc.join_timeout = 0.2
    _summary, ref_seq, handle = service.storage.latest_with_handle("cdoc0")
    tail = service.oplog.get("cdoc0", from_seq=ref_seq)
    key = svc._cache_key("cdoc0", handle, ref_seq, tail)
    assert svc.cache.begin(key)[0] == "lead"  # the crashed leader
    t0 = time.monotonic()
    results = svc.catch_up(["cdoc0"], upload=False)
    assert time.monotonic() - t0 < 30, "follower must not hang"
    fresh = CatchupService(service, cache=None, mesh=None)
    assert results == fresh.catch_up(["cdoc0"], upload=False)
    # the dead flight was abandoned: nothing in flight, entry published,
    # so the next herd single-flights normally again
    assert svc.cache._flights == {}
    assert svc.catch_up(["cdoc0"], upload=False) == results


def test_join_timeout_config_gate(monkeypatch):
    monkeypatch.setenv("FLUID_TPU_CATCHUP_JOINTIMEOUT", "7.5")
    svc = CatchupService(LocalOrderingService(), mesh=None)
    assert svc.join_timeout == 7.5


def test_concurrent_catch_up_threads_cost_one_fold():
    """The thundering-herd contract: N concurrent catch-ups of the same
    (doc, seq) → ONE fold; the rest wait on the in-flight key and serve
    from the published entry without ever taking the device."""
    service = LocalOrderingService()
    bench.build_catchup_corpus(service, 1, 12)
    svc = CatchupService(service, mesh=None)
    folding = threading.Event()
    release = threading.Event()
    fold_calls = []
    real_fold = svc._device_fold

    def slow_fold(works):
        fold_calls.append(len(works))
        folding.set()
        assert release.wait(timeout=30)
        return real_fold(works)

    svc._device_fold = slow_fold
    results = {}

    def run(name):
        results[name] = svc.catch_up(["cdoc0"], upload=False)

    leader = threading.Thread(target=run, args=("leader",))
    leader.start()
    assert folding.wait(timeout=30)  # the key is now in flight
    waiters = [threading.Thread(target=run, args=(f"w{i}",))
               for i in range(4)]
    for t in waiters:
        t.start()
    release.set()
    leader.join(timeout=60)
    for t in waiters:
        t.join(timeout=60)
    assert fold_calls == [1], "the herd must cost exactly one fold"
    assert len({tuple(sorted(r.items())) for r in results.values()}) == 1
    assert svc.cache.counters.get("waits") >= 1


# --- tier 1 at the service: stale-store protection ---------------------------


def test_recreated_store_never_serves_stale_folds():
    """EpochTracker parity for the fold cache: a recreated (storage,
    oplog) pair carrying DIFFERENT ops at the same seq range under the
    same base summary digest must fold fresh — the old generation's
    cached tree would be byte-plausible and silently wrong."""
    service = LocalOrderingService()
    bench.build_catchup_corpus(service, 2, 10)
    svc = CatchupService(service, mesh=None)
    old = svc.catch_up(upload=False)

    # "Recreate" the store: new epoch, same doc ids, same seeded summary
    # (content-addressed → same base digest), different tail content.
    new_storage, new_oplog = SummaryStorage(), OpLog()
    service.storage, service.oplog = new_storage, new_oplog
    bench.build_catchup_corpus(service, 2, 10)
    for doc_id in ("cdoc0", "cdoc1"):
        msgs = new_oplog.get(doc_id)
        # mutate one op's text so the same seq range carries new bytes
        msgs[0].contents["ops"][0]["contents"] = {
            "kind": "insert", "pos": 0, "text": "REGENERATED",
        }
    fresh = svc.catch_up(upload=False)
    assert fresh != old, "stale fold served across a storage generation"
    for doc_id in ("cdoc0", "cdoc1"):
        assert fresh[doc_id][0] == bench.catchup_oracle_digest(
            service, doc_id)


# --- determinism: cache-on == cache-off (golden + fuzz) ----------------------


def _grow(runtimes, rng, edits=6):
    for i in range(edits):
        rt = runtimes[i % len(runtimes)]
        text = rt.get_datastore("ds").get_channel("text")
        length = len(text.text)
        if length < 4 or rng.random() < 0.7:
            text.insert_text(rng.randint(0, length), "gh"[i % 2] * 2)
        else:
            start = rng.randint(0, length - 2)
            text.remove_range(start, min(length, start + 2))
        for r in runtimes:
            r.drain()


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_cache_on_matches_cache_off(seed):
    """Across seeds and growth rounds: the cached service's results —
    cold fill, warm full hits, and suffix-extended folds — are
    byte-identical to an uncached service folding the same state."""
    import random

    service = LocalOrderingService()
    rng = random.Random(9000 + seed)
    runtimes = {
        f"doc{d}": _seed_string_doc(service, f"doc{d}",
                                    edits=6 + seed + d)
        for d in range(3)
    }
    cached = CatchupService(service, mesh=None)
    plain = CatchupService(service, mesh=None, cache=None, pack_cache=None)
    for _round in range(3):
        expect = plain.catch_up(upload=False)
        cold = cached.catch_up(upload=False)
        warm = cached.catch_up(upload=False)
        assert cold == expect, f"seed {seed}: cache-on != cache-off"
        assert warm == expect, f"seed {seed}: warm hit changed bytes"
        for rts in runtimes.values():
            _grow(rts, rng)
    # growth rounds extend tails over an unchanged base → tier 2 must
    # have reused packed windows at least once along the way
    pc = cached._pack_cache.stats()
    assert pc["suffix_hits"] + pc["exact_hits"] > 0, pc


def test_golden_corpus_cache_on_matches_cache_off():
    """Golden (pinned-workload) corpus through the service path: cached
    cold + warm results both equal the uncached fold and the container
    oracle."""
    service = LocalOrderingService()
    doc_ids = bench.build_catchup_corpus(service, 12, 20)
    cached = CatchupService(service, mesh=None)
    plain = CatchupService(service, mesh=None, cache=None, pack_cache=None)
    expect = plain.catch_up(doc_ids, upload=False)
    assert cached.catch_up(doc_ids, upload=False) == expect
    assert cached.catch_up(doc_ids, upload=False) == expect  # warm
    assert expect["cdoc0"][0] == bench.catchup_oracle_digest(
        service, "cdoc0")


# --- tier 2: pack cache -------------------------------------------------------


def _message_doc(idx: int, n_ops: int, token) -> MergeTreeDocInput:
    """A message-list (non-binary) doc over the pinned synth stream —
    the shape the catch-up service feeds the pipeline."""
    msgs = bench.doc_ops(bench.synth_doc(idx, n_ops))
    return MergeTreeDocInput(
        doc_id=f"pdoc{idx}", ops=msgs, final_seq=msgs[-1].seq,
        final_msn=0, cache_token=token,
    )


def test_pack_cache_exact_hit_reuses_chunk():
    docs = [_message_doc(i, 24, ("tok", i)) for i in range(6)]
    cache = PackCache()
    expect = [s.digest() for s in replay_mergetree_batch(docs)]
    for _pass in range(2):
        got = pipelined_mergetree_replay(docs, chunk_docs=8,
                                         pack_cache=cache)
        assert [s.digest() for s in got] == expect
    stats = cache.stats()
    assert stats["misses"] == 1 and stats["exact_hits"] == 1, stats


def test_pack_cache_suffix_extends_packed_window():
    """A tail that grew re-packs ONLY the suffix: byte-identical to a
    fresh pack of the full window, counted as a suffix hit."""
    full = [bench.doc_ops(bench.synth_doc(i, 32)) for i in range(6)]

    def window(n_ops):
        return [
            MergeTreeDocInput(
                doc_id=f"pdoc{i}", ops=msgs[:n_ops],
                final_seq=msgs[n_ops - 1].seq, final_msn=0,
                cache_token=("tok", i),
            )
            for i, msgs in enumerate(full)
        ]

    cache = PackCache()
    # 26 → 32 ops stays inside the T=32 / S=64 buckets, so the grown
    # window is suffix-extendable (the bucket-crossing case is covered
    # by test_pack_cache_bails_to_full_pack_when_buckets_grow).
    first = pipelined_mergetree_replay(window(26), chunk_docs=8,
                                       pack_cache=cache)
    assert [s.digest() for s in first] == \
        [s.digest() for s in replay_mergetree_batch(window(26))]
    grown = window(32)
    got = pipelined_mergetree_replay(grown, chunk_docs=8, pack_cache=cache)
    assert [s.digest() for s in got] == \
        [s.digest() for s in replay_mergetree_batch(grown)], (
            "suffix-extended pack changed bytes")
    stats = cache.stats()
    assert stats["suffix_hits"] == 1, stats
    # the extended window is now the cached one: an exact replay hits
    again = pipelined_mergetree_replay(grown, chunk_docs=8,
                                       pack_cache=cache)
    assert [s.digest() for s in again] == [s.digest() for s in got]
    assert cache.stats()["exact_hits"] == 1


def test_pack_cache_bails_to_full_pack_when_buckets_grow():
    """A suffix that would outgrow the chunk's op-row bucket must fall
    back to a full pack — correct bytes, counted as a miss."""
    full = [bench.doc_ops(bench.synth_doc(i, 48)) for i in range(4)]

    def window(n_ops):
        return [
            MergeTreeDocInput(
                doc_id=f"pdoc{i}", ops=msgs[:n_ops],
                final_seq=msgs[n_ops - 1].seq, final_msn=0,
                cache_token=("tok", i),
            )
            for i, msgs in enumerate(full)
        ]

    cache = PackCache()
    pipelined_mergetree_replay(window(14), chunk_docs=8, pack_cache=cache)
    grown = window(48)  # 14 → 48 text ops crosses the T=16 bucket
    got = pipelined_mergetree_replay(grown, chunk_docs=8, pack_cache=cache)
    assert [s.digest() for s in got] == \
        [s.digest() for s in replay_mergetree_batch(grown)]
    stats = cache.stats()
    assert stats["misses"] == 2 and stats["suffix_hits"] == 0, stats


def test_pack_cache_bypasses_binary_and_untokened_docs():
    """A chunk with a token-less doc bypasses tier 2; a binary-stream
    chunk whose docs all carry tokens is cached like any other."""
    cache = PackCache()
    binary = [bench.synth_doc(i, 16) for i in range(4)]  # no tokens
    got = pipelined_mergetree_replay(binary, chunk_docs=8,
                                     pack_cache=cache)
    assert [s.digest() for s in got] == \
        [s.digest() for s in replay_mergetree_batch(binary)]
    stats = cache.stats()
    assert stats["bypass"] == 1 and stats["inserts"] == 0, stats
    for i, doc in enumerate(binary):
        doc.cache_token = ("tok", i)
    for _pass in range(2):
        got = pipelined_mergetree_replay(binary, chunk_docs=8,
                                         pack_cache=cache)
        assert [s.digest() for s in got] == \
            [s.digest() for s in replay_mergetree_batch(binary)]
    stats = cache.stats()
    assert (stats["bypass"], stats["inserts"], stats["exact_hits"]) == \
        (1, 1, 1), stats


def test_pack_cache_byte_bound_evicts():
    cache = PackCache(max_bytes=1)  # nothing fits
    docs = [_message_doc(i, 16, ("tok", i)) for i in range(4)]
    got = pipelined_mergetree_replay(docs, chunk_docs=8, pack_cache=cache)
    assert [s.digest() for s in got] == \
        [s.digest() for s in replay_mergetree_batch(docs)]
    stats = cache.stats()
    assert stats["entries"] == 0 and stats["evictions"] >= 1, stats


def _append_ops(service, doc_id, msgs):
    """Append a doc's sequenced channel ops to the op log, each in the
    groupedBatch envelope the container runtime emits."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    for m in msgs:
        service.oplog.append(doc_id, SequencedMessage(
            seq=m.seq, client_id=m.client_id, client_seq=m.client_seq,
            ref_seq=m.ref_seq, min_seq=m.min_seq, type=MessageType.OP,
            contents={"type": "groupedBatch", "ops": [
                {"ds": "ds", "channel": "text", "clientSeq": m.client_seq,
                 "contents": m.contents}]}))


def stream_service(n_docs=8, lo=120, hi=128):
    """Cold string documents (an empty summary at seq 0) holding the first
    ``lo`` ops of the pinned synth streams, which the catch-up service
    packs as record streams; returns (service, doc ids, a ``grow(docs)``
    that appends the ops up to ``hi``).  120 → 128 ops stays inside the
    T=128 / S=256 fine buckets, so a grown window can extend in place."""
    from fluidframework_tpu.runtime.container import ContainerRuntime

    service = LocalOrderingService()
    seeded = ContainerRuntime()
    seeded.create_datastore("ds").create_channel("sequence-tpu", "text")
    tree = seeded.summarize()
    streams = [bench.doc_ops(bench.synth_doc(i, hi)) for i in range(n_docs)]
    doc_ids = [f"sd{i}" for i in range(n_docs)]
    for doc_id, msgs in zip(doc_ids, streams):
        service.storage.upload(doc_id, tree, 0)
        _append_ops(service, doc_id, msgs[:lo])

    def grow(which):
        for i in which:
            _append_ops(service, doc_ids[i], streams[i][lo:])

    return service, doc_ids, grow


def container_fold(service, doc_ids):
    cpu = CatchupService(service, mesh=None, cache=None, pack_cache=None)
    cpu._device_plan = lambda w: None
    return cpu.catch_up(doc_ids, upload=False)


def test_service_stream_docs_ride_the_pack_cache():
    """Stream documents keep tier 2: a grown tail suffix-extends the
    cached window (decoding only the new records), an unchanged one hits
    it exactly, and every fold matches the container fold."""
    service, doc_ids, grow = stream_service()
    svc = CatchupService(service, mesh=None, cache=None)
    assert svc.catch_up(doc_ids, upload=False) == \
        container_fold(service, doc_ids)
    assert svc.pipeline_stats["pack_stream_docs"] == len(doc_ids)
    grow([0, 7])
    assert svc.catch_up(doc_ids, upload=False) == \
        container_fold(service, doc_ids)
    pc = svc._pack_cache.stats()
    assert (pc["misses"], pc["suffix_hits"], pc["bypass"]) == (1, 1, 0), pc
    assert svc.catch_up(doc_ids, upload=False) == \
        container_fold(service, doc_ids)
    assert svc._pack_cache.stats()["exact_hits"] == 1
    assert svc.pipeline_stats["pack_stream_docs"] == 3 * len(doc_ids)
    assert svc.pipeline_stats.get("pack_message_docs", 0) == 0


def test_service_growth_rides_pack_suffix_reuse():
    """Service-level tier-2: catch-up, grow the SAME docs' tails (no
    upload, so the base anchor is unchanged), catch-up again — the
    second fold must suffix-extend the cached packed window and still
    match a forced-CPU container fold byte-for-byte."""
    import random

    service = LocalOrderingService()
    runtimes = {f"doc{d}": _seed_string_doc(service, f"doc{d}", edits=8)
                for d in range(3)}
    svc = CatchupService(service, mesh=None)
    svc.catch_up(upload=False)
    rng = random.Random("suffix")
    for rts in runtimes.values():
        _grow(rts, rng, edits=5)
    cpu = CatchupService(service, cache=None, pack_cache=None)
    cpu._device_plan = lambda w: None
    expect = cpu.catch_up(upload=False)
    got = svc.catch_up(upload=False)
    assert got == expect, "suffix-reused fold != container fold"
    stats = svc._pack_cache.stats()
    assert stats["suffix_hits"] >= 1, stats
