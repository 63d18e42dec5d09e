"""Digest-gated incremental export (ISSUE 6): tier 0 of the catch-up
cache.  The fold emits a per-doc state digest on device; a warm catch-up
over a grown tail downloads + extracts ONLY the changed documents' export
rows, serving unchanged documents' cached summaries byte-identically.

Pinned here: golden + fuzz byte identity (delta-on == delta-off == the
one-batch replay) across grown tails, the forced-digest-mismatch and
cold-start fallback routes, epoch invalidation, the tier-0 LRU/byte
bounds, the honest ``device_wait``/``download``/``d2h_bytes`` stage
split, and the deterministic ≥5× d2h byte drop on a warm grown-tail run
(a byte-counter gate — it cannot flake on scheduler noise)."""

import random

import pytest

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.pipeline import (
    PackCache,
    pipelined_mergetree_replay,
)
from fluidframework_tpu.service.catchup_cache import DeltaExportCache


def _streams(n_docs, n_ops=32):
    return [bench.doc_ops(bench.synth_doc(i, n_ops)) for i in range(n_docs)]


def _window(streams, i, n_ops, epoch="ep"):
    msgs = streams[i][:n_ops]
    return MergeTreeDocInput(
        doc_id=f"d{i}", ops=msgs, final_seq=msgs[-1].seq, final_msn=0,
        cache_token=(epoch, f"d{i}", 0, ""),
    )


def _corpus(streams, grown=(), lo=26, hi=32, epoch="ep"):
    # 26 → 32 ops stays inside the T=32 / S=64 buckets, so grown windows
    # ride the pack cache's suffix path (the bucket-crossing repack case
    # is exercised by the fuzz test's larger growth).
    return [
        _window(streams, i, hi if i in grown else lo, epoch)
        for i in range(len(streams))
    ]


def _run(docs, delta, pack, **kw):
    stage: dict = {}
    stats: dict = {}
    out = pipelined_mergetree_replay(
        docs, chunk_docs=kw.pop("chunk_docs", 8), delta_cache=delta,
        pack_cache=pack, stage=stage, stats=stats, **kw)
    return [s.digest() for s in out], stage, stats


# --- golden byte identity ----------------------------------------------------


def test_delta_download_golden_byte_identity():
    """Cold fill, then a warm grown-tail pass: delta-download summaries
    are byte-identical to the one-batch full replay; unchanged docs are
    served without download and the d2h byte counter drops."""
    streams = _streams(12)
    delta, pack = DeltaExportCache(), PackCache()
    cold_docs = _corpus(streams)
    got, stage_cold, _ = _run(cold_docs, delta, pack)
    assert got == [s.digest() for s in replay_mergetree_batch(cold_docs)]
    assert stage_cold["d2h_bytes"] > 0
    assert "device_wait" in stage_cold and "download" in stage_cold

    grown = _corpus(streams, grown={0, 5})
    got, stage_warm, stats = _run(grown, delta, pack)
    assert got == [s.digest() for s in replay_mergetree_batch(grown)], (
        "delta-download changed bytes on a grown tail"
    )
    assert stats.get("delta_docs", 0) == 10, stats
    assert delta.stats()["served"] == 10
    assert stage_warm["d2h_bytes"] < stage_cold["d2h_bytes"]


def test_delta_all_unchanged_serves_without_rows():
    """A byte-identical re-run downloads only the digest plane: every
    document serves from tier 0, zero extraction."""
    streams = _streams(10)
    delta, pack = DeltaExportCache(), PackCache()
    docs = _corpus(streams)
    expect, stage_cold, _ = _run(docs, delta, pack)
    again, stage_warm, stats = _run(docs, delta, pack)
    assert again == expect
    assert stats.get("delta_docs", 0) == len(docs)
    # Only the [D, 2] int32 digest plane crossed: 8 bytes per doc.
    assert stage_warm["d2h_bytes"] == 8 * len(docs)
    assert stage_warm.get("extract", 0.0) == 0.0


def test_cold_start_without_cache_is_the_full_path():
    """delta_cache=None keeps the existing full-fetch pipeline exactly
    (the fallback route IS the golden oracle)."""
    streams = _streams(8)
    docs = _corpus(streams)
    got, stage, stats = _run(docs, None, None)
    assert got == [s.digest() for s in replay_mergetree_batch(docs)]
    assert "delta_docs" not in stats
    assert stage["d2h_bytes"] > 0


# --- fallback routes ---------------------------------------------------------


def test_forced_digest_mismatch_falls_back_to_download():
    """A corrupted tier-0 digest must fall back to the full row fetch for
    that document — counted as ``changed``, bytes still identical."""
    streams = _streams(9)
    delta, pack = DeltaExportCache(), PackCache()
    docs = _corpus(streams)
    expect, _, _ = _run(docs, delta, pack)
    # Poison one entry's digest (simulates any digest drift).
    with delta._lock:
        token = docs[3].cache_token
        entry = delta._entries[token]
        delta._entries[token] = entry._replace(digest=(1, 2))
    again, _, stats = _run(docs, delta, pack)
    assert again == expect, "digest-mismatch fallback changed bytes"
    assert stats.get("delta_docs", 0) == len(docs) - 1
    st = delta.stats()
    assert st["changed"] == 1, st
    # ...and the fallback re-published the true digest: a third run
    # serves everything again.
    final, _, stats3 = _run(docs, delta, pack)
    assert final == expect
    assert stats3.get("delta_docs", 0) == len(docs)


def test_epoch_bump_invalidates_tier0():
    """Entries are keyed by the storage epoch (token component 0): a new
    generation can never be served stale summaries, and eager
    invalidation frees the budget."""
    streams = _streams(6)
    delta, pack = DeltaExportCache(), PackCache()
    _run(_corpus(streams, epoch="e1"), delta, pack)
    assert len(delta) == 6
    assert delta.invalidate_epoch("e2") == 6
    assert len(delta) == 0
    assert delta.stats()["invalidations"] == 6
    # New-generation tokens fold full (no serves) and stay byte-correct.
    docs2 = _corpus(streams, epoch="e2")
    got, _, stats = _run(docs2, delta, pack)
    assert got == [s.digest() for s in replay_mergetree_batch(docs2)]
    assert stats.get("delta_docs", 0) == 0


# --- tier-0 cache unit behavior ----------------------------------------------


def test_tier0_anchor_guards_host_side_inputs():
    """Same token + same device digest but a changed host anchor (an
    extraction input the digest cannot see — final_msn here) must MISS:
    the cached summary's header/expiry would be wrong."""
    streams = _streams(4)
    delta, pack = DeltaExportCache(), PackCache()
    docs = _corpus(streams)
    _run(docs, delta, pack)
    moved = [
        MergeTreeDocInput(
            doc_id=d.doc_id, ops=d.ops, final_seq=d.final_seq,
            final_msn=d.final_msn + 1, cache_token=d.cache_token)
        for d in docs
    ]
    got, _, stats = _run(moved, delta, pack)
    assert got == [s.digest() for s in replay_mergetree_batch(moved)]
    assert stats.get("delta_docs", 0) == 0, (
        "anchor drift must not serve cached summaries"
    )


def test_tier0_bypasses_binary_and_tokenless_docs():
    """Token-less docs, binary or not, bypass tier 0; a tokened binary
    doc is anchored on its stream's op count."""
    delta = DeltaExportCache()
    binary = bench.synth_doc(0, 16)  # binary stream, no token
    tokenless = MergeTreeDocInput(
        doc_id="t", ops=bench.doc_ops(bench.synth_doc(1, 8)),
        final_seq=8, final_msn=0)
    for doc in (binary, tokenless):
        assert not delta.candidate(doc)
        assert delta.serve(doc, (0, 0)) is None
        delta.put(doc, (0, 0), replay_mergetree_batch([doc])[0])
    assert len(delta) == 0
    binary.cache_token = ("ep", "b", 0, "")
    tree = replay_mergetree_batch([binary])[0]
    delta.put(binary, (0, 0), tree)
    assert delta.candidate(binary)
    assert delta.serve(binary, (0, 0)) is tree
    shorter = bench.synth_doc(0, 15)  # same token, one op fewer
    shorter.cache_token = binary.cache_token
    shorter.final_seq = binary.final_seq
    assert not delta.candidate(shorter)


def test_tier0_byte_bound_and_lru_eviction():
    from fluidframework_tpu.protocol.summary import SummaryTree
    from fluidframework_tpu.service.catchup_cache import tree_nbytes

    def blob(n):
        t = SummaryTree()
        t.add_blob("body", b"x" * n)
        return t

    def doc(i):
        return MergeTreeDocInput(
            doc_id=f"d{i}", ops=bench.doc_ops(bench.synth_doc(i, 4)),
            final_seq=4, final_msn=0, cache_token=("e", i))

    one = tree_nbytes(blob(1000))
    cache = DeltaExportCache(max_bytes=3 * one)
    for i in range(3):
        cache.put(doc(i), (i, i), blob(1000))
    assert len(cache) == 3
    # Touch d0 (serve) so d1 is least-recent, then overflow by one.
    assert cache.serve(doc(0), (0, 0)) is not None
    cache.put(doc(3), (3, 3), blob(1000))
    assert cache.serve(doc(1), (1, 1)) is None, "LRU must evict d1 first"
    assert cache.serve(doc(0), (0, 0)) is not None
    st = cache.stats()
    assert st["evictions"] == 1 and st["bytes"] <= cache.max_bytes
    # An entry larger than the whole budget is never admitted.
    big = DeltaExportCache(max_bytes=400)
    big.put(doc(0), (0, 0), blob(10))
    big.put(doc(1), (1, 1), blob(10_000))
    assert big.serve(doc(1), (1, 1)) is None
    assert big.serve(doc(0), (0, 0)) is not None


def test_digest_invariant_to_props_K_bucket_growth():
    """Another document introducing NEW annotate keys grows the chunk's
    props-K bucket.  An unchanged document's digest must not move (absent
    keys hash zero) — else every K growth silently degrades tier 0 to
    full download across the whole chunk."""
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import (
        replay_export,
        split_export_digest,
    )
    from fluidframework_tpu.ops.pipeline import PackCache
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def msg(seq, contents):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents=contents)

    def annotated_doc(doc_id, keys):
        ops = [msg(1, {"kind": "insert", "pos": 0, "text": "stable txt"})]
        for i, key in enumerate(keys):
            ops.append(msg(2 + i, {"kind": "annotate", "start": 0,
                                   "end": 4, "props": {key: 1}}))
        return MergeTreeDocInput(
            doc_id=doc_id, ops=ops, final_seq=len(ops), final_msn=0,
            cache_token=("ep", doc_id, 0, ""))

    def digest_of(docs, want_id):
        state, ops, meta = PackCache().pack(docs)
        core, dig = split_export_digest(
            replay_export(state, ops, meta, digest=True), True)
        dig_np = np.asarray(dig)
        d = [x.doc_id for x in meta["docs"]].index(want_id)
        return (int(dig_np[d, 0]), int(dig_np[d, 1]))

    a = annotated_doc("A", ["f"])
    with_k1 = digest_of([a, annotated_doc("B", ["f"])], "A")
    with_k3 = digest_of([a, annotated_doc("B", ["f", "g", "h"])], "A")
    assert with_k1 == with_k3, (
        "unchanged doc's digest moved when the chunk's K bucket grew"
    )
    # ...while a SET value must stay distinct from absent even for the
    # first-interned value id 0 (the +1 shift): same segments, same cols,
    # only the props plane differs — a full-segment annotate never splits.
    plain = MergeTreeDocInput(
        doc_id="A", ops=[msg(1, {"kind": "insert", "pos": 0,
                                 "text": "stable txt"})],
        final_seq=1, final_msn=0, cache_token=("ep", "A", 0, ""))
    full_ann = MergeTreeDocInput(
        doc_id="A",
        ops=plain.ops + [msg(2, {"kind": "annotate", "start": 0,
                                 "end": 10, "props": {"f": 1}})],
        final_seq=2, final_msn=0, cache_token=("ep", "A", 0, ""))
    assert digest_of([plain], "A") != digest_of([full_ann], "A"), (
        "value id 0 aliased with absent — the +1 shift is not applied"
    )


def test_gather_device_path_matches_host_view(monkeypatch):
    """``gather_export_rows`` has two legs: the zero-copy host view (CPU
    buffers) and the jitted device gather (accelerators).  CPU CI always
    takes the first — force the second and pin byte parity, so the
    accelerator leg cannot rot unexercised."""
    import numpy as np

    from fluidframework_tpu.ops import mergetree_kernel as mk

    streams = _streams(6)
    delta, pack = DeltaExportCache(), PackCache()
    docs = _corpus(streams)
    expect, _, _ = _run(docs, delta, pack)
    grown = _corpus(streams, grown={1, 4})
    via_host, _, _ = _run(grown, DeltaExportCache(), PackCache())
    # Fill a fresh tier 0, then serve the same grown corpus with the
    # host view disabled: the device gather must produce the same bytes.
    delta2, pack2 = DeltaExportCache(), PackCache()
    _run(docs, delta2, pack2)
    # The helper on the host leg first: exact rows, exact byte count.
    a = mk.jnp.arange(120, dtype=mk.jnp.int16).reshape(30, 4)
    idx = np.asarray([2, 7, 19], np.int32)
    host_rows, host_moved = mk.gather_export_rows(a, idx)
    assert host_rows.shape == (3, 4) and host_moved == host_rows.nbytes
    monkeypatch.setattr(mk, "_host_view", lambda a: None)
    via_dev, _, stats = _run(grown, delta2, pack2)
    assert via_dev == via_host
    assert stats.get("delta_docs", 0) == len(docs) - 2
    # Device leg: same rows; the internal fine-bucket pad rows count as
    # moved bytes (they really cross the link on an accelerator).
    dev_rows, dev_moved = mk.gather_export_rows(a, idx)
    assert np.array_equal(dev_rows, np.asarray(host_rows))
    assert dev_moved >= host_moved
    assert dev_moved == 8 * a[0].nbytes  # next_bucket_fine(3, floor=8)


# --- fuzz: delta-on == delta-off across random growth ------------------------


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_delta_on_matches_delta_off(seed):
    """Random growth rounds (including bucket-crossing repacks and
    interval/annotate docs): every round's delta-served results equal a
    fresh full replay byte-for-byte."""
    from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
    from fluidframework_tpu.testing.mocks import channel_log

    rng = random.Random(7700 + seed)
    streams = _streams(8, n_ops=48)
    fuzz_docs = []
    for i, spec in enumerate((StringFuzzSpec(annotate=True,
                                             intervals=True),
                              StringFuzzSpec(obliterate=True))):
        _r, f = run_fuzz(spec, seed=7800 + 10 * seed + i, n_clients=3,
                         rounds=6, sync_every=2)
        fuzz_docs.append(MergeTreeDocInput(
            doc_id=f"fz{i}", ops=channel_log(f, "fuzz"),
            final_seq=f.sequencer.seq, final_msn=f.sequencer.min_seq,
            cache_token=("ep", f"fz{i}", 0, "")))
    delta, pack = DeltaExportCache(), PackCache()
    windows = [12] * len(streams)
    served_total = 0
    for _round in range(4):
        docs = [_window(streams, i, windows[i])
                for i in range(len(streams))] + fuzz_docs
        expect = [s.digest() for s in replay_mergetree_batch(docs)]
        got, _, stats = _run(docs, delta, pack, chunk_docs=6)
        assert got == expect, f"seed {seed}: delta-on != full replay"
        served_total += stats.get("delta_docs", 0)
        for i in range(len(streams)):  # grow a random subset
            if rng.random() < 0.4:
                windows[i] = min(len(streams[i]),
                                 windows[i] + rng.randint(1, 14))
    assert served_total > 0, "fuzz never exercised the delta serve path"


# --- the perf gate: bytes, not seconds ---------------------------------------


def test_warm_grown_tail_fetches_5x_fewer_bytes():
    """The acceptance gate, on deterministic byte counters: a warm
    grown-tail run (1/16 of documents grew) moves ≥5× fewer d2h bytes
    than the full-download path over the same corpus."""
    streams = _streams(128)
    delta, pack = DeltaExportCache(), PackCache()
    cold = _corpus(streams)
    _run(cold, delta, pack, chunk_docs=64)
    grown_idx = set(range(0, 128, 16))
    grown = _corpus(streams, grown=grown_idx)
    got_delta, stage_delta, stats = _run(grown, delta, pack,
                                         chunk_docs=64)
    got_full, stage_full, _ = _run(grown, None, None, chunk_docs=64)
    assert got_delta == got_full, "delta and full runs disagree"
    assert stats.get("delta_docs", 0) == 128 - len(grown_idx)
    assert stage_delta["d2h_bytes"] * 5 <= stage_full["d2h_bytes"], (
        f"delta fetched {stage_delta['d2h_bytes']} B vs full "
        f"{stage_full['d2h_bytes']} B — less than the 5x floor"
    )
    assert delta.stats()["bytes_saved"] > 0


# --- service level -----------------------------------------------------------


def test_service_tier0_serves_when_tier1_is_off():
    """With tier 1 disabled (as after an eviction/restart of the result
    cache), a repeated catch-up re-folds — and tier 0 serves every
    unchanged string channel without a download, byte-identically."""
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    service = LocalOrderingService()
    doc_ids = bench.build_catchup_corpus(service, 6, 14)
    svc = CatchupService(service, mesh=None, cache=None)
    assert svc.delta_cache is not None, "gate must default on"
    plain = CatchupService(service, mesh=None, cache=None,
                           pack_cache=None, delta_cache=None)
    expect = plain.catch_up(doc_ids, upload=False)
    first = svc.catch_up(doc_ids, upload=False)
    second = svc.catch_up(doc_ids, upload=False)
    assert first == expect and second == expect
    st = svc.delta_cache.stats()
    assert st["served"] == 6, st
    assert svc.pipeline_stats.get("delta_docs", 0) == 6


def test_service_delta_gate_off(monkeypatch):
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    monkeypatch.setenv("FLUID_TPU_CATCHUP_DELTADOWNLOAD", "off")
    svc = CatchupService(LocalOrderingService(), mesh=None)
    assert svc.delta_cache is None
