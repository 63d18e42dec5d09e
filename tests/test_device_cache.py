"""Device-resident pack buffers (ISSUE 13): tier 2.5 of the catch-up
cache.  Packed chunk arrays stay resident in device memory keyed by the
chunk's token tuple; an exact warm hit dispatches with ZERO h2d pack
bytes, a grown tail uploads only its suffix rows through a donated
in-place splice, and every mismatch (bucket growth / repack, narrow↔wide
encoding flips, unknown pack lineage) falls back to the full upload.

Pinned here: golden + fuzz byte identity (resident-on == resident-off ==
the one-batch replay) across growth rounds, the donated splice's unit
parity against a numpy reference, the donation really happening (old
buffers dead), deterministic ``h2d_bytes`` gates (exact warm hit ≤
digest-plane bytes; suffix warm catch-up ≥5× less than full upload),
LRU/byte-bound eviction + epoch invalidation, and the mesh-sharded fold
serving the identical tier stack on a forced multi-device CPU mesh —
the mesh-parity acceptance criterion."""

import random

import numpy as np
import pytest

import bench
from fluidframework_tpu.ops.device_cache import DevicePackCache, _splice_ops
from fluidframework_tpu.ops.mergetree_kernel import (
    MTOps,
    MergeTreeDocInput,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.pipeline import (
    PackCache,
    pipelined_mergetree_replay,
)
from fluidframework_tpu.service.catchup_cache import DeltaExportCache


def _streams(n_docs, n_ops=128):
    return [bench.doc_ops(bench.synth_doc(i, n_ops)) for i in range(n_docs)]


def _window(streams, i, n_ops, epoch="ep"):
    msgs = streams[i][:n_ops]
    return MergeTreeDocInput(
        doc_id=f"d{i}", ops=msgs, final_seq=msgs[-1].seq, final_msn=0,
        cache_token=(epoch, f"d{i}", 0, ""),
    )


def _corpus(streams, grown=(), lo=120, hi=128, epoch="ep"):
    # 120 → 128 ops stays inside the T=128 / S=256 fine buckets, so
    # grown windows ride the tier-2 suffix path and the tier-2.5 splice;
    # the bucket-crossing repack case is exercised separately.
    return [
        _window(streams, i, hi if i in grown else lo, epoch)
        for i in range(len(streams))
    ]


def _run(docs, dev, pack, delta=None, **kw):
    stage: dict = {}
    stats: dict = {}
    out = pipelined_mergetree_replay(
        docs, chunk_docs=kw.pop("chunk_docs", 8), device_cache=dev,
        pack_cache=pack, delta_cache=delta, stage=stage, stats=stats, **kw)
    return [s.digest() for s in out], stage, stats


# --- golden byte identity ----------------------------------------------------


def test_resident_golden_byte_identity():
    """Cold fill, exact re-run, grown-tail splice: resident-on results
    are byte-identical to the one-batch replay at every step, and the
    resident counters report the serve/splice split."""
    streams = _streams(12)
    dev, pack = DevicePackCache(), PackCache()
    cold = _corpus(streams)
    got, stage_cold, _ = _run(cold, dev, pack)
    assert got == [s.digest() for s in replay_mergetree_batch(cold)]
    assert stage_cold["h2d_bytes"] > 0 and "upload" in stage_cold

    again, stage_exact, _ = _run(cold, dev, pack)
    assert again == got
    assert stage_exact["h2d_bytes"] == 0, (
        "exact warm hit must upload ZERO pack bytes")
    assert dev.stats()["served"] == 2  # both chunks resident

    grown = _corpus(streams, grown={0, 5})
    got3, stage_sfx, _ = _run(grown, dev, pack)
    assert got3 == [s.digest() for s in replay_mergetree_batch(grown)], (
        "donated suffix splice changed bytes"
    )
    st = dev.stats()
    assert st["spliced"] >= 1 and st["bytes_saved"] > 0
    assert 0 < stage_sfx["h2d_bytes"] < stage_cold["h2d_bytes"]


def test_resident_off_is_the_same_bytes():
    """device_cache=None keeps the existing full-upload pipeline exactly
    — and counts the full host arrays as h2d_bytes."""
    streams = _streams(8)
    docs = _corpus(streams)
    on, _, _ = _run(docs, DevicePackCache(), PackCache())
    off, stage, _ = _run(docs, None, PackCache())
    assert on == off
    assert stage["h2d_bytes"] > 0
    # Round 14: the stage schema is seeded identically for every
    # configuration — without the tier the key exists but no explicit
    # transfer leg ever runs (the upload rides the dispatch jit).
    assert stage["upload"] == 0.0


# --- the perf gates: bytes, not seconds --------------------------------------


def test_exact_warm_hit_uploads_at_most_digest_plane_bytes():
    """THE acceptance gate, upload side: a warm catch-up over unchanged
    documents uploads ≤ digest-plane bytes of pack data (here: zero —
    ops, state and doc_base are all resident) while the download side
    moves only the [D, 2] digest plane."""
    streams = _streams(16)
    dev, pack, delta = DevicePackCache(), PackCache(), DeltaExportCache()
    docs = _corpus(streams)
    _run(docs, dev, pack, delta)
    got, stage_warm, stats = _run(docs, dev, pack, delta)
    assert got == [s.digest() for s in replay_mergetree_batch(docs)]
    digest_plane_bytes = 8 * len(docs)
    assert stage_warm["h2d_bytes"] <= digest_plane_bytes, stage_warm
    assert stage_warm["d2h_bytes"] == digest_plane_bytes
    assert stats.get("delta_docs", 0) == len(docs)


def test_suffix_warm_catchup_5x_fewer_h2d_bytes():
    """Grown-tail warm catch-up (1/16 of documents grew) uploads ≥5×
    fewer h2d bytes than the full-upload reference over the same corpus
    — a deterministic byte-counter gate, not wall-clock."""
    streams = _streams(32)
    dev, pack = DevicePackCache(), PackCache()
    cold = _corpus(streams)
    _run(cold, dev, pack, chunk_docs=16)
    grown_idx = set(range(0, 32, 16))
    grown = _corpus(streams, grown=grown_idx)
    got_res, stage_res, _ = _run(grown, dev, pack, chunk_docs=16)
    got_full, stage_full, _ = _run(grown, None, PackCache(),
                                   chunk_docs=16)
    assert got_res == got_full, "resident and full runs disagree"
    assert stage_res["h2d_bytes"] * 5 <= stage_full["h2d_bytes"], (
        f"resident uploaded {stage_res['h2d_bytes']} B vs full "
        f"{stage_full['h2d_bytes']} B — less than the 5x floor"
    )
    # One grown doc per 16-doc chunk: both chunks splice.
    assert dev.stats()["spliced"] == 2


# --- the donated splice ------------------------------------------------------


def test_splice_unit_matches_numpy_reference():
    """``_splice_ops`` == the obvious per-doc row-write loop, for ragged
    per-doc suffix lengths including zero."""
    rng = np.random.default_rng(7)
    D, T, L, K = 5, 24, 8, 2

    def ops_of(arrs):
        return MTOps(**arrs)

    base = {f: rng.integers(0, 100, (D, T), np.int32)
            for f in MTOps._fields if f != "pvals"}
    base["pvals"] = rng.integers(0, 100, (D, T, K), np.int32)
    rows = {f: rng.integers(0, 100, (D, L), np.int32)
            for f in MTOps._fields if f != "pvals"}
    rows["pvals"] = rng.integers(0, 100, (D, L, K), np.int32)
    start = np.asarray([0, 3, 16, 20, 7], np.int32)
    count = np.asarray([2, 8, 8, 4, 0], np.int32)

    import jax

    spliced = _splice_ops(
        ops_of({f: jax.device_put(v) for f, v in base.items()}),
        ops_of({f: jax.device_put(v) for f, v in rows.items()}),
        jax.device_put(start), jax.device_put(count))
    for f in MTOps._fields:
        expect = base[f].copy()
        for d in range(D):
            for j in range(int(count[d])):
                expect[d, start[d] + j] = rows[f][d, j]
        assert np.array_equal(np.asarray(getattr(spliced, f)), expect), f


def test_donation_really_happens_old_buffers_dead():
    """The splice donates the resident buffers: after a suffix acquire
    the PREVIOUS device arrays are deleted (no 2× HBM spike) — reading a
    stale reference raises instead of aliasing garbage."""
    streams = _streams(6)
    dev, pack = DevicePackCache(), PackCache()
    _run(_corpus(streams), dev, pack, chunk_docs=6)
    [entry] = dev._entries.values()
    old_kind = entry.ops.kind
    got, _, _ = _run(_corpus(streams, grown={1}), dev, pack, chunk_docs=6)
    assert dev.stats()["spliced"] == 1
    assert entry.ops.kind is not old_kind
    with pytest.raises(RuntimeError):
        np.asarray(old_kind)


# --- fallback routes: the tier can lose a win, never corrupt -----------------


def test_bucket_crossing_repack_falls_back_to_full_upload():
    """Growth that crosses the T bucket repacks (tier-2 bails, shapes
    move) — the resident tier sees a signature mismatch, full-uploads,
    and the bytes stay identical."""
    streams = _streams(6, n_ops=48)
    dev, pack = DevicePackCache(), PackCache()
    small = [_window(streams, i, 20) for i in range(6)]
    _run(small, dev, pack, chunk_docs=6)
    grown = [_window(streams, i, 40) for i in range(6)]  # T 24 -> 48
    got, _, _ = _run(grown, dev, pack, chunk_docs=6)
    assert got == [s.digest() for s in replay_mergetree_batch(grown)]
    st = dev.stats()
    assert st["spliced"] == 0 and st["misses"] == 2
    # ...and the replaced entry serves exactly afterwards.
    _, stage, _ = _run(grown, dev, pack, chunk_docs=6)
    assert stage["h2d_bytes"] == 0


def test_narrow_wide_encoding_flip_migrates_in_graph(monkeypatch):
    """A narrow→wide upload-encoding flip (forced here via
    FF_UPLOAD_NARROW; at full scale suffix text at the shared arena
    tail does it by blowing the int16 offset bound) must NOT cost the
    full re-upload: the resident int16 buffers widen IN-GRAPH (donated,
    zero link bytes) and the suffix still splices — bytes identical,
    and the upload stays suffix-sized."""
    streams = _streams(6)
    dev, pack = DevicePackCache(), PackCache()
    cold = _corpus(streams)
    _, stage_cold, _ = _run(cold, dev, pack, chunk_docs=6)
    monkeypatch.setenv("FF_UPLOAD_NARROW", "0")
    grown = _corpus(streams, grown={2})
    got, stage, _ = _run(grown, dev, pack, chunk_docs=6)
    assert got == [s.digest() for s in replay_mergetree_batch(grown)]
    st = dev.stats()
    assert st["spliced"] == 1 and st["misses"] == 1, st
    # Wide suffix rows cost more per row than narrow ones, but still a
    # fraction of the full (now-wide) planes.
    assert 0 < stage["h2d_bytes"] < stage_cold["h2d_bytes"]
    # ...and the migrated entry's byte accounting tracks the wide size.
    assert dev.stats()["bytes"] > 0


def test_wide_to_narrow_flip_full_uploads(monkeypatch):
    """The opposite direction (resident wide, chunk narrow again) has
    no in-graph migration — full upload, never a corrupted splice."""
    streams = _streams(6)
    dev, pack = DevicePackCache(), PackCache()
    monkeypatch.setenv("FF_UPLOAD_NARROW", "0")
    _run(_corpus(streams), dev, pack, chunk_docs=6)
    monkeypatch.setenv("FF_UPLOAD_NARROW", "1")
    grown = _corpus(streams, grown={2})
    got, _, _ = _run(grown, dev, pack, chunk_docs=6)
    assert got == [s.digest() for s in replay_mergetree_batch(grown)]
    st = dev.stats()
    assert st["spliced"] == 0 and st["misses"] == 2, st


def test_suffix_without_pack_lineage_full_uploads():
    """Without tier 2 there is no lineage proof that the host arrays
    extend the resident ones (a fresh repack's arena layout may differ)
    — the suffix route must NOT splice; exact reuse still works (a
    deterministic re-pack of identical windows is byte-identical)."""
    streams = _streams(6)
    dev = DevicePackCache()
    docs = _corpus(streams)
    _run(docs, dev, None, chunk_docs=6)
    _, stage_exact, _ = _run(docs, dev, None, chunk_docs=6)
    assert stage_exact["h2d_bytes"] == 0
    assert dev.stats()["served"] == 1
    grown = _corpus(streams, grown={0})
    got, stage, _ = _run(grown, dev, None, chunk_docs=6)
    assert got == [s.digest() for s in replay_mergetree_batch(grown)]
    st = dev.stats()
    assert st["spliced"] == 0 and st["misses"] == 2, st


def test_tail_needing_more_overlap_slots_misses_the_resident_planes():
    """A warm chunk's grown tail adds a concurrent remover to a base
    record that already carries one, so the chunk needs more overlap
    slots than its resident base planes have: tier 2 still extends, but
    tier 2.5 must miss and upload the wider state — never splice the
    suffix onto the narrower resident planes."""
    import json

    from fluidframework_tpu.dds import SharedString
    from tests.test_mergetree_kernel import _ins, _rm, _seq_msgs

    msgs = _seq_msgs([
        ("c0", 0, _ins(0, "abcdefgh")),
        ("zed", 1, _rm(2, 6)),
        ("kim", 1, _rm(2, 6)),          # the base record's one "ro"
        ("c0", 3, _ins(0, "Q")),        # sequential tail op
        ("amy", 1, _rm(2, 6)),          # a lagged third remover
    ])
    base = SharedString("ov")
    for msg in msgs[:3]:
        base.process(msg, local=False)
    summary = base.summarize()
    records = json.loads(summary.blob_bytes("body"))

    def window(n):
        return MergeTreeDocInput(
            doc_id="ov", ops=msgs[3:n], base_records=records,
            base_seq=3, base_msn=0, final_seq=msgs[n - 1].seq, final_msn=0,
            cache_token=("ep", "ov", 0, ""))

    dev, pack = DevicePackCache(), PackCache()
    short = [window(4)]
    got, _, stats = _run(short, dev, pack, chunk_docs=1)
    assert got == [s.digest() for s in replay_mergetree_batch(short)]
    assert stats["ov_slots_1"] == 1
    grown = [window(5)]
    got, _, stats = _run(grown, dev, pack, chunk_docs=1)
    oracle = SharedString("ov")
    oracle.load(summary)
    for msg in msgs[3:]:
        oracle.process(msg, local=False)
    oracle.advance(msgs[-1].seq, 0)
    assert got == [oracle.summarize().digest()]
    assert stats["ov_slots_2"] == 1 and stats.get("fallback_docs", 0) == 0
    assert pack.stats()["suffix_hits"] == 1
    st = dev.stats()
    assert st["spliced"] == 0 and st["misses"] == 2, st


def test_bypasses_binary_and_tokenless_chunks():
    """A token-less chunk bypasses tier 2.5; a binary-stream chunk whose
    docs all carry tokens stays resident."""
    dev = DevicePackCache()
    binary = [bench.synth_doc(i, 16) for i in range(4)]  # no tokens
    got, stage, _ = _run(binary, dev, None, chunk_docs=4)
    assert got == [s.digest() for s in replay_mergetree_batch(binary)]
    assert dev.stats()["bypass"] == 1 and len(dev) == 0
    assert stage["h2d_bytes"] > 0  # the full upload is still counted
    for i, doc in enumerate(binary):
        doc.cache_token = ("ep", f"b{i}", 0, "")
    _run(binary, dev, None, chunk_docs=4)
    got, stage, _ = _run(binary, dev, None, chunk_docs=4)
    assert got == [s.digest() for s in replay_mergetree_batch(binary)]
    assert dev.stats()["served"] == 1 and len(dev) == 1
    assert stage["h2d_bytes"] == 0


def test_service_stream_docs_splice_and_serve_resident():
    """Stream documents keep tiers 2.5 and 0 through the service: a grown
    tail uploads only its suffix rows through the splice; an unchanged
    catch-up dispatches the resident buffers with zero h2d bytes and
    serves every summary from tier 0 without a download."""
    from fluidframework_tpu.service.catchup import CatchupService
    from tests.test_catchup_cache import container_fold, stream_service

    service, doc_ids, grow = stream_service()
    svc = CatchupService(service, mesh=None, cache=None)
    stage = svc.pipeline_stage
    svc.catch_up(doc_ids, upload=False)
    cold_h2d = stage["h2d_bytes"]
    assert svc.pipeline_stats["pack_stream_docs"] == len(doc_ids)

    grow([2])
    assert svc.catch_up(doc_ids, upload=False) == \
        container_fold(service, doc_ids)
    assert svc.device_cache.stats()["spliced"] == 1
    assert 0 < stage["h2d_bytes"] - cold_h2d < cold_h2d

    h2d, delta = stage["h2d_bytes"], svc.pipeline_stats.get("delta_docs", 0)
    assert svc.catch_up(doc_ids, upload=False) == \
        container_fold(service, doc_ids)
    assert stage["h2d_bytes"] == h2d, "exact hit must upload nothing"
    assert svc.device_cache.stats()["served"] == 1
    assert svc.pipeline_stats["delta_docs"] - delta == len(doc_ids)
    assert svc.delta_cache.stats()["served"] >= len(doc_ids)


# --- cache unit behavior -----------------------------------------------------


def test_byte_bound_and_lru_eviction():
    streams = _streams(8, n_ops=32)
    probe, pack = DevicePackCache(), PackCache()
    docs = _corpus(streams, lo=24, hi=32)
    _run(docs, probe, pack, chunk_docs=2)  # 4 chunks
    assert len(probe) == 4
    per_entry = max(e.nbytes for e in probe._entries.values())
    dev = DevicePackCache(max_bytes=2 * per_entry)
    pack2 = PackCache()
    _run(docs, dev, pack2, chunk_docs=2)
    st = dev.stats()
    assert len(dev) <= 2 and st["evictions"] >= 2
    assert st["bytes"] <= dev.max_bytes
    # An entry larger than the whole budget is never admitted.
    tiny = DevicePackCache(max_bytes=16)
    _run(docs[:2], tiny, PackCache(), chunk_docs=2)
    assert len(tiny) == 0 and tiny.stats()["evictions"] >= 1


def test_epoch_bump_invalidates_resident_entries():
    streams = _streams(4)
    dev, pack = DevicePackCache(), PackCache()
    _run(_corpus(streams, epoch="e1"), dev, pack, chunk_docs=4)
    assert len(dev) == 1
    assert dev.invalidate_epoch("e2") == 1
    assert len(dev) == 0
    assert dev.stats()["invalidations"] == 1
    assert dev.invalidate_epoch("e2") == 0  # O(1) unchanged-epoch path
    docs2 = _corpus(streams, epoch="e2")
    got, _, _ = _run(docs2, dev, pack, chunk_docs=4)
    assert got == [s.digest() for s in replay_mergetree_batch(docs2)]


def test_service_device_gate_off(monkeypatch):
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    monkeypatch.setenv("FLUID_TPU_CATCHUP_DEVICERESIDENT", "off")
    svc = CatchupService(LocalOrderingService(), mesh=None)
    assert svc.device_cache is None


# --- fuzz: resident-on == resident-off across random growth ------------------


@pytest.mark.parametrize("seed", range(2))
def test_fuzz_resident_on_matches_off(seed):
    """Random growth rounds (bucket-crossing repacks and
    interval/annotate fuzz docs included): every round's resident-tier
    results equal a fresh full replay byte-for-byte."""
    from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
    from fluidframework_tpu.testing.mocks import channel_log

    rng = random.Random(9100 + seed)
    streams = _streams(8, n_ops=48)
    fuzz_docs = []
    for i, spec in enumerate((StringFuzzSpec(annotate=True,
                                             intervals=True),
                              StringFuzzSpec(obliterate=True))):
        _r, f = run_fuzz(spec, seed=9200 + 10 * seed + i, n_clients=3,
                         rounds=6, sync_every=2)
        fuzz_docs.append(MergeTreeDocInput(
            doc_id=f"fz{i}", ops=channel_log(f, "fuzz"),
            final_seq=f.sequencer.seq, final_msn=f.sequencer.min_seq,
            cache_token=("ep", f"fz{i}", 0, "")))
    dev, pack = DevicePackCache(), PackCache()
    delta = DeltaExportCache()
    windows = [12] * len(streams)
    for _round in range(4):
        docs = [_window(streams, i, windows[i])
                for i in range(len(streams))] + fuzz_docs
        expect = [s.digest() for s in replay_mergetree_batch(docs)]
        got, _, _ = _run(docs, dev, pack, delta, chunk_docs=6)
        assert got == expect, f"seed {seed}: resident-on != full replay"
        for i in range(len(streams)):  # grow a random subset
            if rng.random() < 0.4:
                windows[i] = min(len(streams[i]),
                                 windows[i] + rng.randint(1, 14))
    st = dev.stats()
    assert st["served"] + st["spliced"] > 0, (
        "fuzz never exercised the resident tier")


# --- mesh parity: the acceptance criterion -----------------------------------


def test_mesh_fold_serves_the_full_tier_stack():
    """The mesh-sharded fold on the forced 8-device CPU mesh serves
    tier-0 / tier-2 / tier-2.5 with the full stage-counter schema:
    byte-identical to the one-batch replay, zero h2d pack bytes on the
    exact warm pass, digest-plane-only d2h, and a suffix splice on the
    grown pass — the mesh-parity debt paid."""
    from fluidframework_tpu.parallel.shard import (
        doc_mesh,
        replay_mergetree_sharded,
    )

    mesh = doc_mesh()
    streams = _streams(11)  # not a multiple of 8: exercises pad tokens
    pack, delta, dev = PackCache(), DeltaExportCache(), DevicePackCache()
    stage: dict = {}
    cold = _corpus(streams)
    out = replay_mergetree_sharded(cold, mesh=mesh, stage=stage,
                                   pack_cache=pack, delta_cache=delta,
                                   device_cache=dev)
    expect = [s.digest() for s in replay_mergetree_batch(cold)]
    assert [s.digest() for s in out] == expect
    assert {"pack", "upload", "dispatch", "device_wait", "download",
            "extract", "h2d_bytes", "d2h_bytes"} <= set(stage)
    h2d_cold = stage["h2d_bytes"]

    stage2: dict = {}
    stats2: dict = {}
    out2 = replay_mergetree_sharded(cold, mesh=mesh, stage=stage2,
                                    stats=stats2, pack_cache=pack,
                                    delta_cache=delta, device_cache=dev)
    assert [s.digest() for s in out2] == expect
    assert stage2["h2d_bytes"] == 0, "mesh exact hit must upload nothing"
    # Digest plane only — counted PADDED (11 docs pad to 16 on the
    # 8-device mesh; the pad rows really cross the link), while the
    # tier-0 handshake itself sees only the real prefix.
    assert stage2["d2h_bytes"] == 8 * 16
    assert stats2.get("delta_docs") == len(cold)

    grown = _corpus(streams, grown={0, 5})
    stage3: dict = {}
    out3 = replay_mergetree_sharded(grown, mesh=mesh, stage=stage3,
                                    stats={}, pack_cache=pack,
                                    delta_cache=delta, device_cache=dev)
    assert [s.digest() for s in out3] == \
        [s.digest() for s in replay_mergetree_batch(grown)]
    assert dev.stats()["spliced"] == 1
    assert stage3["h2d_bytes"] * 5 <= h2d_cold


def test_mesh_service_stage_schema_matches_single_device():
    """CatchupService on the mesh serves byte-identical results through
    the same four-tier stack, and its ``pipeline_stage`` schema is
    IDENTICAL to the single-device instance's (the ISSUE 13 satellite:
    no counter the mesh path drops)."""
    from fluidframework_tpu.parallel.shard import doc_mesh
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    service = LocalOrderingService()
    doc_ids = bench.build_catchup_corpus(service, 6, 14)
    plain = CatchupService(service, mesh=None, cache=None,
                           pack_cache=None, delta_cache=None,
                           device_cache=None)
    expect = plain.catch_up(doc_ids, upload=False)

    single = CatchupService(service, mesh=None, cache=None)
    mesh_svc = CatchupService(service, mesh=doc_mesh(), cache=None)
    assert single.catch_up(doc_ids, upload=False) == expect
    assert single.catch_up(doc_ids, upload=False) == expect
    assert mesh_svc.catch_up(doc_ids, upload=False) == expect
    assert mesh_svc.catch_up(doc_ids, upload=False) == expect
    assert sorted(mesh_svc.pipeline_stage) == \
        sorted(single.pipeline_stage), "mesh stage schema drifted"
    for svc in (single, mesh_svc):
        assert svc.device_cache.stats()["served"] >= 1
        assert svc.delta_cache.stats()["served"] >= 1
        assert svc._pack_cache.stats()["exact_hits"] >= 1
