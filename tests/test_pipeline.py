"""The product's pipelined bulk replay (ops/pipeline.py) vs the one-batch
replay_mergetree_batch: identical summaries in the caller's order across
cold, warm, interval, attribution, and oracle-fallback docs — the service
and the bench harness both ride this path."""

import numpy as np
import pytest

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.pipeline import pipelined_mergetree_replay
from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
from fluidframework_tpu.testing.mocks import channel_log
from tests.test_upload_narrow import _warm_doc


def _mixed_docs():
    docs = [bench.synth_doc(i, 40) for i in range(40)]      # cold binary
    docs += [_warm_doc(260 + i) for i in range(3)]          # warm
    for seed in (270, 271):                                  # fuzz logs
        _r, f = run_fuzz(StringFuzzSpec(annotate=True, intervals=True),
                         seed=seed, n_clients=3, rounds=8, sync_every=2)
        docs.append(MergeTreeDocInput(
            doc_id=f"mix{seed}", ops=channel_log(f, "fuzz"),
            final_seq=f.sequencer.seq, final_msn=f.sequencer.min_seq))
    return docs


def test_pipelined_matches_one_batch_replay():
    docs = _mixed_docs()
    base_stats: dict = {}
    expect = [s.digest() for s in replay_mergetree_batch(docs, base_stats)]
    stats: dict = {}
    stage: dict = {}
    packed: list = []
    got = pipelined_mergetree_replay(
        docs, chunk_docs=16, pack_threads=2, extract_threads=2,
        fetch_depth=1, stats=stats, stage=stage, packed_out=packed)
    assert [s.digest() for s in got] == expect, "pipeline changed bytes"
    assert len(packed) == (len(docs) + 15) // 16
    assert all(len(entry) == 4 for entry in packed)  # (state, ops, meta, S)
    assert stats.get("device_docs", 0) > 0
    assert stats.get("fallback_docs", 0) == base_stats.get("fallback_docs", 0)
    assert stage.get("pack", 0) > 0 and stage.get("download", 0) >= 0
    # Honest stage attribution (ISSUE 6): the async fold wait is split
    # out of "download", and the d2h byte counter records real traffic.
    assert "device_wait" in stage
    assert stage.get("d2h_bytes", 0) > 0


def test_pipelined_schedule_returns_caller_order():
    """Fact scheduling reorders chunks internally; results must come back
    in the caller's order (alternate props/pure docs so the sort really
    permutes)."""
    docs = []
    for i in range(30):
        docs.append(bench.synth_doc(3 * i + 1, 32))  # mix annotate/pure
    expect = [s.digest() for s in replay_mergetree_batch(docs)]
    got = pipelined_mergetree_replay(docs, chunk_docs=8)
    assert [s.digest() for s in got] == expect


def test_pipelined_empty_and_single():
    assert pipelined_mergetree_replay([]) == []
    [one] = pipelined_mergetree_replay([bench.synth_doc(5, 24)])
    [ref] = replay_mergetree_batch([bench.synth_doc(5, 24)])
    assert one.digest() == ref.digest()


# -- overlap-remover slots through the cache tiers ----------------------------


def _overlap_window(msgs, n):
    return MergeTreeDocInput(doc_id="ov", ops=msgs[:n],
                             final_seq=msgs[n - 1].seq, final_msn=0,
                             cache_token=("ep", "ov", 0, ""))


def test_suffix_extension_that_adds_a_remover_rederives_the_slots():
    """Tier 2 extends a cached pack whose tail gains more concurrent
    removers of one segment: the extension re-derives ``ov_slots`` (from
    1) and the facts a fresh pack of the same window gives, widens the
    base planes, and the fold answers the oracle's bytes."""
    from fluidframework_tpu.ops.mergetree_kernel import pack_mergetree_batch
    from fluidframework_tpu.ops.pipeline import PackCache
    from tests.test_mergetree_kernel import (
        _oracle_digest,
        _overlap_spec,
        _seq_msgs,
    )

    msgs = _seq_msgs(_overlap_spec(3))
    cache = PackCache()
    stats: dict = {}
    # The two inserts, the winner and one overlapping remover.
    pipelined_mergetree_replay([_overlap_window(msgs, 4)],
                               pack_cache=cache, stats=stats)
    assert stats["ov_slots_1"] == 1
    full = _overlap_window(msgs, len(msgs))
    _s, _o, fresh = pack_mergetree_batch([full])
    slots = fresh["ov_slots"]
    assert slots > 1
    [got] = pipelined_mergetree_replay([full], pack_cache=cache,
                                       stats=stats)
    assert cache.stats()["suffix_hits"] == 1
    assert stats[f"ov_slots_{slots}"] == 1
    assert stats.get("fallback_docs", 0) == 0
    assert got.digest() == _oracle_digest(msgs)
    state, _ops, meta = cache.pack([full])  # the exact hit: stored entry
    for key in ("ov_slots", "sequential", "ob_rows", "has_props",
                "i16_ok", "i8_ok"):
        assert meta[key] == fresh[key], key
    assert len(state.remx_seq) == len(state.remx_client) == slots - 1


def test_overlap_slots_past_the_first_enter_the_tier0_digest():
    """Two final states that differ only in an overlap slot past the
    first digest apart; an empty extra slot adds nothing, so a document's
    digest does not move when another document's third remover gives the
    chunk more slots."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.mergetree_kernel import (
        NOT_REMOVED,
        _doc_digests,
        pack_mergetree_batch,
        replay_vmapped,
    )
    from tests.test_mergetree_kernel import _overlap_case

    doc, _expected = _overlap_case("cold-5")
    state, ops, meta = pack_mergetree_batch([doc])
    assert meta["ov_slots"] >= 4
    final = jax.jit(replay_vmapped)(state, ops)
    base = jnp.asarray(meta["doc_base"], jnp.int32)

    def digest(st):
        return np.asarray(_doc_digests(st, base)).tolist()

    seqs = [np.asarray(p) for p in final.remx_seq]
    taken = np.argwhere(seqs[1] != NOT_REMOVED)  # slot 3 is in use
    assert len(taken), "the fixture fills the third overlap slot"
    d, s = taken[0]
    for field in ("remx_seq", "remx_client"):  # another seq; another client
        moved = [np.asarray(p).copy() for p in getattr(final, field)]
        moved[1][d, s] += 1
        assert digest(final._replace(**{field: tuple(moved)})) \
            != digest(final)
    # An empty slot past the first is digest-neutral.
    plain, _o, meta1 = pack_mergetree_batch([bench.synth_doc(3, 24)])
    folded = jax.jit(replay_vmapped)(plain, _o)
    assert len(folded.remx_seq) == len(folded.remx_client) == 0
    padded = folded._replace(
        remx_seq=(jnp.full(folded.rem2_seq.shape, NOT_REMOVED, jnp.int32),),
        remx_client=(jnp.full(folded.rem2_seq.shape, -1, jnp.int32),))
    b1 = jnp.asarray(meta1["doc_base"], jnp.int32)
    assert np.asarray(_doc_digests(padded, b1)).tolist() == \
        np.asarray(_doc_digests(folded, b1)).tolist()
