"""Device merge-tree kernel vs CPU oracle: byte-identical summaries.

The north-star acceptance gate (SURVEY.md §7 layer 4): fuzz-generated
SharedString op logs replayed through the device op-fold must produce the
exact canonical summary bytes of the oracle — same walk, same tie-breaks,
same overlap-removal bookkeeping, same normalization.
"""

import json

import pytest

from fluidframework_tpu.dds import SharedString
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    replay_mergetree_batch,
)
from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
from fluidframework_tpu.testing.mocks import channel_log


def _kernel_inputs_from_fuzz(factory, doc_id="fuzz", base_records=None,
                             min_seq_exclusive=0):
    return MergeTreeDocInput(
        doc_id=doc_id,
        ops=channel_log(factory, "fuzz", min_seq_exclusive=min_seq_exclusive),
        base_records=base_records,
        final_seq=factory.sequencer.seq,
        final_msn=factory.sequencer.min_seq,
    )


@pytest.mark.parametrize("seed", range(8))
def test_mergetree_kernel_matches_oracle_on_fuzz_logs(seed):
    replicas, factory = run_fuzz(
        StringFuzzSpec(), seed=seed, n_clients=3, rounds=20
    )
    oracle = replicas[0].summarize()
    [summary] = replay_mergetree_batch([_kernel_inputs_from_fuzz(factory)])
    assert summary.digest() == oracle.digest(), (
        f"seed={seed}: kernel body "
        f"{summary.blob_bytes('body')!r} != oracle "
        f"{oracle.blob_bytes('body')!r}"
    )


def test_mergetree_kernel_batches_docs_of_different_sizes():
    docs, oracle_digests = [], []
    for seed in (50, 51, 52):
        replicas, factory = run_fuzz(
            StringFuzzSpec(), seed=seed, n_clients=2, rounds=6 + 4 * (seed % 3)
        )
        docs.append(_kernel_inputs_from_fuzz(factory, doc_id=f"d{seed}"))
        oracle_digests.append(replicas[0].summarize().digest())
    summaries = replay_mergetree_batch(docs)
    assert [s.digest() for s in summaries] == oracle_digests


def test_mergetree_kernel_replays_tail_from_base_summary():
    """The flagship catch-up shape: summary at seq S + op tail."""
    replicas, factory = run_fuzz(
        StringFuzzSpec(), seed=9, n_clients=3, rounds=16
    )
    full_ops = channel_log(factory, "fuzz")
    mid_seq = full_ops[len(full_ops) // 2].seq
    # Build the base summary by oracle catch-up to the midpoint.
    partial = SharedString("fuzz")
    for msg in full_ops:
        if msg.seq <= mid_seq:
            partial.process(msg, local=False)
    base_summary = partial.summarize()
    base_records = json.loads(base_summary.blob_bytes("body"))
    doc = MergeTreeDocInput(
        doc_id="fuzz",
        ops=[m for m in full_ops if m.seq > mid_seq],
        base_records=base_records,
        final_seq=factory.sequencer.seq,
        final_msn=factory.sequencer.min_seq,
    )
    [summary] = replay_mergetree_batch([doc])
    # Oracle continuation from the same summary must agree too.
    resumed = SharedString("fuzz")
    resumed.load(base_summary)
    for msg in full_ops:
        if msg.seq > mid_seq:
            resumed.process(msg, local=False)
    resumed.advance(factory.sequencer.seq, factory.sequencer.min_seq)
    assert summary.digest() == resumed.summarize().digest()


@pytest.mark.parametrize("seed", range(6))
def test_mergetree_kernel_with_interval_ops(seed):
    """Config #3 parity: logs containing interval ops replay through the
    device fold + host interval pass to oracle-identical bytes."""
    replicas, factory = run_fuzz(
        StringFuzzSpec(intervals=True), seed=900 + seed, n_clients=3, rounds=25
    )
    oracle = replicas[0].summarize()
    [summary] = replay_mergetree_batch([_kernel_inputs_from_fuzz(factory)])
    assert summary.digest() == oracle.digest(), (
        f"seed={seed}: kernel {summary.children.keys()} vs oracle "
        f"{oracle.children.keys()}"
    )


def test_interval_tail_from_base_summary():
    """Catch-up with a base summary carrying an intervals blob."""
    replicas, factory = run_fuzz(
        StringFuzzSpec(intervals=True), seed=42, n_clients=3, rounds=16
    )
    full_ops = channel_log(factory, "fuzz")
    mid_seq = full_ops[len(full_ops) // 2].seq
    partial = SharedString("fuzz")
    for msg in full_ops:
        if msg.seq <= mid_seq:
            partial.process(msg, local=False)
    base_summary = partial.summarize()
    base_records = json.loads(base_summary.blob_bytes("body"))
    try:
        base_intervals = json.loads(base_summary.blob_bytes("intervals"))
    except KeyError:
        base_intervals = None
    doc = MergeTreeDocInput(
        doc_id="fuzz",
        ops=[m for m in full_ops if m.seq > mid_seq],
        base_records=base_records,
        base_intervals=base_intervals,
        base_seq=partial.tree.current_seq,
        base_msn=partial.tree.min_seq,
        final_seq=factory.sequencer.seq,
        final_msn=factory.sequencer.min_seq,
    )
    [summary] = replay_mergetree_batch([doc])
    resumed = SharedString("fuzz")
    resumed.load(base_summary)
    for msg in full_ops:
        if msg.seq > mid_seq:
            resumed.process(msg, local=False)
    resumed.advance(factory.sequencer.seq, factory.sequencer.min_seq)
    assert summary.digest() == resumed.summarize().digest()


def test_summarize_refuses_inflight_interval_ops():
    from fluidframework_tpu.testing import MockContainerRuntimeFactory

    factory = MockContainerRuntimeFactory()
    a = factory.create_client("A").attach(SharedString("s"))
    a.insert_text(0, "text")
    factory.process_all_messages()
    a.add_interval(0, 2)
    with pytest.raises(RuntimeError, match="in-flight interval ops"):
        a.summarize()
    factory.process_all_messages()
    a.summarize()  # fine once sequenced


def test_insert_with_none_prop_value_matches_kernel():
    """Regression: a None prop value on insert means 'absent' on both paths."""
    from fluidframework_tpu.testing import MockContainerRuntimeFactory

    factory = MockContainerRuntimeFactory()
    a = factory.create_client("A").attach(SharedString("s"))
    a.insert_text(0, "hello", props={"k": None, "m": 2})
    factory.process_all_messages()
    [dev] = replay_mergetree_batch(
        [
            MergeTreeDocInput(
                "s",
                channel_log(factory, "s"),
                final_seq=factory.sequencer.seq,
                final_msn=factory.sequencer.min_seq,
            )
        ]
    )
    assert dev.digest() == a.summarize().digest()
    assert json.loads(a.summarize().blob_bytes("body"))[0]["p"] == {"m": 2}


def test_mergetree_kernel_empty_doc_and_noop_padding():
    doc = MergeTreeDocInput(doc_id="empty", ops=[], final_seq=0, final_msn=0)
    [summary] = replay_mergetree_batch([doc])
    fresh = SharedString("empty")
    assert summary.digest() == fresh.summarize().digest()


def test_export_widths_agree_and_widen_roundtrips():
    """The int16 export (doc-rebased tstart, remapped sentinels) must widen
    back to exactly the int32 export, and both must extract to the same
    canonical summaries (the i16 path halves the device→host transfer — the
    measured pipeline bottleneck)."""
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import (
        pack_mergetree_batch,
        replay_export,
        summaries_from_export,
        widen_export,
    )

    docs = []
    for seed in (70, 71, 72, 73):
        replicas, factory = run_fuzz(
            StringFuzzSpec(), seed=seed, n_clients=3, rounds=8
        )
        docs.append(_kernel_inputs_from_fuzz(factory, doc_id=f"w{seed}"))
    state, ops, meta = pack_mergetree_batch(docs)
    S = state.tstart.shape[1]
    assert meta["i16_ok"], "small fuzz batch must qualify for int16 export"

    from fluidframework_tpu.ops.mergetree_kernel import export_to_numpy

    ex16 = export_to_numpy(replay_export(None, ops, meta, S=S))
    slots16 = ex16[0] if isinstance(ex16, tuple) else ex16
    assert slots16.dtype == np.int16
    meta32 = dict(meta, i16_ok=False)
    ex32 = export_to_numpy(replay_export(None, ops, meta32, S=S))
    assert ex32.dtype == np.int32
    from fluidframework_tpu.ops.mergetree_kernel import _export_flags

    _i, ob_f, ov_f, i8_f, props_f = _export_flags(meta)
    w16 = widen_export(ex16, meta["doc_base"], ob_rows=ob_f, ov_slots=ov_f,
                       i8=i8_f, n_props=meta["props_K"], props_rows=props_f)
    w32 = widen_export(ex32, None, ob_rows=ob_f, ov_slots=ov_f,
                       n_props=meta["props_K"], props_rows=props_f)
    if i8_f:
        # Bit-equality holds for the slots extraction reads ([0, n) per
        # doc); beyond n the int8 pack truncates dead-slot garbage to 8
        # bits, so the widths legitimately differ there.
        n = w32[:, -1, 0]
        for d in range(w32.shape[0]):
            np.testing.assert_array_equal(
                w16[d, :, :n[d]], w32[d, :, :n[d]], err_msg=f"doc {d}"
            )
    else:
        np.testing.assert_array_equal(w16, w32)
    d16 = [s.digest() for s in summaries_from_export(meta, ex16)]
    d32 = [s.digest() for s in summaries_from_export(meta32, ex32)]
    assert d16 == d32


def test_obliterate_rows_elided_when_chunk_has_none():
    """A chunk with no obliterate ops transfers 4 fewer slot rows; the
    host reinserts sentinels and summaries stay byte-identical.  A chunk
    WITH an obliterate keeps the full layout."""
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import (
        EXPORT_SLOT_FIELDS,
        NON_OB_SLOT_FIELDS,
        pack_mergetree_batch,
        replay_export,
        summaries_from_export,
    )
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def op(seq, contents):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents=contents,
        )

    plain = MergeTreeDocInput(
        doc_id="plain",
        ops=[op(1, {"kind": "insert", "pos": 0, "text": "hello"}),
             op(2, {"kind": "remove", "start": 1, "end": 3})],
        final_seq=2, final_msn=0,
    )
    from fluidframework_tpu.ops.mergetree_kernel import export_layout_rows

    state, ops, meta = pack_mergetree_batch([plain])
    assert meta["ob_rows"] is False
    assert meta["ov_slots"] == 0  # sequential: rem2 rows elided too
    from fluidframework_tpu.ops.mergetree_kernel import export_to_numpy

    assert meta["i8_ok"], "fixture must qualify for the i8 layout"
    ex = export_to_numpy(replay_export(None, ops, meta, S=state.tstart.shape[1]))
    # i8 layouts return (slot_rows, misc) — the misc row left the buffer
    slots, misc = ex
    assert slots.shape[1] == export_layout_rows(meta)
    assert misc.shape == (1, 4) and misc.dtype == np.int32
    # elisions + byte packing really shrink the buffer vs the full layout
    full_rows = len(EXPORT_SLOT_FIELDS) + meta["props_K"] + 1
    assert slots.shape[1] < full_rows - 5
    [summary] = summaries_from_export(meta, ex)
    replica = SharedString("plain")
    for msg in plain.ops:
        replica.process(msg, local=False)
    assert summary.digest() == replica.summarize().digest()

    obd = MergeTreeDocInput(
        doc_id="ob",
        ops=[op(1, {"kind": "insert", "pos": 0, "text": "hello"}),
             op(2, {"kind": "obliterate", "start": 1, "end": 3})],
        final_seq=2, final_msn=0,
    )
    state2, ops2, meta2 = pack_mergetree_batch([obd])
    assert meta2["ob_rows"] is True
    ex2 = export_to_numpy(
        replay_export(None, ops2, meta2, S=state2.tstart.shape[1])
    )
    slots2 = ex2[0] if isinstance(ex2, tuple) else ex2
    assert slots2.shape[1] == export_layout_rows(meta2)
    [summary2] = summaries_from_export(meta2, ex2)
    replica2 = SharedString("ob")
    for msg in obd.ops:
        replica2.process(msg, local=False)
    assert summary2.digest() == replica2.summarize().digest()


def test_export_i16_disabled_for_wide_values():
    """A chunk whose head sequence exceeds the int16 range must fall back to
    the int32 export and still match the oracle byte-for-byte."""
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import pack_mergetree_batch
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    big = 40_000  # > int16 max
    ops = [
        SequencedMessage(seq=big + i, client_id="c0", client_seq=i + 1,
                         ref_seq=big + i - 1, min_seq=0, type=MessageType.OP,
                         contents={"kind": "insert", "pos": 0, "text": "ab"})
        for i in range(3)
    ]
    doc = MergeTreeDocInput(doc_id="wide", ops=ops, final_seq=big + 3,
                            final_msn=0)
    _state, _ops, meta = pack_mergetree_batch([doc])
    assert not meta["i16_ok"]
    [summary] = replay_mergetree_batch([doc])
    body = json.loads(summary.blob_bytes("body"))
    assert "".join(rec["t"] for rec in body) == "ababab"


@pytest.mark.parametrize("seed", range(6))
def test_mergetree_kernel_obliterate_matches_oracle(seed):
    """Obliterate through the device fold: fuzz logs with obliterate ops
    (concurrent obliterates, obliterate-vs-insert races) replayed by the
    kernel must be byte-identical to the oracle."""
    replicas, factory = run_fuzz(
        StringFuzzSpec(obliterate=True), seed=900 + seed, n_clients=3,
        rounds=14, sync_every=1,
    )
    oracle = replicas[0].summarize()
    [summary] = replay_mergetree_batch([_kernel_inputs_from_fuzz(factory)])
    assert summary.digest() == oracle.digest(), (
        f"seed={seed}: kernel body "
        f"{summary.blob_bytes('body')!r} != oracle "
        f"{oracle.blob_bytes('body')!r}"
    )


def test_mergetree_kernel_obliterate_warm_start():
    """Warm start: a summary with in-window obliterate stamps re-enters the
    kernel as base records and tail inserts still die/survive correctly."""
    replicas, factory = run_fuzz(
        StringFuzzSpec(obliterate=True), seed=950, n_clients=3,
        rounds=10, sync_every=1,
    )
    ops = channel_log(factory, "fuzz")
    mid_seq = ops[len(ops) // 2].seq
    partial = SharedString("fuzz")
    for msg in ops:
        if msg.seq <= mid_seq:
            partial.process(msg, local=False)
    base = partial.summarize()
    import json as _json

    doc = MergeTreeDocInput(
        doc_id="fuzz",
        ops=[m for m in ops if m.seq > mid_seq],
        base_records=_json.loads(base.blob_bytes("body")),
        base_seq=mid_seq, base_msn=partial.tree.min_seq,
        final_seq=factory.sequencer.seq,
        final_msn=factory.sequencer.min_seq,
    )
    [summary] = replay_mergetree_batch([doc])
    assert summary.digest() == replicas[0].summarize().digest()


def test_hard_mergetree_semantics_match_oracle(graft_entry):
    """The dryrun's hard-semantics docs (deep-lag obliterate arrival kill,
    overlap removers, annotate races, lagged fuzz logs, a warm obliterate
    base) folded on one device through the served dispatch and
    extraction, cold and warm, equal the CPU oracle byte for byte."""
    from fluidframework_tpu.ops.mergetree_kernel import (
        export_to_numpy,
        pack_mergetree_batch,
        replay_export,
        summaries_from_export,
    )

    docs = graft_entry._hard_mergetree_docs()
    oracle = {}
    for doc in docs:
        replica = SharedString(doc.doc_id)
        if doc.base_records is not None:
            replica.tree.load_records(doc.base_records, doc.base_seq,
                                      doc.base_msn)
        for m in doc.ops:
            replica.process(m, local=False)
        replica.advance(doc.final_seq, doc.final_msn)
        oracle[doc.doc_id] = replica.summarize().digest()

    cold = [d for d in docs if d.base_records is None]
    assert len(cold) < len(docs), "hard docs must include a warm doc"
    for chunk, warm in ((cold, False), (docs, True)):
        state, ops, meta = pack_mergetree_batch(chunk)
        export = replay_export(state if warm else None, ops, meta,
                               S=state.tstart.shape[1])
        stats = {}
        summaries = summaries_from_export(meta, export_to_numpy(export),
                                          stats=stats)
        assert stats.get("device_docs") == len(chunk), stats
        assert [s.digest() for s in summaries] == \
            [oracle[d.doc_id] for d in chunk], f"warm={warm}"


def test_sequential_tail_over_stamped_base_skips_kills_correctly():
    """The fold's sequential fast path skips the arrival-kill scan even
    when the BASE summary carries obliterate stamps (a stamp seq <=
    base_seq <= every sequential tail ref can never kill).  Pin that
    claim against the oracle: warm doc, in-window base ob stamps, strictly
    sequential tail with inserts landing between stamped slots."""
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import pack_mergetree_batch
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def op(seq, contents):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents=contents,
        )

    # Build the base via the oracle: insert then obliterate the middle —
    # the summary retains stamped tombstones in-window.
    base_replica = SharedString("wb")
    for msg in (op(1, {"kind": "insert", "pos": 0, "text": "abcdef"}),
                op(2, {"kind": "obliterate", "start": 1, "end": 5})):
        base_replica.process(msg, local=False)
    base_summary = base_replica.summarize()
    base_records = json.loads(base_summary.blob_bytes("body"))
    assert any("ob" in rec for rec in base_records), \
        "base must carry obliterate stamps for this test to bite"

    tail = [op(3, {"kind": "insert", "pos": 1, "text": "XY"}),
            op(4, {"kind": "remove", "start": 0, "end": 1})]
    doc = MergeTreeDocInput(
        doc_id="wb", ops=tail, base_records=base_records,
        base_seq=2, base_msn=0, final_seq=4, final_msn=0,
    )
    _s, _o, meta = pack_mergetree_batch([doc])
    assert meta["sequential"] and meta["ob_rows"], (
        "fixture must hit the sequential fast path WITH base stamps")

    [summary] = replay_mergetree_batch([doc])
    resumed = SharedString("wb")
    resumed.load(base_summary)
    for msg in tail:
        resumed.process(msg, local=False)
    resumed.advance(4, 0)
    assert summary.digest() == resumed.summarize().digest()


def test_header_fast_format_matches_canonical_json():
    """The hand-formatted header blob must stay byte-equal to
    canonical_json for every value shape the header can carry."""
    from fluidframework_tpu.protocol.summary import canonical_json

    for length, min_seq, seq in [(0, 0, 0), (7, 3, 12), (32766, 1, 983040),
                                 (123456789, 98765, 2**31 - 1)]:
        fast = b'{"length":%d,"minSeq":%d,"seq":%d}' % (length, min_seq, seq)
        assert fast == canonical_json(
            {"seq": seq, "minSeq": min_seq, "length": length})


def test_ob_stamp_author_involvement_in_lagged_view():
    """Fuzz seed 1500041 (minimized): a segment removed by one client but
    carrying ANOTHER client's obliterate stamp must be hidden from views
    in the stamp author's name — the author's optimistic view hid every
    covered slot, so a lagged insert by the author resolves positions
    without it.  The kernel's visibility lacked the stamp-author term and
    placed the insert several chars off."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def m(seq, client, ref, contents):
        return SequencedMessage(seq=seq, client_id=client, client_seq=seq,
                                ref_seq=ref, min_seq=0,
                                type=MessageType.OP, contents=contents)

    log = [
        m(1, "c0", 0, {"kind": "insert", "pos": 0, "text": "abcdef"}),
        # c1's remove of [2,4) wins the removal of "cd"...
        m(2, "c1", 1, {"kind": "remove", "start": 2, "end": 4}),
        # ...then c2 obliterates [1,3) of its ref-2 view "abef" — the
        # "cd" tombstone sits at ZERO WIDTH strictly inside the range,
        # so it gets c2's stamp with NO remover bookkeeping (the stamp
        # is the only durable record of c2's coverage).
        m(3, "c2", 2, {"kind": "obliterate", "start": 1, "end": 3}),
        # c2's lagged insert (ref 1, before the removal): in c2's own
        # view "cd" must be HIDDEN (c2 stamped it) even though c1 won
        # the removal and c2 never became its overlap remover — pos 2
        # is the end of "af", not a point inside "cd".
        m(4, "c2", 1, {"kind": "insert", "pos": 2, "text": "XY"}),
    ]
    oracle = SharedString("obinv")
    for msg in log:
        oracle.process(msg, local=False)
    doc = MergeTreeDocInput(doc_id="obinv", ops=log, final_seq=4,
                            final_msn=0)
    [summary] = replay_mergetree_batch([doc])
    assert summary.digest() == oracle.summarize().digest(), (
        "stamp-author involvement: kernel != oracle"
    )


# --- The scan step's one-slot shift: a roll and a select, equal to the
# per-document take it replaced (which the TPU compiles to a general
# batched gather).

SHIFT_S, SHIFT_N, SHIFT_K = 16, 11, 3


def _take_shift(f, keep):
    """The former form: ``take`` along the slot axis from slot - 1 past
    the prefix ``keep``."""
    import jax.numpy as jnp

    slot = jnp.arange(f.shape[0])
    return jnp.take(f, jnp.where(keep, slot, slot - 1), axis=0)


def _index_pick(f, at):
    """The former form of a one-slot pick: a dynamic index."""
    import jax.numpy as jnp

    return f[jnp.argmax(at)]


@pytest.mark.parametrize("idx", [0, SHIFT_N // 2, SHIFT_N - 1, SHIFT_S - 1],
                         ids=["first", "middle", "last-live", "last-slot"])
@pytest.mark.parametrize("plane", ["slots", "props"])
def test_shift_right_equals_the_take_shift(plane, idx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fluidframework_tpu.ops.mergetree_kernel import (
        NOT_REMOVED,
        _pick,
        _shift_right,
    )

    rng = np.random.default_rng(idx)
    shape = (SHIFT_S,) if plane == "slots" else (SHIFT_S, SHIFT_K)
    f = rng.integers(-2, 1 << 20, size=shape).astype(np.int32)
    f[rng.random(shape) < 0.2] = NOT_REMOVED
    slot = jnp.arange(SHIFT_S)
    keep = slot <= idx
    np.testing.assert_array_equal(_shift_right(jnp.asarray(f), keep),
                                  _take_shift(jnp.asarray(f), keep))
    if plane == "slots":
        assert int(_pick(jnp.asarray(f), slot == idx)) == int(f[idx])
    # Under vmap each document has its own split point, as in the fold.
    idxs = jnp.asarray([0, idx, SHIFT_N - 1, SHIFT_S - 1])
    batch = jnp.asarray(np.stack([np.roll(f, d, axis=0) for d in range(4)]))
    keeps = slot[None, :] <= idxs[:, None]
    np.testing.assert_array_equal(jax.vmap(_shift_right)(batch, keeps),
                                  jax.vmap(_take_shift)(batch, keeps))


def _string_tail_docs(seed, n_docs, n_ops=96):
    """The benchmark's concurrent SharedString tails (three clients,
    lagged views, 30% of documents annotating) as kernel inputs."""
    from benchmark.corpus import generator
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    make = generator("string_tail")
    docs = []
    for i in range(n_docs):
        tail = make(seed, i, n_ops)
        ops = [SequencedMessage(seq=seq, client_id=client, client_seq=seq,
                                ref_seq=ref, min_seq=msn,
                                type=MessageType.OP, contents=contents)
               for seq, client, ref, msn, contents in tail]
        docs.append(MergeTreeDocInput(doc_id=f"d{i}", ops=ops,
                                      final_seq=tail[-1][0],
                                      final_msn=tail[-1][3]))
    return docs


TAIL_SEED = 2_147_483_659


@pytest.fixture(scope="module")
def tail_chunk():
    from fluidframework_tpu.ops.mergetree_kernel import pack_mergetree_batch

    docs = _string_tail_docs(TAIL_SEED, 36)
    state, ops, meta = pack_mergetree_batch(docs)
    assert not meta["sequential"] and meta["has_props"]
    return docs, state, ops, meta


def _use_former_helpers(patch):
    from fluidframework_tpu.ops import mergetree_kernel as mk

    patch.setattr(mk, "_shift_right", _take_shift)
    patch.setattr(mk, "_pick", _index_pick)


def _assert_states_equal(new, old):
    import numpy as np

    for name, a, b in zip(new._fields, new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _fold(state, ops, facts):
    """The vmapped scan, traced afresh, so that it reads the helpers as
    they stand."""
    import jax

    from fluidframework_tpu.ops import mergetree_kernel as mk

    return jax.jit(lambda s, o: mk.replay_vmapped(s, o, **facts))(state, ops)


@pytest.mark.parametrize("facts", ["served", "full"])
def test_string_tail_fold_equals_the_take_fold(tail_chunk, monkeypatch,
                                               facts):
    """The whole fold over concurrent tails, array for array, against the
    same fold built on the former take shifts and index picks: under the
    chunk's own facts (as served) and under the full semantics (every
    plane shifted, the obliterate neighbor picks traced in)."""
    import numpy as np

    _docs, state, ops, meta = tail_chunk
    kw = {}
    if facts == "served":
        kw = dict(sequential=bool(meta["sequential"]),
                  has_ob=bool(meta["ob_rows"]), has_ov=meta["ov_slots"] > 0,
                  has_props=bool(meta["has_props"]))
    new = _fold(state, ops, kw)
    with monkeypatch.context() as patch:
        _use_former_helpers(patch)
        old = _fold(state, ops, kw)
    assert int(np.asarray(new.n).min()) > 0
    _assert_states_equal(new, old)


def test_string_tail_documents_match_the_oracle(tail_chunk):
    docs = tail_chunk[0]
    expected = []
    for doc in docs:
        replica = SharedString(doc.doc_id)
        for msg in doc.ops:
            replica.process(msg, local=False)
        expected.append(replica.summarize().digest())
    stats = {}
    summaries = replay_mergetree_batch(docs, stats=stats)
    assert stats.get("device_docs", 0) > len(docs) // 2, stats
    assert [s.digest() for s in summaries] == expected


@pytest.mark.parametrize("kind", ["split-disabled", "remove"])
def test_scan_step_without_a_shift_equals_the_take_step(tail_chunk,
                                                        monkeypatch, kind):
    """Where no shift takes effect (a split with ``enable`` false; a
    remove, whose insert branch is selected away) the step returns what
    the former step returned, and a disabled split the state itself."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops import mergetree_kernel as mk

    _docs, state, ops, _meta = tail_chunk
    d, t = 7, 60   # an annotating document, well into its tail
    doc_state = jax.tree.map(lambda x: jnp.asarray(x[d]), state)
    doc_ops = jax.tree.map(lambda x: jnp.asarray(x[d]), ops)
    mid = mk.replay_scan(doc_state, jax.tree.map(lambda x: x[:t], doc_ops))
    if kind == "split-disabled":
        def step():
            return mk._split_at(mid, jnp.int32(3), doc_ops.ref_seq[t],
                                doc_ops.client[t], jnp.bool_(False))
    else:
        remove = jax.tree.map(lambda x: x[t], doc_ops)._replace(
            kind=jnp.int32(mk.K_REMOVE), a=jnp.int32(1), b=jnp.int32(5))

        def step():
            return mk._apply_op(mid, remove)
    new = step()
    with monkeypatch.context() as patch:
        _use_former_helpers(patch)
        old = step()
    _assert_states_equal(new, old)
    if kind == "split-disabled":
        _assert_states_equal(new, mid)


# -- overlapping removers: one slot each, up to the cap ----------------------


def _seq_msgs(spec):
    """Sequenced messages from ``(client, ref_seq, contents)`` rows, seqs
    from 1, nothing below the window expiring (min_seq 0)."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    return [SequencedMessage(seq=seq, client_id=client, client_seq=seq,
                             ref_seq=ref, min_seq=0, type=MessageType.OP,
                             contents=contents)
            for seq, (client, ref, contents) in enumerate(spec, 1)]


def _rm(start, end, kind="remove"):
    return {"kind": kind, "start": start, "end": end}


def _ins(pos, text):
    return {"kind": "insert", "pos": pos, "text": text}


#: remover names out of their arrival order, so the summary's sorted "ro"
#: lists differ from the slot order (and "ann" < "ann!" although the JSON
#: token '"ann"' sorts after '"ann!"')
REMOVERS = ("zed", "ann!", "kim", "ann", "bob", "eve", "max", "lou",
            "pat", "sam")


def _overlap_spec(n_removers):
    """Two inserts, then ``n_removers`` (>= 3) clients removing [2, 6)
    from the same view: a winner, a remover that takes [2, 4) and later
    [4, 6) (its own view) around the others' whole-range removes, so the
    two halves hold the same removers in other slot orders, the last
    whole-range remover obliterating when there are four or more; then
    lagged inserts by a remover and by a bystander, an interval where no
    obliterate is (the two together take the oracle), and a sequential
    tail op."""
    names = REMOVERS[:n_removers]
    spec = [("c0", 0, _ins(0, "abcdefgh")), ("c0", 1, _ins(8, "XYZ")),
            (names[0], 2, _rm(2, 6)), (names[1], 2, _rm(2, 4))]
    for n in names[2:]:
        obliterate = n_removers >= 4 and n == names[-1]
        spec.append((n, 2, _rm(2, 6, "obliterate" if obliterate
                              else "remove")))
    spec.append((names[1], 2, _rm(2, 4)))  # [4, 6) in its own view
    spec.append((names[1], 2, _ins(3, "r")))      # its view hides [2, 6)
    spec.append((names[-1], 2, _ins(3, "q")))     # ...from a later slot
    spec.append(("by", 2, _ins(7, "b")))           # sees [2, 6) present
    if n_removers < 4:
        spec.append(("by", 2, {"kind": "intervalAdd", "label": "default",
                               "id": "iv", "start": 1, "end": 7}))
    spec.append(("c0", len(spec), _rm(0, 1)))
    return spec


def _oracle_digest(msgs, base_summary=None, final_seq=None):
    replica = SharedString("ov")
    if base_summary is not None:
        replica.load(base_summary)
    for msg in msgs:
        replica.process(msg, local=False)
    if final_seq is not None:
        replica.advance(final_seq, 0)
    return replica.summarize().digest()


def _overlap_case(case):
    """(doc, oracle digest) for a parity case: ``cold-<n>`` n removers of
    one segment from no base; ``warm-ro<k>`` a base summary whose record
    carries k overlap removers, then a tail adding one more lagged
    remover; ``past-cap`` more removers than the winner plus
    ``OV_SLOT_CAP``."""
    from fluidframework_tpu.ops.mergetree_kernel import OV_SLOT_CAP

    kind, _, arg = case.partition("-")
    if kind == "past":
        msgs = _seq_msgs([("c0", 0, _ins(0, "abcdefgh"))] + [
            (f"r{k}", 1, _rm(2, 6)) for k in range(OV_SLOT_CAP + 2)])
    else:
        msgs = _seq_msgs(_overlap_spec(int(arg.lstrip("ro")) + (
            2 if kind == "warm" else 0)))
    final = msgs[-1].seq
    if kind != "warm":
        doc = MergeTreeDocInput(doc_id="ov", ops=msgs, final_seq=final,
                                final_msn=0)
        return doc, _oracle_digest(msgs)
    # The base: the two inserts and the winner plus k overlap removers
    # (the first removers' rows, all before the lagged tail).
    n_ro = int(arg[2:])
    base_seq = 2 + 1 + n_ro
    partial = SharedString("ov")
    for msg in msgs[:base_seq]:
        partial.process(msg, local=False)
    base = partial.summarize()
    records = json.loads(base.blob_bytes("body"))
    assert max(len(r.get("ro", [])) for r in records) == n_ro
    tail = msgs[base_seq:]
    doc = MergeTreeDocInput(doc_id="ov", ops=tail, base_records=records,
                            final_seq=final, final_msn=0,
                            base_seq=base_seq, base_msn=0,
                            base_intervals=None)
    expected = _oracle_digest(tail, base, final)
    assert expected == _oracle_digest(msgs, final_seq=final)
    return doc, expected


@pytest.mark.parametrize("extractor", ["native", "python"])
@pytest.mark.parametrize("case", ["cold-3", "cold-4", "cold-5", "warm-ro1",
                                  "warm-ro2", "warm-ro3", "past-cap"])
def test_overlapping_removers_fold_on_the_device(case, extractor,
                                                 monkeypatch):
    """Every concurrent remover of a segment keeps a slot on the device,
    and the summary is byte-identical to the oracle's ("ro" sorted by
    name, records merged by remover set).  Only past the slot cap does
    the document still take the oracle, counted as an overflow."""
    from fluidframework_tpu.ops import mergetree_kernel as mk
    from fluidframework_tpu.ops import native_pack

    if extractor == "python":
        monkeypatch.setattr(native_pack, "extract_bodies",
                            lambda *a, **k: None)
        monkeypatch.setattr(mk, "widen_export_native",
                            lambda *a, **k: None)
    doc, expected = _overlap_case(case)
    _state, _ops, meta = mk.pack_mergetree_batch([doc])
    stats: dict = {}
    [summary] = replay_mergetree_batch([doc], stats=stats)
    assert summary.digest() == expected
    if case == "past-cap":
        assert meta["ov_slots"] == mk.OV_SLOT_CAP
        assert stats["fallback_docs"] == stats["fallback_overflow"] == 1
        return
    assert meta["ov_slots"] >= 2
    assert stats.get("fallback_docs", 0) == 0 and stats["device_docs"] == 1
    assert stats[f"ov_slots_{meta['ov_slots']}"] == 1
