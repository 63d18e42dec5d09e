"""Upload-side narrow transfer encoding (h2d leg of the link-bound
pipeline): ``narrow_ops_for_upload`` + in-graph ``_widen_ops`` must be an
exact round trip — the fold and export are byte-identical whether the op
stream rides the wire as int32 or as the narrowed int16/int8 layout
(BASELINE.md round-5: with the device fold at ~2 ms, e2e is host+link,
so halving the op-stream upload is a first-order lever)."""

import numpy as np
import pytest

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    MTOps,
    _UPLOAD_NARROW_DTYPES,
    export_to_numpy,
    narrow_ops_for_upload,
    pack_mergetree_batch,
    replay_export,
)
from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
from fluidframework_tpu.testing.mocks import channel_log


def _export_bytes(state, ops, meta, S):
    ex = export_to_numpy(replay_export(state, ops, meta, S=S))
    leaves = ex if isinstance(ex, tuple) else (ex,)
    return tuple(leaf.tobytes() for leaf in leaves)


def _narrow_vs_wide(docs, monkeypatch, warm=False):
    """Pin narrow-vs-wide export byte identity through the dispatch
    path — cold by default, or the warm (base-state) path production
    ``replay_mergetree_batch`` takes for catch-up chunks."""
    state, ops, meta = pack_mergetree_batch(docs)
    S = state.tstart.shape[1]
    assert meta["i16_ok"]
    narrow = narrow_ops_for_upload(ops, meta)
    assert narrow.seq.dtype == np.int16 and narrow.kind.dtype == np.int8
    saved = sum(np.asarray(x).nbytes for x in ops) - \
        sum(np.asarray(x).nbytes for x in narrow)
    assert saved > 0
    st = state if warm else None
    # The dispatch path narrows internally; pin both encodings' bytes.
    with_narrow = _export_bytes(st, ops, meta, S)
    monkeypatch.setenv("FF_UPLOAD_NARROW", "0")
    wide = _export_bytes(st, ops, meta, S)
    assert with_narrow == wide


def test_narrow_roundtrip_on_bench_workload(monkeypatch):
    _narrow_vs_wide([bench.synth_doc(i, 48) for i in range(24)], monkeypatch)


def test_narrow_roundtrip_on_fuzz_logs(monkeypatch):
    docs = []
    for seed in (210, 211, 212):
        _r, factory = run_fuzz(StringFuzzSpec(annotate=True), seed=seed,
                               n_clients=3, rounds=8, sync_every=2)
        docs.append(MergeTreeDocInput(
            doc_id=f"n{seed}", ops=channel_log(factory, "fuzz"),
            final_seq=factory.sequencer.seq,
            final_msn=factory.sequencer.min_seq,
        ))
    _narrow_vs_wide(docs, monkeypatch)


def _warm_doc(seed, rounds=12):
    """A snapshot+tail MergeTreeDocInput: fuzz a session, summarize at
    the midpoint, return the base records + remaining tail — the
    flagship warm catch-up shape."""
    import json as _json

    from fluidframework_tpu.dds import SharedString

    _r, factory = run_fuzz(StringFuzzSpec(), seed=seed, n_clients=3,
                           rounds=rounds)
    full_ops = channel_log(factory, "fuzz")
    mid_seq = full_ops[len(full_ops) // 2].seq
    partial = SharedString("fuzz")
    for msg in full_ops:
        if msg.seq <= mid_seq:
            partial.process(msg, local=False)
    base_records = _json.loads(partial.summarize().blob_bytes("body"))
    return MergeTreeDocInput(
        doc_id=f"warm{seed}",
        ops=[m for m in full_ops if m.seq > mid_seq],
        base_records=base_records,
        final_seq=factory.sequencer.seq,
        final_msn=factory.sequencer.min_seq,
    )


def test_narrow_roundtrip_on_warm_base_state_path(monkeypatch):
    """The warm (_export_warm_fn) path: catch-up chunks with base
    summaries carry state-relative arena offsets alongside the rebased
    op tstart — the un-rebase must interact correctly with both."""
    _narrow_vs_wide([_warm_doc(s) for s in (220, 221)], monkeypatch,
                    warm=True)


def test_narrow_state_roundtrip_exact():
    """narrow_state_for_upload → _widen_state reproduces the packed base
    state array-for-array (sentinel remap + live-slot tstart rebase)."""
    import jax.numpy as jnp

    from fluidframework_tpu.ops.mergetree_kernel import (
        _widen_state,
        narrow_state_for_upload,
    )

    state, _ops, meta = pack_mergetree_batch([_warm_doc(230)])
    narrow = narrow_state_for_upload(state, meta)
    assert narrow.ins_seq.dtype == np.int16, "warm chunk should narrow"
    widened = _widen_state(narrow, jnp.asarray(meta["doc_base"]))
    for f in state._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(widened, f)), np.asarray(getattr(state, f)),
            err_msg=f)


def test_narrow_state_sentinel_collision_falls_back():
    """A genuine seq of 32767 (the remapped sentinel's value) in a
    sentinel plane must force the wide upload — narrowing it would widen
    back as NOT_REMOVED and resurrect a removed segment."""
    from fluidframework_tpu.ops.mergetree_kernel import (
        narrow_state_for_upload,
    )

    state, _ops, meta = pack_mergetree_batch([_warm_doc(231)])
    assert meta["i16_ok"]
    bad_rem = np.array(state.rem_seq)
    d = 0
    live = int(state.n[d])
    assert live > 0
    bad_rem[d, 0] = 32767  # == I16_NOT_REMOVED, but a "real" value here
    bad = state._replace(rem_seq=bad_rem)
    out = narrow_state_for_upload(bad, meta)
    assert out.rem_seq.dtype == np.int32 and out.ins_seq is bad.ins_seq


def test_widen_refuses_unknown_dtype():
    """A non-int32, non-narrow stream must be refused loudly — silently
    un-rebasing a never-rebased stream corrupts arena offsets."""
    import jax.numpy as jnp

    from fluidframework_tpu.ops.mergetree_kernel import _widen_ops

    docs = [bench.synth_doc(i, 16) for i in range(2)]
    _state, ops, _meta = pack_mergetree_batch(docs)
    # int8 seq: a dtype the narrower never emits for seq (x64 mode is
    # off, so int64 would silently truncate back to int32 here).
    bad = MTOps(*(jnp.asarray(np.asarray(x), jnp.int8)
                  if f == "seq" else jnp.asarray(np.asarray(x))
                  for f, x in zip(MTOps._fields, ops)))
    with pytest.raises(TypeError, match="seq dtype"):
        _widen_ops(bad, jnp.zeros((2,), jnp.int32))


def test_narrow_skips_non_qualifying_and_device_streams():
    docs = [bench.synth_doc(i, 32) for i in range(4)]
    state, ops, meta = pack_mergetree_batch(docs)
    # not i16_ok → identity (same objects, no copies)
    wide = narrow_ops_for_upload(ops, dict(meta, i16_ok=False))
    assert wide.seq is ops.seq
    # already-narrow stream → identity
    narrow = narrow_ops_for_upload(ops, meta)
    again = narrow_ops_for_upload(narrow, meta)
    assert again.seq is narrow.seq


def test_narrow_bounds_recheck_falls_back_to_wide():
    """A stream violating a narrow dtype's range (despite i16_ok being
    claimed) must pass through wide, never truncate."""
    docs = [bench.synth_doc(i, 32) for i in range(4)]
    _state, ops, meta = pack_mergetree_batch(docs)
    bad_client = np.array(ops.client)
    bad_client[0, 0] = 1000  # exceeds the int8 client row
    bad = ops._replace(client=bad_client)
    out = narrow_ops_for_upload(bad, meta)
    assert out.client.dtype == np.int32 and out.seq is bad.seq


def test_narrow_dtype_table_covers_every_op_field():
    assert set(_UPLOAD_NARROW_DTYPES) == set(MTOps._fields)


def test_native_widen_matches_python_widen_all_layouts():
    """oppack_widen vs widen_export: byte-identical canonical buffers on
    every transfer layout the export can emit (i16, i8 pairs, ob/ov row
    elisions, props elision, warm doc_base rebase)."""
    from fluidframework_tpu.ops.mergetree_kernel import (
        _export_flags,
        widen_export,
        widen_export_native,
    )
    from fluidframework_tpu.ops.native_pack import load_library

    if load_library() is None:
        pytest.skip("liboppack unavailable")

    cases = {
        # props-free sequential bench docs: i8 pairs + ob/ov/props elision
        "i8-elided": [bench.synth_doc(i, 48) for i in range(16)],
        # annotate-carrying docs: props rows present
        "props": [bench.synth_doc(3 * i + 1, 48) for i in range(12)],
        # warm snapshot+tail docs: doc_base rebase over base states
        "warm": [_warm_doc(240 + i) for i in range(3)],
    }
    exercised = set()
    for name, docs in cases.items():
        state, ops, meta = pack_mergetree_batch(docs)
        S = state.tstart.shape[1]
        assert meta["i16_ok"], name
        st = state if name == "warm" else None
        ex = export_to_numpy(replay_export(st, ops, meta, S=S))
        _i16, ob_f, ov_f, i8_f, props_f = _export_flags(meta)
        exercised.add((ob_f, ov_f, i8_f, props_f))
        native = widen_export_native(ex, meta.get("doc_base"), ob_f, ov_f,
                                     i8_f, meta.get("props_K"), props_f)
        assert native is not None, name
        py = widen_export(ex, meta.get("doc_base"), ob_rows=ob_f,
                          ov_slots=ov_f, i8=i8_f,
                          n_props=meta.get("props_K"), props_rows=props_f)
        np.testing.assert_array_equal(native, py, err_msg=name)
        assert native.dtype == py.dtype == np.int32
    assert len(exercised) >= 2, f"layout variety too thin: {exercised}"
    # int32 full-layout buffers must pass through to the numpy path
    state, ops, meta = pack_mergetree_batch(cases["props"])
    meta32 = dict(meta, i16_ok=False)
    ex32 = export_to_numpy(
        replay_export(None, ops, meta32, S=state.tstart.shape[1]))
    assert widen_export_native(ex32, None, True, True, False,
                               meta.get("props_K"), True) is None


def test_native_widen_rejects_malformed_desc_table():
    """oppack_widen must bounds-check the DESC table, not just ``n``
    (advisor, round 5): a ROW16 source index past R_src, a PAIR8 pair
    index past R_src, an unknown mode, or a MISC row without the misc
    output all return -1 instead of reading out of bounds."""
    import ctypes

    from fluidframework_tpu.ops.native_pack import load_library

    lib = load_library()
    if lib is None:
        pytest.skip("liboppack unavailable")
    D, S, R_src = 1, 4, 2
    src = np.zeros((D, R_src, S), np.int16)  # n (last row, col 0) = 0
    dst = np.zeros((D, 2, S), np.int32)

    def widen(desc_rows, misc=None):
        desc = np.asarray(desc_rows, np.int32).reshape(-1)
        misc_ptr = misc.ctypes.data if misc is not None else None
        misc_cols = misc.shape[1] if misc is not None else 0
        return lib.oppack_widen(
            src, D, S, R_src, len(desc_rows), misc_ptr, misc_cols, desc,
            None, 32767, 2147483647, dst,
        )

    ok = [(1, 0, 0, 0), (1, R_src - 1, 0, 0)]
    assert widen(ok) == 0  # control: a valid table still widens
    # ROW16 source index out of range (both ends)
    assert widen([(1, R_src, 0, 0), (1, 0, 0, 0)]) == -1
    assert widen([(1, -1, 0, 0), (1, 0, 0, 0)]) == -1
    # PAIR8 pair index maps past the source rows (arg/2 >= R_src)
    assert widen([(2, 2 * R_src, 0, 0), (1, 0, 0, 0)]) == -1
    assert widen([(2, -1, 0, 0), (1, 0, 0, 0)]) == -1
    # MISC row requires a non-null misc pointer
    assert widen([(3, 0, 0, 0), (1, 0, 0, 0)]) == -1
    misc = np.zeros((D, 2), np.int16)
    assert widen([(3, 0, 0, 0), (1, 0, 0, 0)], misc=misc) == 0
    # unknown mode
    assert widen([(4, 0, 0, 0), (1, 0, 0, 0)]) == -1
    assert widen([(-1, 0, 0, 0), (1, 0, 0, 0)]) == -1
