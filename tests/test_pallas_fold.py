"""Exact parity: the Pallas VMEM-resident fold vs the canonical scan.

The Pallas kernel is a Mosaic-conservative restatement of the scan step
(rolls instead of gathers, reduction searches, ladder prefix sums); these
tests pin it to ``replay_vmapped`` ARRAY-FOR-ARRAY on the bench workload,
the dryrun's hard-semantics docs (deep-lag obliterate, overlap removers,
annotate races, warm obliterate base), and fuzz logs.  Interpret mode —
runs on any backend, so CI covers the port's semantics; Mosaic compilation
is exercised on real TPU behind FF_PALLAS_FOLD."""

import jax
import numpy as np
import pytest

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    pack_mergetree_batch,
    replay_vmapped,
    summaries_from_export,
    _export_state,
)
from fluidframework_tpu.ops.pallas_fold import replay_vmapped_pallas


@pytest.fixture(autouse=True)
def _packed_for_pallas(monkeypatch):
    """Pack as when the Pallas fold serves: it keeps one overlap slot, so
    under its mode the pack gives no chunk more (a third remover
    overflows to the oracle in both folds)."""
    monkeypatch.setenv("FF_PALLAS_FOLD", "interpret")


def _planes(state):
    """(name, plane) for every plane of a state, a tuple field's (the
    overlap slots past the first) one by one."""
    for field in state._fields:
        v = getattr(state, field)
        if isinstance(v, tuple):
            yield from ((f"{field}[{i}]", x) for i, x in enumerate(v))
        else:
            yield field, v


def _assert_states_equal(a, b, n_docs):
    pa, pb = list(_planes(a)), list(_planes(b))
    assert [f for f, _x in pa] == [f for f, _x in pb]
    for (field, av), (_f, bv) in zip(pa, pb):
        av, bv = np.asarray(av), np.asarray(bv)
        assert av.shape == bv.shape, field
        if field in ("n", "overflow"):
            np.testing.assert_array_equal(av, bv, err_msg=field)
            continue
        # Only slots [0, n) are meaningful; the scan and the kernel may
        # differ in dead-slot garbage above n after shifts.
        for d in range(n_docs):
            nd = int(np.asarray(a.n)[d])
            np.testing.assert_array_equal(
                av[d, :nd], bv[d, :nd], err_msg=f"{field} doc {d}"
            )


def _parity(docs):
    state, ops, meta = pack_mergetree_batch(docs)
    final_scan = jax.jit(replay_vmapped)(state, ops)
    final_pallas = replay_vmapped_pallas(state, ops, interpret=True)
    _assert_states_equal(final_scan, final_pallas, len(docs))
    return final_pallas, meta


def test_pallas_fold_matches_scan_on_bench_workload():
    docs = [bench.synth_doc(i, 48) for i in range(24)]
    final, meta = _parity(docs)
    # and byte-identical summaries through the export + extraction path
    # (same flags replay_export derives from the packed meta)
    import jax.numpy as jnp

    from fluidframework_tpu.ops.mergetree_kernel import (
        _export_flags,
        export_to_numpy,
    )

    i16, ob_rows, ov_rows, i8, props_rows = _export_flags(meta)
    doc_base = jnp.asarray(meta["doc_base"]) if i16 else \
        jnp.zeros((len(docs),), jnp.int32)
    export = export_to_numpy(
        _export_state(final, doc_base, i16, ob_rows, ov_rows, i8,
                      props_rows=props_rows))
    summaries = summaries_from_export(meta, export)
    for doc, summary in zip(docs[:6], summaries[:6]):
        assert summary.digest() == \
            bench.oracle_replay(doc).summarize().digest(), doc.doc_id


def test_pallas_fold_matches_scan_on_hard_semantics():
    """Deep-lag obliterate arrival kills, overlap removers, annotate
    races, lagged fuzz logs, warm obliterate base — the riskiest step
    logic — through the Pallas port."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        pathlib.Path(__file__).parent.parent / "__graft_entry__.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _parity(mod._hard_mergetree_docs())


def test_padded_block_dims_satisfy_mosaic_rule():
    """The round-5 recorded Mosaic failure was a block whose dims violate
    the (8, 128) divisibility rule (``block shape (1, 96)`` vs array
    ``(1024, 96)``).  Every BlockSpec the kernel builds is (DOC_BLOCK,
    lanes) with lanes from _padded_dims — pin the invariant directly."""
    from fluidframework_tpu.ops.pallas_fold import (
        DOC_BLOCK,
        LANE,
        _padded_dims,
    )

    assert DOC_BLOCK % 8 == 0 and LANE % 128 == 0
    for D, S, T in [(1, 1, 1), (24, 96, 48), (11, 48, 24),
                    (1024, 96, 96), (8, 128, 128), (1000, 192, 130)]:
        Dp, Sp, Tp = _padded_dims(D, S, T)
        assert Dp % DOC_BLOCK == 0 and Dp >= D
        assert Sp % LANE == 0 and Sp >= S, (S, Sp)
        assert Tp % LANE == 0 and Tp >= T, (T, Tp)


def test_pallas_fold_parity_on_nondivisible_buckets():
    """Interpret-mode parity on exactly the shapes the recorded error
    names: lane dims (S, T) that are NOT multiples of 128 and a doc
    count that is not a multiple of 8 — the pad lanes/rows must be
    masked to inertness."""
    docs = [bench.synth_doc(i, 24) for i in range(11)]
    # The natural buckets must genuinely violate the rule on EVERY
    # padded axis (or the test would prove nothing): D not a multiple
    # of 8, S and T not multiples of 128.
    state, ops, _meta = pack_mergetree_batch(docs)
    D, S = state.tstart.shape
    T = ops.kind.shape[1]
    assert D % 8 != 0, f"D={D} accidentally 8-aligned"
    assert S % 128 != 0, f"S={S} accidentally 128-aligned"
    assert T % 128 != 0, f"T={T} accidentally 128-aligned"
    _parity(docs)


@pytest.mark.parametrize("seed", range(3))
def test_pallas_fold_matches_scan_on_fuzz_logs(seed):
    from fluidframework_tpu.ops.mergetree_kernel import MergeTreeDocInput
    from fluidframework_tpu.testing.fuzz import StringFuzzSpec, run_fuzz
    from fluidframework_tpu.testing.mocks import channel_log

    docs = []
    for i, spec_ in enumerate((StringFuzzSpec(annotate=True),
                               StringFuzzSpec(obliterate=True))):
        _r, factory = run_fuzz(spec_, seed=1300 + 10 * seed + i,
                               n_clients=3, rounds=8, sync_every=2)
        docs.append(MergeTreeDocInput(
            doc_id=f"fz{i}", ops=channel_log(factory, "fuzz"),
            final_seq=factory.sequencer.seq,
            final_msn=factory.sequencer.min_seq,
        ))
    _parity(docs)
