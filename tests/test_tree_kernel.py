"""Device tree-kernel parity: byte-identical summaries vs the oracle.

The convergence oracle pattern (SURVEY.md §4): generate sequenced tree op
logs through the mock runtime's fuzz loop, replay them through both the
CPU oracle and the vmapped device fold, and compare canonical digests.
"""

import random

import pytest

from fluidframework_tpu.dds.tree import ROOT_ID, SharedTree
from fluidframework_tpu.ops.tree_kernel import (
    TreeDocInput,
    oracle_fallback_summary,
    replay_tree_batch,
)
from fluidframework_tpu.testing.mocks import (
    MockContainerRuntimeFactory,
    channel_log,
)


def oracle_summary(doc: TreeDocInput):
    return oracle_fallback_summary(doc)


def run_fuzz_doc(seed, steps=80, n_clients=3, with_moves=True):
    """Drive a fuzzed multi-client session; return the sequenced log and
    final window, the exact catch-up work item."""
    rng = random.Random(seed)
    factory = MockContainerRuntimeFactory()
    trees = []
    for i in range(n_clients):
        rt = factory.create_client(f"client{i}")
        trees.append(rt.attach(SharedTree("tree")))
    for _ in range(steps):
        t = rng.choice(trees)
        roll = rng.random()
        try:
            if roll < 0.4:
                field = rng.choice(["a", "b"])
                parents = [ROOT_ID] + [
                    c for c in t.children(ROOT_ID, "a")
                ]
                parent = rng.choice(parents)
                kids = t.children(parent, field)
                nested = (
                    {"kids": [t.build("leaf", value=rng.randint(0, 9))]}
                    if rng.random() < 0.3 else None
                )
                t.insert(parent, field, rng.randint(0, len(kids)),
                         [t.build("n", value=rng.randint(0, 99),
                                  fields=nested)])
            elif roll < 0.55:
                field = rng.choice(["a", "b"])
                kids = t.children(ROOT_ID, field)
                if kids:
                    t.remove(rng.choice(kids))
            elif roll < 0.7:
                field = rng.choice(["a", "b"])
                kids = t.children(ROOT_ID, field)
                if kids:
                    t.set_value(
                        rng.choice(kids),
                        rng.choice([rng.randint(0, 99), "s", None]),
                    )
            elif roll < 0.85 and with_moves:
                src = rng.choice(["a", "b"])
                kids = t.children(ROOT_ID, src)
                if kids:
                    nid = rng.choice(kids)
                    if rng.random() < 0.3 and len(kids) > 1:
                        dest_parent = rng.choice(
                            [k for k in kids if k != nid]
                        )
                        dest = (dest_parent, "kids")
                    else:
                        dest = (ROOT_ID, rng.choice(["a", "b"]))
                    n_dest = len([
                        k for k in t.children(*dest) if k != nid
                    ])
                    t.move([nid], dest[0], dest[1],
                           rng.randint(0, n_dest))
            else:
                factory.process_some_messages(rng.randint(1, 4))
        except (KeyError, ValueError):
            pass
    factory.process_all_messages()
    log = channel_log(factory, "tree")
    final_seq = factory.sequencer.seq
    final_msn = factory.sequencer.min_seq
    return factory, trees, log, final_seq, final_msn


@pytest.mark.parametrize("seed", [1, 2, 3, 17, 55, 301])
def test_device_matches_oracle_fuzz(seed):
    factory, trees, log, final_seq, final_msn = run_fuzz_doc(seed)
    doc = TreeDocInput(
        doc_id="tree", ops=log, final_seq=final_seq, final_msn=final_msn
    )
    (device,) = replay_tree_batch([doc])
    oracle = oracle_summary(doc)
    assert device.digest() == oracle.digest()
    # And both equal the live replicas' summaries.
    assert device.digest() == trees[0].summarize().digest()


def test_device_batch_many_docs():
    docs = []
    oracles = []
    for seed in range(8):
        _f, _t, log, fs, fm = run_fuzz_doc(seed + 1000, steps=40,
                                           with_moves=(seed % 2 == 0))
        doc = TreeDocInput("tree", ops=log, final_seq=fs, final_msn=fm)
        docs.append(doc)
        oracles.append(oracle_summary(doc))
    results = replay_tree_batch(docs)
    for device, oracle in zip(results, oracles):
        assert device.digest() == oracle.digest()


def test_device_from_base_summary():
    """Catch-up from a mid-stream summary + tail, the north-star shape."""
    factory, trees, log, final_seq, final_msn = run_fuzz_doc(77, steps=60)
    # Split: summary at the midpoint op, tail after.
    mid = len(log) // 2
    base_replica = SharedTree("tree")
    for msg in log[:mid]:
        base_replica.process(msg, local=False)
    base = base_replica.summarize()
    doc = TreeDocInput(
        "tree", ops=log[mid:], base_summary=base,
        final_seq=final_seq, final_msn=final_msn,
    )
    (device,) = replay_tree_batch([doc])
    oracle = oracle_summary(doc)
    assert device.digest() == oracle.digest()
    assert device.digest() == trees[0].summarize().digest()


def test_revive_falls_back_to_oracle():
    factory = MockContainerRuntimeFactory()
    rt = factory.create_client("c0")
    t = rt.attach(SharedTree("tree"))
    (nid,) = t.insert(ROOT_ID, "", 0, [t.build("n", value="v")])
    factory.process_all_messages()
    t.remove(nid)
    factory.process_all_messages()
    _seq, _c, cs = t.edit_manager.trunk[-1]
    t.undo_changeset(cs)  # produces a revive edit
    factory.process_all_messages()
    log = channel_log(factory, "tree")
    doc = TreeDocInput("tree", ops=log,
                       final_seq=factory.sequencer.seq,
                       final_msn=factory.sequencer.min_seq)
    (device,) = replay_tree_batch([doc])
    assert device.digest() == t.summarize().digest()


def test_empty_and_noop_docs():
    doc = TreeDocInput("empty", ops=[])
    (device,) = replay_tree_batch([doc])
    oracle = oracle_summary(doc)
    assert device.digest() == oracle.digest()
    assert replay_tree_batch([]) == []


def test_deterministic_across_runs():
    """Same batch twice → bitwise-equal results (SURVEY.md §5 race
    detection equivalent: determinism checks)."""
    _f, _t, log, fs, fm = run_fuzz_doc(5, steps=50)
    doc = TreeDocInput("tree", ops=log, final_seq=fs, final_msn=fm)
    d1 = replay_tree_batch([doc])[0].digest()
    d2 = replay_tree_batch([doc])[0].digest()
    assert d1 == d2


def test_limbo_rescue_survives_purge_summary_and_device():
    """A node moved into a subtree whose tombstone then EXPIRES must stay
    rescuable by id: the purge detaches it to limbo instead of deleting it,
    summaries carry a "limbo" section so reloads converge, the device fold
    applies the rescue move exactly, and a limbo-carrying base summary
    routes the warm fold to the oracle (fuzz-found divergence class)."""
    import json

    from fluidframework_tpu.dds.tree import SharedTree
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def op(seq, min_seq, edits):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=min_seq, type=MessageType.OP, contents={"edits": edits},
        )

    def ins(nid, parent, field, val):
        return {"kind": "insert", "parent": parent, "field": field,
                "anchor": None,
                "content": [{"id": nid, "type": "n", "value": val}]}

    log = [
        op(1, 0, [ins("A", "", "a", 1)]),
        op(2, 0, [ins("B", "", "a", 2)]),
        op(3, 0, [{"kind": "move", "ids": ["B"], "parent": "A",
                   "field": "kids", "anchor": None,
                   "prev": [["B", "", "a", None]]}]),
        op(4, 0, [{"kind": "remove", "ids": ["A"]}]),
        op(5, 4, [ins("C", "", "a", 3)]),  # A expires -> B detached (limbo)
        op(6, 4, [ins("D", "", "a", 4)]),
        op(7, 4, [{"kind": "move", "ids": ["B"], "parent": "", "field": "a",
                   "anchor": None,
                   "prev": [["B", "A", "kids", None]]}]),  # the rescue
    ]

    live = SharedTree("t")
    for m in log:
        live.process(m, local=False)
    final = live.summarize()
    header = json.loads(final.blob_bytes("header"))
    assert any(
        n["id"] == "B" for n in header["fields"]["a"]
    ), "rescued node must be visible again"

    # mid-stream summary carries the limbo section; reload + tail converges
    mid = SharedTree("t")
    for m in log[:6]:
        mid.process(m, local=False)
    mid_summary = mid.summarize()
    mid_obj = json.loads(mid_summary.blob_bytes("header"))
    assert [n["id"] for n in mid_obj["limbo"]] == ["B"]
    reloaded = SharedTree("t")
    reloaded.load(mid_summary)
    for m in log[6:]:
        reloaded.process(m, local=False)
    assert reloaded.summarize().digest() == final.digest()

    # device: cold fold exact; warm fold from the limbo base falls back
    [dev] = replay_tree_batch(
        [TreeDocInput("t", ops=log, final_seq=7, final_msn=4)]
    )
    assert dev.digest() == final.digest()
    stats = {}
    [warm] = replay_tree_batch(
        [TreeDocInput("t", ops=log[6:], base_summary=mid_summary,
                      final_seq=7, final_msn=4)],
        stats=stats,
    )
    assert warm.digest() == final.digest()
    # Per-reason fallback accounting (ISSUE 14 satellite): the opaque
    # total survives, joined by WHY the doc left the device path.
    assert stats == {"fallback_docs": 1, "fallback_base_limbo": 1}


def test_deep_tree_fuzz_device_parity():
    """Deep tree fuzz (120 steps, 4 clients — the purge-race shape that
    diverged before the limbo hardening; 400-seed sweeps ran clean
    offline) with device parity and fallback accounting."""
    for seed in (40007, 40045, 40060, 40100, 40200):
        factory, trees, log, fs, fm = run_fuzz_doc(
            seed, steps=120, n_clients=4
        )
        assert len({t.summarize().digest() for t in trees}) == 1
        doc = TreeDocInput("tree", ops=log, final_seq=fs, final_msn=fm)
        stats = {}
        [device] = replay_tree_batch([doc], stats=stats)
        assert device.digest() == trees[0].summarize().digest(), seed


def test_summarize_wider_min_seq_emits_limbo():
    """summarize(min_seq) beyond the channel's advanced window must surface
    kept descendants of newly-expiring tombstones as limbo — identical to a
    replica whose window actually advanced (review-found: the container
    summarizes channels with ITS min_seq, which can exceed the channel's)."""
    import json

    from fluidframework_tpu.dds.tree import ROOT_ID, SharedTree
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def op(seq, min_seq, edits):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=min_seq, type=MessageType.OP, contents={"edits": edits},
        )

    log = [
        op(1, 0, [{"kind": "insert", "parent": "", "field": "a",
                   "anchor": None,
                   "content": [{"id": "A", "type": "n", "value": 1}]}]),
        op(2, 0, [{"kind": "insert", "parent": "", "field": "a",
                   "anchor": None,
                   "content": [{"id": "B", "type": "n", "value": 2}]}]),
        op(3, 0, [{"kind": "move", "ids": ["B"], "parent": "A",
                   "field": "kids", "anchor": None,
                   "prev": [["B", "", "a", None]]}]),
        op(4, 0, [{"kind": "remove", "ids": ["A"]}]),
    ]
    idle = SharedTree("t")
    for m in log:
        idle.process(m, local=False)  # window never advances (min_seq 0)
    wide = idle.summarize(min_seq=4)  # container-wide MSN exceeds channel's
    obj = json.loads(wide.blob_bytes("header"))
    assert [n["id"] for n in obj.get("limbo", [])] == ["B"]

    advanced = SharedTree("t")
    for m in log[:3]:
        advanced.process(m, local=False)
    advanced.process(
        SequencedMessage(seq=4, client_id="c0", client_seq=4, ref_seq=3,
                         min_seq=0, type=MessageType.OP,
                         contents={"edits": [{"kind": "remove",
                                              "ids": ["A"]}]}),
        local=False,
    )
    advanced.advance(5, 4)  # the purge actually runs
    assert advanced.summarize(min_seq=4).digest() == wide.digest()


# -- hardware-rule regression net: the tree family's buckets satisfy the
# Mosaic block rule, and pad rows stay inert on non-divisible buckets. --


def _fuzz_doc_input(seed, steps):
    _factory, _trees, log, final_seq, final_msn = run_fuzz_doc(
        seed, steps=steps)
    return TreeDocInput(doc_id="tree", ops=log, final_seq=final_seq,
                        final_msn=final_msn)


def test_tree_buckets_satisfy_mosaic_block_rule():
    """Every device-plane bucket the tree packer
    derives (N and T from tree_buckets, C inside pack_tree_batch) is a
    power-of-two ladder value at or above its floor — hence divisible
    by the 8-row sublane unit — and covers the per-doc used-row counts
    it was sized from (pads extend, never truncate)."""
    from fluidframework_tpu.ops.tree_kernel import (
        pack_tree_batch,
        tree_buckets,
    )

    docs = [_fuzz_doc_input(1400 + i, steps)
            for i, steps in enumerate((4, 25, 60, 110))]
    for k in range(1, len(docs) + 1):
        sub = docs[:k]
        N, T = tree_buckets(sub)
        state, edits, meta = pack_tree_batch(sub)
        C = state.head.shape[1]
        for bucket, floor in ((N, 16), (T, 16), (C, 8)):
            assert bucket >= floor and bucket % 8 == 0
            # Power-of-two ladder: a finite, stable set of jit shapes.
            assert bucket & (bucket - 1) == 0, bucket
        # The allocated planes use exactly the derived buckets ...
        assert state.next.shape == (k, N)
        assert edits.kind.shape == (k, T)
        # ... and every used-row count fits inside its bucket.
        assert int(meta["n_nodes"].max()) <= N
        assert int(meta["n_cont"].max()) <= C
        assert int(meta["t_rows"].max()) <= T


def test_tree_parity_on_nondivisible_buckets():
    """Full digest parity on a batch whose natural
    buckets genuinely violate the (8, 128) lane rule — a doc count that
    is not a multiple of 8 and node/edit buckets that are not multiples
    of 128 — so pad rows must be provably inert, not accidentally
    aligned away."""
    from fluidframework_tpu.ops.tree_kernel import tree_buckets

    docs = [_fuzz_doc_input(1500 + i, steps=18) for i in range(11)]
    N, T = tree_buckets(docs)
    assert len(docs) % 8 != 0, "D accidentally 8-aligned"
    assert N % 128 != 0, f"N={N} accidentally 128-aligned"
    assert T % 128 != 0, f"T={T} accidentally 128-aligned"
    summaries = replay_tree_batch(docs)
    for doc, device in zip(docs, summaries):
        assert device.digest() == oracle_summary(doc).digest()
