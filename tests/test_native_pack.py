"""Native (C++) op packing: binary codec round-trip, bit-identical arrays
vs the pure-Python pack path, byte-identical summaries end-to-end."""

import random

import numpy as np
import pytest

from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.ops.interning import Interner
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    pack_mergetree_batch,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.native_pack import (
    _count_py,
    count_streams,
    decode_string_ops,
    encode_string_ops,
    load_library,
    stream_offset,
    stream_seq_at,
)
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage


def synth_ops(seed, n_ops, unicode_text=False):
    rng = random.Random(seed)
    alphabet = "abçdé日本語 zz" if unicode_text else "abcdefgh "
    ops, length = [], 0
    for i in range(n_ops):
        seq = i + 1
        client = f"c{i % 3}"
        if length < 4 or rng.random() < 0.7:
            text = "".join(rng.choice(alphabet)
                           for _ in range(rng.randint(1, 6)))
            contents = {"kind": "insert", "pos": rng.randint(0, length),
                        "text": text}
            length += len(text)
        else:
            start = rng.randint(0, length - 2)
            end = min(length, start + rng.randint(1, 5))
            contents = {"kind": "remove", "start": start, "end": end}
            length -= end - start
        ops.append(SequencedMessage(
            seq=seq, client_id=client, client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents=contents,
        ))
    return ops


def test_native_library_builds():
    # g++ is in the image; the library must actually compile and load.
    assert load_library() is not None


def test_codec_roundtrip_including_unicode():
    ops = synth_ops(7, 40, unicode_text=True)
    clients = Interner()
    blob = encode_string_ops(ops, clients)
    n, text_bytes, text_chars, first_seq, last_seq = \
        count_streams([blob])[0].tolist()
    assert n == 40
    assert text_bytes >= text_chars  # multibyte chars present
    assert (first_seq, last_seq) == (1, 40)
    decoded = decode_string_ops(blob, list(clients.values))
    for orig, back in zip(ops, decoded):
        assert orig.seq == back.seq
        assert orig.client_id == back.client_id
        assert orig.contents == back.contents


@pytest.mark.parametrize("n_ops", [0, 1, 37])
def test_count_stream_native_matches_python(n_ops):
    """The C++ sizing pass and ``_count_py`` agree on every field, the
    window's first and last seq included (0, 0 for an empty stream)."""
    ops = synth_ops(3, n_ops, unicode_text=True)
    for i, msg in enumerate(ops):
        msg.seq = 5 + 2 * i  # gaps: other channels' ops between
    blob = encode_string_ops(ops, Interner())
    other = encode_string_ops(synth_ops(4, 9), Interner())
    native = count_streams([other, blob, b""]).tolist()
    assert native == [list(_count_py(b)) for b in (other, blob, b"")]
    got = native[1]
    assert got[0] == n_ops
    assert got[3:] == ([ops[0].seq, ops[-1].seq] if ops else [0, 0])
    assert native[2] == [0] * 5
    for i, msg in enumerate(ops):
        assert stream_seq_at(blob, i) == msg.seq
    assert stream_offset(blob, n_ops) == len(blob)


@pytest.mark.parametrize("unicode_text", [False, True])
def test_native_pack_bit_identical_to_python(unicode_text):
    docs_py, docs_bin = [], []
    for d in range(6):
        ops = synth_ops(d, 30 + d, unicode_text=unicode_text)
        clients = Interner()
        blob = encode_string_ops(ops, clients)
        docs_py.append(MergeTreeDocInput(
            doc_id=f"doc{d}", ops=ops, final_seq=len(ops), final_msn=0))
        docs_bin.append(MergeTreeDocInput(
            doc_id=f"doc{d}", ops=[], binary_ops=blob,
            binary_clients=list(clients.values),
            final_seq=len(ops), final_msn=0))

    st_py, op_py, meta_py = pack_mergetree_batch(docs_py)
    st_bin, op_bin, meta_bin = pack_mergetree_batch(docs_bin)
    for name in op_py._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(op_py, name)),
            np.asarray(getattr(op_bin, name)), err_msg=name)
    for name in st_py._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_py, name)),
            np.asarray(getattr(st_bin, name)), err_msg=name)
    assert meta_py["arena"].finalize() == meta_bin["arena"].finalize()


def test_native_end_to_end_summary_byte_identity():
    docs = []
    oracles = []
    for d in range(4):
        ops = synth_ops(100 + d, 50)
        clients = Interner()
        blob = encode_string_ops(ops, clients)
        docs.append(MergeTreeDocInput(
            doc_id=f"doc{d}", ops=[], binary_ops=blob,
            binary_clients=list(clients.values),
            final_seq=len(ops), final_msn=0))
        replica = SharedString(f"doc{d}")
        for msg in ops:
            replica.process(msg, local=False)
        oracles.append(replica.summarize())

    summaries = replay_mergetree_batch(docs)
    for dev, oracle in zip(summaries, oracles):
        assert dev.digest() == oracle.digest()


def test_mixed_python_and_binary_docs_in_one_batch():
    ops_a = synth_ops(1, 25)
    clients = Interner()
    blob = encode_string_ops(ops_a, clients)
    doc_bin = MergeTreeDocInput(
        doc_id="bin", ops=[], binary_ops=blob,
        binary_clients=list(clients.values),
        final_seq=len(ops_a), final_msn=0)
    ops_b = synth_ops(2, 25)
    doc_py = MergeTreeDocInput(
        doc_id="py", ops=ops_b, final_seq=len(ops_b), final_msn=0)

    summaries = replay_mergetree_batch([doc_bin, doc_py])
    for doc_id, ops, summary in [("bin", ops_a, summaries[0]),
                                 ("py", ops_b, summaries[1])]:
        replica = SharedString(doc_id)
        for msg in ops:
            replica.process(msg, local=False)
        assert summary.digest() == replica.summarize().digest()


def test_native_extract_bodies_byte_identity_hostile_text():
    """C++ oppack_extract vs the per-slot Python extraction on streams with
    JSON-escape-needing text, unicode, props, annotates, and window expiry:
    the summary bytes must be identical (and match the oracle)."""
    import random as _random

    from fluidframework_tpu.dds.sequence import SharedString
    from fluidframework_tpu.ops.interning import Interner
    from fluidframework_tpu.ops.mergetree_kernel import (
        MergeTreeDocInput,
        replay_mergetree_batch,
    )
    from fluidframework_tpu.ops.native_pack import (
        encode_string_ops,
        load_library,
    )
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    assert load_library() is not None, "native library must build in CI"

    alphabet = ['"', "\\", "\n", "\t", "\x07", "é", "文", "𝄞", "a", "b ", "c"]
    docs = []
    for di in range(6):
        rng = _random.Random(1000 + di)
        ops, length = [], 0
        for i in range(40):
            seq = i + 1
            client = f"c{i % 3}"
            r = rng.random()
            if r < 0.6 or length < 4:
                text = "".join(
                    rng.choice(alphabet) for _ in range(rng.randint(1, 5))
                )
                contents = {"kind": "insert",
                            "pos": rng.randint(0, length), "text": text}
                length += len(text)
            elif r < 0.85:
                start = rng.randint(0, length - 2)
                end = min(length, start + rng.randint(1, 5))
                contents = {"kind": "remove", "start": start, "end": end}
                length -= end - start
            else:
                start = rng.randint(0, length - 2)
                end = min(length, start + rng.randint(1, 4))
                contents = {"kind": "annotate", "start": start, "end": end,
                            "props": {"style": rng.choice(
                                ["bold", "ital\"ic", None, 7])}}
            ops.append(SequencedMessage(
                seq=seq, client_id=client, client_seq=seq, ref_seq=seq - 1,
                min_seq=0, type=MessageType.OP, contents=contents,
            ))
        final_msn = 12 if di % 2 else 0   # exercise tombstone expiry
        if di < 3:
            # message-list path
            docs.append(MergeTreeDocInput(
                doc_id=f"h{di}", ops=ops, final_seq=40, final_msn=final_msn,
            ))
        else:
            # binary path WITH props (encoder-local intern tables)
            clients, keys, vals = Interner(), Interner(), Interner()
            blob = encode_string_ops(ops, clients, keys, vals)
            docs.append(MergeTreeDocInput(
                doc_id=f"h{di}", ops=[], binary_ops=blob,
                binary_clients=list(clients.values),
                binary_prop_keys=list(keys.values),
                binary_values=list(vals.values),
                final_seq=40, final_msn=final_msn,
            ))
    device = replay_mergetree_batch(docs)
    for doc, dev in zip(docs, device):
        replica = SharedString(doc.doc_id)
        ops = doc.ops
        if doc.binary_ops is not None:
            from fluidframework_tpu.ops.native_pack import decode_string_ops
            ops = decode_string_ops(
                doc.binary_ops, list(doc.binary_clients),
                prop_keys=doc.binary_prop_keys, values=doc.binary_values)
        for msg in ops:
            replica.process(msg, local=False)
        replica.advance(doc.final_seq, doc.final_msn)
        oracle = replica.summarize()
        assert dev.digest() == oracle.digest(), doc.doc_id


@pytest.mark.parametrize("layout", ["streams", "runs-between-messages"])
def test_native_run_pack_matches_python_pack(layout, monkeypatch):
    """One native call per run of stream docs fills the rows, text arena
    and arena bases the Python stream pack fills, whether the chunk is one
    run or runs between message docs."""
    import fluidframework_tpu.ops.native_pack as npk

    if load_library() is None:
        pytest.skip("liboppack unavailable")

    def build():
        docs = []
        for d in range(7):
            ops = synth_ops(300 + d, 40 + d, unicode_text=(d % 2 == 0))
            if layout != "streams" and d in (2, 4):
                docs.append(MergeTreeDocInput(
                    doc_id=f"doc{d}", ops=ops, final_seq=len(ops),
                    final_msn=0))
                continue
            if d % 3 == 1:
                ops[5].contents = {"kind": "annotate", "start": 0, "end": 2,
                                   "props": {f"k{d}": "v", "z": None}}
            clients, keys, values = Interner(), Interner(), Interner()
            blob = encode_string_ops(ops, clients, keys, values)
            docs.append(MergeTreeDocInput(
                doc_id=f"doc{d}", ops=[], binary_ops=blob,
                binary_clients=list(clients.values),
                binary_prop_keys=list(keys.values) or None,
                binary_values=list(values.values) or None,
                final_seq=len(ops), final_msn=0))
        return pack_mergetree_batch(docs)

    st_fast, op_fast, meta_fast = build()
    monkeypatch.setattr(npk, "load_library", lambda: None)
    st_slow, op_slow, meta_slow = build()
    for name in op_fast._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(op_fast, name)),
            np.asarray(getattr(op_slow, name)), err_msg=name)
    np.testing.assert_array_equal(meta_fast["doc_base"],
                                  meta_slow["doc_base"])
    assert meta_fast["arena"].finalize() == meta_slow["arena"].finalize()
    assert meta_fast["prop_keys"] == meta_slow["prop_keys"]
    assert list(meta_fast["values"].values) == \
        list(meta_slow["values"].values)


# --- the served stream path: CatchupService encodes, the C++ packer packs ---

#: multi-byte and astral text, as users type it
SERVED_ALPHABET = "ab çé日本語😀 "
CLIENTS = ("client0", "client1", "client2")


def _served_tail(seed, n_ops):
    """A string_tail-shaped tail: three clients in concurrent rounds of
    1-6 ops, each writing against its round's start plus its own ops,
    ``min_seq`` the least ref the clients last sent; inserts (some with
    props), removes, obliterates and annotates of two keys (one value
    None)."""
    rng = random.Random(seed)
    last_ref = {c: 0 for c in CLIENTS}
    ops, base = [], 0
    while len(ops) < n_ops:
        ref = len(ops)
        own = {c: 0 for c in CLIENTS}
        grown = shrunk = 0
        for _ in range(min(rng.randint(1, 6), n_ops - len(ops))):
            i = len(ops)
            client = CLIENTS[i % 3]
            length = base + own[client]
            r = rng.random()
            if r < 0.5 or length < 4:
                text = "".join(rng.choice(SERVED_ALPHABET)
                               for _ in range(rng.randint(1, 6)))
                contents = {"kind": "insert", "pos": rng.randint(0, length),
                            "text": text}
                if r < 0.08:
                    contents["props"] = {"b": "bold"}
                change = len(text)
            else:
                start = rng.randint(0, length - 2)
                end = min(length, start + rng.randint(1, 6))
                if r < 0.85:
                    kind = "obliterate" if r < 0.6 else "remove"
                    contents = {"kind": kind, "start": start, "end": end}
                    change = start - end
                else:
                    contents = {"kind": "annotate", "start": start,
                                "end": end, "props": {
                                    "f": rng.randint(0, 3),
                                    "g": rng.choice([None, "x", "y"])}}
                    change = 0
            own[client] += change
            grown += max(change, 0)
            shrunk += min(change, 0)
            last_ref[client] = ref
            ops.append((i + 1, client, ref, min(last_ref.values()),
                        contents))
        # An obliterate also takes the round's concurrent inserts in its
        # range, so such a round's inserts do not raise the lower bound.
        obliterated = any(op[4]["kind"] == "obliterate"
                          for op in ops[ref:])
        base = max(0, base + shrunk + (0 if obliterated else grown))
    return ops


def _envelope(seq, client, cseq, ref, min_seq, contents_list):
    return SequencedMessage(
        seq=seq, client_id=client, client_seq=cseq, ref_seq=ref,
        min_seq=min_seq, type=MessageType.OP,
        contents={"type": "groupedBatch", "ops": [
            {"ds": "ds", "channel": "text", "clientSeq": cseq,
             "contents": c} for c in contents_list]})


def _seed_served(service, n_docs, n_ops=48):
    from fluidframework_tpu.runtime.container import ContainerRuntime

    seeded = ContainerRuntime()
    seeded.create_datastore("ds").create_channel("sequence-tpu", "text")
    tree = seeded.summarize()
    doc_ids = []
    for d in range(n_docs):
        doc_id = f"sdoc{d}"
        service.storage.upload(doc_id, tree, 0)
        for seq, client, ref, min_seq, contents in _served_tail(40 + d,
                                                                n_ops):
            service.oplog.append(doc_id, _envelope(
                seq, client, seq, ref, min_seq, [contents]))
        doc_ids.append(doc_id)
    return doc_ids


def _cpu_catch_up(service, doc_ids):
    from fluidframework_tpu.service.catchup import CatchupService

    cpu = CatchupService(service, mesh=None, cache=None, pack_cache=None)
    cpu._device_plan = lambda w: None
    return cpu.catch_up(doc_ids, upload=False)


def test_served_stream_path_matches_message_path(monkeypatch):
    """A cold catch-up request packs every string channel from its record
    stream, and its summaries are byte-identical to the message path's
    and to the container oracle's."""
    import fluidframework_tpu.ops.native_pack as npk
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService

    service = LocalOrderingService()
    doc_ids = _seed_served(service, 10)
    svc = CatchupService(service, mesh=None)
    call: dict = {}
    got = svc.catch_up(doc_ids, upload=False, stats=call)
    assert svc.pipeline_stats["pack_stream_docs"] == len(doc_ids)
    assert svc.pipeline_stats.get("pack_message_docs", 0) == 0
    assert call["deviceDocs"] == len(doc_ids) and call["cpuDocs"] == 0

    assert got == _cpu_catch_up(service, doc_ids)
    # The message path: without the native packer every channel keeps
    # its flattened messages.
    monkeypatch.setattr(npk, "load_library", lambda: None)
    messages = CatchupService(service, mesh=None, cache=None)
    assert messages.catch_up(doc_ids, upload=False) == got
    assert messages.pipeline_stats["pack_message_docs"] == len(doc_ids)
    assert messages.pipeline_stats.get("pack_stream_docs", 0) == 0


def test_interval_and_warm_base_channels_keep_the_message_path():
    """A channel whose tail holds interval ops, and a channel with a warm
    base, are message documents, and still fold on the device."""
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService
    from tests.test_service import _seed_string_doc

    service = LocalOrderingService()
    _seed_string_doc(service, "cold", edits=6)
    ivl = _seed_string_doc(service, "ivl", edits=6)
    text = ivl[0].get_datastore("ds").get_channel("text")
    text.add_interval(1, 4)
    for rt in ivl:
        rt.drain()
    warm = _seed_string_doc(service, "warm", edits=6)
    svc = CatchupService(service, mesh=None)
    svc.catch_up(["warm"], upload=True)  # the base summary gains records
    text = warm[1].get_datastore("ds").get_channel("text")
    text.insert_text(2, "zz")
    for rt in warm:
        rt.drain()

    stats = svc.pipeline_stats
    before = dict(stats)
    call: dict = {}
    doc_ids = ["cold", "ivl", "warm"]
    got = svc.catch_up(doc_ids, upload=False, stats=call)
    assert got == _cpu_catch_up(service, doc_ids)

    def moved(key):
        return stats.get(key, 0) - before.get(key, 0)

    assert moved("pack_stream_docs") == 1      # "cold"
    assert moved("pack_message_docs") == 2     # "ivl", "warm"
    assert call["deviceDocs"] == 3 and call["cpuDocs"] == 0
    assert call["fallbackChannels"] == 0


def _decoded(doc_msgs):
    from fluidframework_tpu.runtime.op_pipeline import decode_stream

    return list(decode_stream(doc_msgs))


def test_channel_stream_is_the_encoding_of_the_flattened_ops():
    """``encode_channel_stream`` writes the bytes ``encode_string_ops``
    writes for ``flatten_channel_ops``'s messages, with the same intern
    tables; sub-ops of one batch keep its seq, and other channels' ops
    are left out."""
    from fluidframework_tpu.ops.native_pack import encode_channel_stream
    from fluidframework_tpu.service.catchup import flatten_channel_ops

    msgs = []
    for seq, client, ref, min_seq, contents in _served_tail(5, 60):
        batch = [contents]
        if seq % 7 == 0:
            batch.append({"kind": "insert", "pos": 0, "text": "é"})
        msgs.append(_envelope(seq, client, seq, ref, min_seq, batch))
        msgs.append(SequencedMessage(
            seq=seq, client_id=client, client_seq=seq, ref_seq=ref,
            min_seq=min_seq, type=MessageType.OP,
            contents={"type": "groupedBatch", "ops": [
                {"ds": "ds", "channel": "other", "clientSeq": seq,
                 "contents": {"kind": "insert", "pos": 0, "text": "q"}}]}))
    decoded = _decoded(msgs)
    blob, clients, keys, values = encode_channel_stream(decoded, "ds",
                                                        "text")
    cl, ks, vs = Interner(), Interner(), Interner()
    flat = flatten_channel_ops(decoded, "ds", "text")
    assert blob == encode_string_ops(flat, cl, ks, vs)
    assert clients == cl.values and keys == ks.values
    assert values == vs.values
    assert count_streams([blob])[0, 0] == len(flat)


@pytest.mark.parametrize("contents", [
    {"kind": "intervalAdd", "label": "default", "id": "iv", "start": 0,
     "end": 1},
    {"kind": "insert", "pos": 0, "text": ""},
    {"kind": "insert", "pos": 0, "text": "a\ud800b"},
], ids=["interval", "empty-insert", "lone-surrogate"])
def test_channel_stream_refuses_what_it_cannot_carry(contents):
    from fluidframework_tpu.ops.native_pack import encode_channel_stream

    msgs = [_envelope(1, "c0", 1, 0, 0,
                      [{"kind": "insert", "pos": 0, "text": "ab"}]),
            _envelope(2, "c1", 1, 1, 0, [contents])]
    assert encode_channel_stream(_decoded(msgs), "ds", "text") is None


@pytest.mark.parametrize("case", ["cold-5", "past-cap"])
def test_stream_document_takes_the_same_fallback_routes(case):
    """The overlap-remover routes count the same for a document packed
    from its stream as from its messages: device slots up to the cap,
    ``fallback_overflow`` past it, the oracle's bytes either way."""
    from tests.test_mergetree_kernel import _overlap_case

    doc, expected = _overlap_case(case)
    clients, keys, values = Interner(), Interner(), Interner()
    blob = encode_string_ops(doc.ops, clients, keys, values)
    stream_doc = MergeTreeDocInput(
        doc_id=doc.doc_id, ops=[], binary_ops=blob,
        binary_clients=list(clients.values),
        binary_prop_keys=list(keys.values) or None,
        binary_values=list(values.values) or None,
        final_seq=doc.final_seq, final_msn=doc.final_msn)
    routes = []
    for form in (doc, stream_doc):
        stats: dict = {}
        [summary] = replay_mergetree_batch([form], stats=stats)
        assert summary.digest() == expected
        routes.append(stats)
    assert routes[0] == routes[1]
    assert routes[1].get("fallback_overflow", 0) == (case == "past-cap")
