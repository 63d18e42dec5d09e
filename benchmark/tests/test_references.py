"""The plain reference agrees with the program's CPU container fold on
seeded documents, and sees a summary one op stale as wrong.  (The
reference itself imports nothing of the program; this test does.)"""

import ast
import os

import pytest

from benchmark.corpus import generator
from benchmark.corpus.envelope import seed_store
from benchmark.reference import string_ref
from benchmark.tests.tiny import ROOT_DIR

CHANNEL = {"ds": "ds", "id": "text", "type": "sequence-tpu"}
N_OPS = 96


def _container_blobs(service, doc):
    from fluidframework_tpu.runtime.container import ContainerRuntime

    runtime = ContainerRuntime()
    summary, ref_seq = service.storage.latest(doc)
    runtime.load(summary)
    for msg in service.oplog.get(doc, from_seq=ref_seq):
        runtime.process(msg)
    node = runtime.summarize().get(f".datastores/ds/{CHANNEL['id']}")
    return {k: v.content for k, v in node.children.items()}


@pytest.mark.parametrize("seed", [2_147_483_659, 5_300_000_017])
def test_reference_matches_the_container_fold(seed):
    from fluidframework_tpu.service import LocalOrderingService

    make = generator("string_tail")
    ids = [f"d{i}" for i in range(96)]
    tails = [make(seed, i, N_OPS) for i in range(len(ids))]
    service = LocalOrderingService()
    seed_store(service, ids, tails, CHANNEL)
    for doc, tail in zip(ids, tails):
        summary, _ref_seq = service.storage.latest(doc)
        node = summary.get(f".datastores/ds/{CHANNEL['id']}")
        base = {k: v.content for k, v in node.children.items()}
        assert string_ref.check(base, tail, 0) is None
        blobs = _container_blobs(service, doc)
        head = tail[-1][0]
        assert string_ref.check(blobs, tail, head) is None, doc
        assert string_ref.check(blobs, tail, head - 1) is not None, doc


def test_tails_are_concurrent_and_the_window_floor_advances():
    make = generator("string_tail")
    lagged = ops = 0
    for i in range(64):
        tail = make(9, i, N_OPS)
        assert [op[0] for op in tail] == list(range(1, N_OPS + 1))
        mins = [op[3] for op in tail]
        assert mins == sorted(mins) and mins[-1] >= N_OPS // 2
        for seq, _client, ref, min_seq, _op in tail:
            assert min_seq <= ref < seq
            lagged += ref < seq - 1
            ops += 1
    assert lagged / ops > 0.5


def test_the_reference_resolves_concurrent_ops():
    # client1 and client2 both write against seq 1 ("ab" by client0)
    tail = [
        (1, "c0", 0, 0, {"kind": "insert", "pos": 0, "text": "ab"}),
        (2, "c1", 1, 0, {"kind": "insert", "pos": 1, "text": "X"}),
        (3, "c2", 1, 0, {"kind": "insert", "pos": 1, "text": "Y"}),
        (4, "c1", 1, 1, {"kind": "remove", "start": 0, "end": 3}),
        (5, "c2", 1, 1, {"kind": "remove", "start": 2, "end": 3}),
    ]
    header, state = string_ref.string_state(tail, 5)
    # newest first at one place; client1's remove skips client2's "Y"
    # (not in its view: a X b) but takes its own "X"; client2 (view
    # a Y b) removes "b" after client1 did, as an overlapping remover
    assert "".join(ch[0] for ch in state) == "aYXb"
    assert [(ch[0], ch[3], ch[4], ch[5]) for ch in state] == [
        ("a", 4, "c1", ()), ("Y", None, None, ()), ("X", 4, "c1", ()),
        ("b", 4, "c1", ("c2",))]
    assert header == {"length": 1, "minSeq": 1, "seq": 5}
    # once the floor passes the remove, its tombstones are collected
    tail.append((6, "c0", 5, 5, {"kind": "insert", "pos": 0, "text": "z"}))
    _header, state = string_ref.string_state(tail, 6)
    assert [ch[:3] for ch in state] == [("z", 6, "c0"), ("Y", 0, None)]


def test_the_same_seed_makes_the_same_data():
    make = generator("string_tail")
    assert make(7, 3, N_OPS) == make(7, 3, N_OPS)
    assert make(7, 3, N_OPS) != make(8, 3, N_OPS)
    big = 2 ** 31 + 11
    assert len(make(big, 10239, N_OPS)) == N_OPS


def test_references_import_nothing_of_the_program():
    folder = os.path.join(ROOT_DIR, "benchmark", "reference")
    for name in os.listdir(folder):
        if not name.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(folder, name)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                assert not (node.module or "").startswith(
                    "fluidframework_tpu"), name
                assert node.level == 1 or node.module in (
                    "__future__",), name
            if isinstance(node, ast.Import):
                for alias in node.names:
                    assert alias.name in ("json",), name
