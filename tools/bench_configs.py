"""All five BASELINE.json configs measured: CPU oracle vs device path.

BASELINE.md's measurement table is produced by this harness.  Each config
times

- the CPU oracle (per-op ``process`` replay through the DDS, the pinned 1×
  denominator) on a doc sample, and
- the device path END-TO-END (pack → fold → download → canonical summary
  extraction) over the full doc population, chunked like production,

and asserts byte-identical summaries on sampled docs.  Workloads are
seeded and deterministic; sizes via BENCHCFG_* env vars.

Configs (BASELINE.json):
  1 sharedstring  — merge-tree insert/remove/annotate replay (bench.py's
                    pinned workload, reused here)
  2 map           — SharedMap LWW set/delete/clear replay
  3 intervals     — SharedString + IntervalCollection annotate workload
  4 matrix        — SharedMatrix row/col insert/remove + cell sets
  5 tree          — SharedTree edit replay (insert/set/remove/move)

Prints one human table to stderr and ONE JSON line to stdout:
    {"metric": "baseline_configs", "configs": {...per-config rows...}}
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from fluidframework_tpu.dds import (  # noqa: E402
    SharedMap,
    SharedMatrix,
    SharedString,
)
from fluidframework_tpu.dds.tree import ROOT_ID, SharedTree  # noqa: E402
from fluidframework_tpu.ops.map_kernel import (  # noqa: E402
    MapDocInput,
    replay_map_batch,
)
from fluidframework_tpu.ops.matrix_kernel import (  # noqa: E402
    MatrixDocInput,
    replay_matrix_batch,
)
from fluidframework_tpu.ops.mergetree_kernel import (  # noqa: E402
    MergeTreeDocInput,
)
from fluidframework_tpu.ops.tree_kernel import (  # noqa: E402
    TreeDocInput,
    replay_tree_batch,
)
from fluidframework_tpu.protocol.messages import (  # noqa: E402
    MessageType,
    SequencedMessage,
)
from fluidframework_tpu.testing.mocks import (  # noqa: E402
    MockContainerRuntimeFactory,
    channel_log,
)

CHUNK = int(os.environ.get("BENCHCFG_CHUNK", "1024"))
CPU_SAMPLE = int(os.environ.get("BENCHCFG_CPU_SAMPLE", "64"))
SANITY_SAMPLE = 3


def _msg(seq: int, client: str, contents: dict) -> SequencedMessage:
    return SequencedMessage(
        seq=seq, client_id=client, client_seq=seq, ref_seq=seq - 1,
        min_seq=0, type=MessageType.OP, contents=contents,
    )


# -- workload generators (seeded, deterministic) ------------------------------


def gen_string_doc(idx: int, n_ops: int) -> MergeTreeDocInput:
    """Config #1: bench.py's pinned workload (binary-stream ingestion)."""
    import bench

    return bench.synth_doc(idx, n_ops)


def gen_map_doc(idx: int, n_ops: int) -> MapDocInput:
    """Config #2: LWW key traffic over a zipf-ish key population, 3 clients,
    92% set / 6% delete / 2% clear."""
    rng = random.Random(idx * 6271 + 5)
    n_keys = 24
    ops = []
    for i in range(n_ops):
        seq = i + 1
        client = f"client{i % 3}"
        r = rng.random()
        key = f"k{int(rng.random() ** 2 * n_keys)}"
        if r < 0.92:
            contents = {"kind": "set", "key": key,
                        "value": rng.randint(0, 999)}
        elif r < 0.98:
            contents = {"kind": "delete", "key": key}
        else:
            contents = {"kind": "clear"}
        ops.append(_msg(seq, client, contents))
    return MapDocInput(doc_id=f"map{idx}", ops=ops)


ALPHABET = "abcdefghijklmnopqrstuvwxyz "


def gen_interval_doc(idx: int, n_ops: int) -> MergeTreeDocInput:
    """Config #3: text traffic carrying a live interval population —
    adds/changes/deletes against sliding local references (message-list
    ingestion; interval ops never ride the binary stream)."""
    rng = random.Random(idx * 9973 + 29)
    ops, length = [], 0
    live: list = []
    for i in range(n_ops):
        seq = i + 1
        client = f"client{i % 3}"
        r = rng.random()
        if r < 0.5 or length < 8:
            pos = rng.randint(0, length)
            text = "".join(
                rng.choice(ALPHABET) for _ in range(rng.randint(1, 8))
            )
            contents = {"kind": "insert", "pos": pos, "text": text}
            length += len(text)
        elif r < 0.7:
            start = rng.randint(0, length - 2)
            end = min(length, start + rng.randint(1, 8))
            contents = {"kind": "remove", "start": start, "end": end}
            length -= end - start
        elif r < 0.85 or not live:
            iid = f"iv{idx}-{seq}"
            start = rng.randint(0, length - 2)
            end = min(length - 1, start + rng.randint(1, 12))
            contents = {"kind": "intervalAdd", "label": "default",
                        "id": iid, "start": start, "end": end,
                        "props": {"c": rng.randint(0, 5)}}
            live.append(iid)
        elif r < 0.95:
            iid = rng.choice(live)
            start = rng.randint(0, length - 2)
            contents = {"kind": "intervalChange", "label": "default",
                        "id": iid, "start": start,
                        "end": min(length - 1, start + rng.randint(1, 12))}
        else:
            iid = live.pop(rng.randrange(len(live)))
            contents = {"kind": "intervalDelete", "label": "default",
                        "id": iid}
        ops.append(_msg(seq, client, contents))
    return MergeTreeDocInput(doc_id=f"iv{idx}", ops=ops,
                             final_seq=n_ops, final_msn=0)


def gen_matrix_doc(idx: int, n_ops: int) -> MatrixDocInput:
    """Config #4: row/col growth + removals + cell sets on the live grid."""
    rng = random.Random(idx * 3557 + 11)
    ops, rows, cols = [], 0, 0
    for i in range(n_ops):
        seq = i + 1
        client = f"client{i % 3}"
        r = rng.random()
        if r < 0.18 or rows == 0:
            count = rng.randint(1, 3)
            contents = {"kind": "insertRows",
                        "pos": rng.randint(0, rows), "count": count}
            rows += count
        elif r < 0.36 or cols == 0:
            count = rng.randint(1, 3)
            contents = {"kind": "insertCols",
                        "pos": rng.randint(0, cols), "count": count}
            cols += count
        elif r < 0.42 and rows > 2:
            start = rng.randint(0, rows - 2)
            end = min(rows, start + rng.randint(1, 2))
            contents = {"kind": "removeRows", "start": start, "end": end}
            rows -= end - start
        elif r < 0.48 and cols > 2:
            start = rng.randint(0, cols - 2)
            end = min(cols, start + rng.randint(1, 2))
            contents = {"kind": "removeCols", "start": start, "end": end}
            cols -= end - start
        else:
            contents = {"kind": "setCell", "row": rng.randint(0, rows - 1),
                        "col": rng.randint(0, cols - 1),
                        "value": rng.randint(0, 999)}
        ops.append(_msg(seq, client, contents))
    return MatrixDocInput(doc_id=f"mx{idx}", ops=ops,
                          final_seq=n_ops, final_msn=0)


def gen_tree_doc(idx: int, n_edits: int) -> TreeDocInput:
    """Config #5: drive a SharedTree client through the mock sequencer
    (tree changesets carry anchors/ids a raw generator can't fabricate)."""
    rng = random.Random(idx * 4099 + 17)
    factory = MockContainerRuntimeFactory()
    t = factory.create_client("client0").attach(SharedTree("tree"))
    nodes: list = []
    for _ in range(n_edits):
        roll = rng.random()
        if roll < 0.45 or len(nodes) < 3:
            field = rng.choice(["a", "b"])
            kids = t.children(ROOT_ID, field)
            [nid] = t.insert(ROOT_ID, field, rng.randint(0, len(kids)),
                             [t.build("n", value=rng.randint(0, 99))])
            nodes.append(nid)
        elif roll < 0.75:
            t.set_value(rng.choice(nodes), rng.randint(0, 999))
        elif roll < 0.88:
            nid = nodes.pop(rng.randrange(len(nodes)))
            t.remove(nid)
        else:
            nid = rng.choice(nodes)
            field = rng.choice(["a", "b"])
            kids = [k for k in t.children(ROOT_ID, field) if k != nid]
            t.move([nid], ROOT_ID, field, rng.randint(0, len(kids)))
        factory.process_all_messages()
    return TreeDocInput(
        doc_id=f"tree{idx}", ops=channel_log(factory, "tree"),
        final_seq=factory.sequencer.seq, final_msn=factory.sequencer.min_seq,
    )


# -- oracle replays -----------------------------------------------------------


def oracle_string(doc: MergeTreeDocInput):
    replica = SharedString(doc.doc_id)
    for msg in doc.ops:
        replica.process(msg, local=False)
    replica.advance(doc.final_seq, doc.final_msn)
    return replica.summarize()


def oracle_map(doc: MapDocInput):
    replica = SharedMap(doc.doc_id)
    for msg in doc.ops:
        replica.process(msg, local=False)
    return replica.summarize()


def oracle_matrix(doc: MatrixDocInput):
    replica = SharedMatrix(doc.doc_id)
    for msg in doc.ops:
        replica.process(msg, local=False)
    replica.advance(doc.final_seq, doc.final_msn)
    return replica.summarize()


def oracle_tree(doc: TreeDocInput):
    from fluidframework_tpu.ops.tree_kernel import oracle_fallback_summary

    return oracle_fallback_summary(doc)


# -- the measurement loop -----------------------------------------------------


def _pipelined_string(docs, stats=None, stage=None):
    """Config #1/#3 device path = the PRODUCT pipeline (the same chunked
    single-device-thread fold the catch-up service runs)."""
    from fluidframework_tpu.ops.pipeline import pipelined_mergetree_replay

    return pipelined_mergetree_replay(docs, chunk_docs=CHUNK, stats=stats,
                                      stage=stage)


def run_config(name, docs, n_ops, oracle_fn, device_batch_fn,
               self_chunked=False):
    total_ops = sum(n_ops(d) for d in docs)
    sample = docs[:CPU_SAMPLE]
    t0 = time.time()
    oracle_digests = [oracle_fn(d).digest() for d in sample]
    cpu_t = time.time() - t0
    cpu_rate = sum(n_ops(d) for d in sample) / cpu_t

    # Device end-to-end (chunked like production).  Warm the compile cache
    # on a FULL first chunk — the (S, T) buckets derive from batch maxima,
    # so a tiny warm batch would compile a different shape and leave the
    # real compilation inside the timed loop.  ``self_chunked`` fns (the
    # product's pipelined replay) receive the whole population in one
    # call and chunk/overlap internally.
    device_batch_fn(docs[:CHUNK])
    stats: dict = {}
    stage: dict = {}
    t0 = time.time()
    if self_chunked:
        # The product pipeline carries the honest stage split
        # (device_wait vs download) + the d2h/h2d byte counters.
        summaries = list(device_batch_fn(docs, stats=stats, stage=stage))
    else:
        summaries = []
        for i in range(0, len(docs), CHUNK):
            summaries.extend(device_batch_fn(docs[i:i + CHUNK], stats=stats))
    dev_t = time.time() - t0
    dev_rate = total_ops / dev_t

    for d in range(0, len(sample), max(1, len(sample) // SANITY_SAMPLE)):
        assert summaries[d].digest() == oracle_digests[d], (
            f"{name}: doc {d} device summary != oracle"
        )
    row = {
        "n_docs": len(docs),
        "total_ops": total_ops,
        "cpu_ops_per_sec": round(cpu_rate, 1),
        "device_ops_per_sec": round(dev_rate, 1),
        "vs_baseline": round(dev_rate / cpu_rate, 2),
        "device_sec": round(dev_t, 3),
        "fallback_docs": stats.get("fallback_docs", 0),
        "device_docs": stats.get("device_docs", 0),
        # Null-stable on non-pipeline configs (no stage instrumentation).
        "stages_busy_sec": ({
            k: round(v, 3) for k, v in sorted(stage.items())
            if k not in ("d2h_bytes", "h2d_bytes")
        } if stage else None),
        "d2h_bytes": (int(stage.get("d2h_bytes", 0)) if stage else None),
        "h2d_bytes": (int(stage.get("h2d_bytes", 0)) if stage else None),
    }
    print(
        f"{name:12s} docs={len(docs):5d} ops={total_ops:7d} "
        f"cpu={cpu_rate:10,.0f}/s device={dev_rate:10,.0f}/s "
        f"ratio={row['vs_baseline']:6.2f}x "
        f"fallbacks={row['fallback_docs']}/{len(docs)}",
        file=sys.stderr,
    )
    return row


def main() -> None:
    """Prints the one result line; any failure propagates with its
    traceback and a non-zero exit."""
    import bench
    from fluidframework_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    print(json.dumps(_run_configs(bench.device_info())), flush=True)


def _run_configs(device: dict) -> dict:
    sizes = {
        "sharedstring": (int(os.environ.get("BENCHCFG_STRING_DOCS", "4096")),
                         96),
        "map": (int(os.environ.get("BENCHCFG_MAP_DOCS", "4096")), 96),
        "intervals": (int(os.environ.get("BENCHCFG_IV_DOCS", "2048")), 96),
        "matrix": (int(os.environ.get("BENCHCFG_MATRIX_DOCS", "1024")), 64),
        "tree": (int(os.environ.get("BENCHCFG_TREE_DOCS", "256")), 48),
    }
    print(f"backend={jax.default_backend()}", file=sys.stderr)
    results = {}

    n, k = sizes["sharedstring"]
    t0 = time.time()
    docs = [gen_string_doc(i, k) for i in range(n)]
    print(f"gen sharedstring {time.time()-t0:.1f}s", file=sys.stderr)
    results["sharedstring"] = run_config(
        "sharedstring", docs, lambda d: k,
        oracle_string_binary, _pipelined_string, self_chunked=True,
    )

    n, k = sizes["map"]
    t0 = time.time()
    docs = [gen_map_doc(i, k) for i in range(n)]
    print(f"gen map {time.time()-t0:.1f}s", file=sys.stderr)
    results["map"] = run_config(
        "map", docs, lambda d: len(d.ops), oracle_map, replay_map_batch,
    )

    n, k = sizes["intervals"]
    t0 = time.time()
    docs = [gen_interval_doc(i, k) for i in range(n)]
    print(f"gen intervals {time.time()-t0:.1f}s", file=sys.stderr)
    results["intervals"] = run_config(
        "intervals", docs, lambda d: len(d.ops),
        oracle_string, _pipelined_string, self_chunked=True,
    )

    n, k = sizes["matrix"]
    t0 = time.time()
    docs = [gen_matrix_doc(i, k) for i in range(n)]
    print(f"gen matrix {time.time()-t0:.1f}s", file=sys.stderr)
    results["matrix"] = run_config(
        "matrix", docs, lambda d: len(d.ops),
        oracle_matrix, replay_matrix_batch,
    )

    n, k = sizes["tree"]
    t0 = time.time()
    docs = [gen_tree_doc(i, k) for i in range(n)]
    print(f"gen tree {time.time()-t0:.1f}s", file=sys.stderr)
    results["tree"] = run_config(
        "tree", docs, lambda d: len(d.ops), oracle_tree, replay_tree_batch,
    )

    return {
        "metric": "baseline_configs",
        "backend": device["platform"],
        "device_kind": device["device_kind"],
        "n_devices": device["n_devices"],
        "configs": results,
    }


def oracle_string_binary(doc: MergeTreeDocInput):
    """Oracle for binary-stream docs (config #1 reuses bench.synth_doc)."""
    import bench

    return bench.oracle_replay(doc).summarize()


if __name__ == "__main__":
    main()
