"""Second-kernel-family benches (ISSUE 14): 10k-doc SharedTree rebase +
interval stabbing as first-class workloads on the generic pipeline.

Through round 13 every bench measured only SharedString catch-up; this
harness is the load-bearing proof that the cache/pipeline abstractions
are not merge-tree-shaped:

- **tree_rebase** — 10k-doc SharedTree catch-up through
  ``pipelined_tree_replay`` (deep-move chains, wide-container fan-out,
  plus the fallback shapes: revive, multi-id move, MAX_DEPTH overflow),
  cold → warm-exact → warm-grown, with the full r13 stage schema
  (``pack/upload/dispatch/device_wait/download/extract``,
  ``h2d_bytes``/``d2h_bytes``), per-reason fallback accounting, all four
  cache tiers' counters, and a CatchupService cold/warm pass whose warm
  serve must be pure tier-1 (``cache_hit_rate`` 1.0, h2d == d2h == 0);
- **interval_stabbing** — 10k string documents whose interval
  populations attach references across segments that later removes
  force through the lazy slide cascade (``ops/interval_replay.py``'s
  hot path: bounded-visibility stabs + ``anchor_final`` cascades),
  folded cold/warm through the SAME pipeline the string family serves.

Byte-identity is asserted in-run: caches-on == caches-off ==
``replay_tree_batch`` across the WHOLE population, re-asserted after a
forced epoch invalidation, and against the ``dds/`` per-op oracles on a
deterministic sample (``BENCHK_ORACLE_EVERY``; 1 = every doc).

Prints ONE JSON line naming its device; any failure exits non-zero with
its traceback:

    JAX_PLATFORMS=cpu python tools/bench_kernels.py \
        > BENCH_kernels_cpu_r14.json
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fluidframework_tpu.ops.mergetree_kernel import (  # noqa: E402
    MergeTreeDocInput,
)
from fluidframework_tpu.ops.tree_kernel import (  # noqa: E402
    MAX_DEPTH,
    TreeDocInput,
)
from fluidframework_tpu.protocol.messages import (  # noqa: E402
    MessageType,
    SequencedMessage,
)

METRIC = "kernel_families"

TREE_DOCS = int(os.environ.get("BENCHK_TREE_DOCS", "10240"))
TREE_EDITS = int(os.environ.get("BENCHK_TREE_EDITS", "48"))
IV_DOCS = int(os.environ.get("BENCHK_IV_DOCS", "10240"))
IV_OPS = int(os.environ.get("BENCHK_IV_OPS", "96"))
#: oracle sampling stride (1 = byte-check EVERY doc against the dds
#: oracle; the cross-configuration parity below is always full-corpus)
ORACLE_EVERY = int(os.environ.get("BENCHK_ORACLE_EVERY", "4"))
CHUNK = int(os.environ.get("BENCHK_CHUNK", "1024"))
GROW_EVERY = int(os.environ.get("BENCHK_GROW_EVERY", "8"))

ALPHABET = "abcdefghijklmnopqrstuvwxyz "

#: deterministic workload-shape assignment: the three fallback shapes
#: ride along at ~9% so the per-reason counters have real traffic, the
#: rest splits between the two device-path shapes.
def tree_shape(idx: int) -> str:
    r = idx % 32
    if r == 0:
        return "revive"
    if r == 1:
        return "multi_id_move"
    if r == 2:
        return "max_depth"
    return "deep-move" if idx % 2 == 0 else "wide-container"


def _msg(seq: int, min_seq: int, edits: list) -> SequencedMessage:
    return SequencedMessage(
        seq=seq, client_id=f"c{seq % 3}", client_seq=seq, ref_seq=seq - 1,
        min_seq=min_seq, type=MessageType.OP, contents={"edits": edits},
    )


def synth_tree_messages(idx: int, n_edits: int):
    """One document's deterministic SharedTree changeset stream.

    Shapes (see :func:`tree_shape`): ``deep-move`` builds a nested chain
    and keeps moving leaves (and chain nodes — including dropped-cycle
    moves) through its containers, the ancestor-walk-heavy rebase case;
    ``wide-container`` fans leaves out under two root fields with
    anchored inserts/removes/sets/moves; the fallback shapes inject one
    revive, one multi-id move, or a > MAX_DEPTH chain + move (device
    overflow) into otherwise-normal traffic.  ``min_seq`` advances
    periodically so purge windows and purge-gated edits execute."""
    rng = random.Random(idx * 48611 + 7)
    shape = tree_shape(idx)
    msgs, seq, min_seq = [], 0, 0
    counter = [0]

    def fresh() -> str:
        counter[0] += 1
        return f"t{idx}-n{counter[0]}"

    def emit(*edits):
        nonlocal seq, min_seq
        seq += 1
        if seq > 24 and seq % 10 == 0:
            min_seq = seq - 20
        msgs.append(_msg(seq, min_seq, list(edits)))

    def leaf(value: int) -> dict:
        return {"id": fresh(), "type": "n", "value": value}

    def ins(parent: str, field: str, spec: dict, anchor=None) -> dict:
        return {"kind": "insert", "parent": parent, "field": field,
                "anchor": anchor, "content": [spec]}

    live: list = []
    chain: list = []
    if shape in ("deep-move", "max_depth"):
        depth = (MAX_DEPTH + 6) if shape == "max_depth" \
            else rng.randint(8, 20)
        spec = leaf(0)
        chain.append(spec["id"])
        root_spec = spec
        for _ in range(depth - 1):
            child = leaf(0)
            spec["fields"] = {"k": [child]}
            spec = child
            chain.append(spec["id"])
        emit(ins("", "a", root_spec))
        if shape == "max_depth":
            # Guarantee the overflow: a move whose destination sits
            # below MAX_DEPTH ancestors makes the device's cycle walk
            # overflow deterministically (the doc's fallback REASON).
            probe = leaf(1)
            live.append((probe["id"], probe["value"]))
            emit(ins("", "b", probe))
            emit({"kind": "move", "ids": [probe["id"]],
                  "parent": chain[-1], "field": "k", "anchor": None})
    removed: list = []
    for i in range(n_edits - len(msgs)):
        roll = rng.random()
        if shape == "revive" and i == n_edits // 2 and removed:
            nid, value = removed[-1]
            emit({"kind": "revive", "ids": [nid], "parent": "",
                  "field": "a", "anchor": None,
                  "content": [{"id": nid, "type": "n", "value": value}]})
            continue
        if shape == "multi_id_move" and i == n_edits // 2 \
                and len(live) >= 2:
            emit({"kind": "move", "ids": [live[0][0], live[1][0]],
                  "parent": "", "field": "b", "anchor": None})
            continue
        if shape in ("deep-move", "max_depth") and roll < 0.35 and chain:
            target_parent = rng.choice(chain)
            if roll < 0.12 and live:
                # move a leaf deep into the chain (the ancestor-walk
                # stab; on the max_depth shape this overflows)
                emit({"kind": "move", "ids": [rng.choice(live)[0]],
                      "parent": target_parent, "field": "k",
                      "anchor": None})
            elif roll < 0.2 and len(chain) > 4:
                # chain node into its own descendant: the CYCLE case —
                # dropped identically by oracle and device
                hi = rng.randrange(2, len(chain) - 1)
                emit({"kind": "move", "ids": [chain[hi - 1]],
                      "parent": chain[hi], "field": "k", "anchor": None})
            else:
                spec = leaf(rng.randint(0, 99))
                live.append((spec["id"], spec["value"]))
                emit(ins(target_parent, "k", spec))
        elif roll < 0.45 or len(live) < 3:
            spec = leaf(rng.randint(0, 99))
            anchor = (rng.choice(live)[0]
                      if live and rng.random() < 0.5 else None)
            live.append((spec["id"], spec["value"]))
            emit(ins("", rng.choice(["a", "b"]), spec, anchor=anchor))
        elif roll < 0.65:
            nid, _v = rng.choice(live)
            emit({"kind": "set", "id": nid,
                  "value": rng.randint(0, 999)})
        elif roll < 0.8:
            k = rng.randrange(len(live))
            nid, value = live.pop(k)
            removed.append((nid, value))
            emit({"kind": "remove", "ids": [nid]})
        else:
            nid, _v = rng.choice(live)
            anchor = (rng.choice(live)[0]
                      if rng.random() < 0.5 else None)
            if anchor == nid:
                anchor = None
            emit({"kind": "move", "ids": [nid], "parent": "",
                  "field": rng.choice(["a", "b"]), "anchor": anchor})
    return msgs


def tree_doc(idx: int, msgs, n_msgs: int) -> TreeDocInput:
    """The catch-up work item over the stream's first ``n_msgs``
    messages — a fixed token, so grown windows extend under the tier
    identity contract."""
    window = msgs[:n_msgs]
    return TreeDocInput(
        doc_id=f"tdoc{idx}", ops=window, final_seq=window[-1].seq,
        final_msn=window[-1].min_seq,
        cache_token=("bench-epoch", f"tdoc{idx}", 0, ""),
    )


def synth_interval_doc(idx: int, n_ops: int,
                       n_msgs=None) -> MergeTreeDocInput:
    """A string document with a DENSE interval population over segments
    that later removes force through the slide cascade: phase 1 builds
    text, phase 2 attaches ~n/4 intervals across it, phase 3 removes
    spans (every ref on a removed segment must slide — repeatedly, when
    the landing segment is itself removed later), phase 4 keeps
    churning adds/changes/deletes.  The stabbing workload for
    ``ops/interval_replay.py``."""
    rng = random.Random(idx * 7103 + 3)
    ops, length = [], 0
    live: list = []
    for i in range(n_ops):
        seq = i + 1
        client = f"client{i % 3}"
        phase = i * 4 // n_ops
        r = rng.random()
        if phase == 0 or length < 16:
            pos = rng.randint(0, length)
            text = "".join(
                rng.choice(ALPHABET) for _ in range(rng.randint(2, 8)))
            contents = {"kind": "insert", "pos": pos, "text": text}
            length += len(text)
        elif phase == 1 or (phase == 3 and (r < 0.4 or not live)):
            iid = f"iv{idx}-{seq}"
            start = rng.randint(0, length - 2)
            contents = {"kind": "intervalAdd", "label": "default",
                        "id": iid, "start": start,
                        "end": min(length - 1, start + rng.randint(1, 12)),
                        "props": {"c": rng.randint(0, 5)}}
            live.append(iid)
        elif phase == 2 and r < 0.7:
            start = rng.randint(0, length - 2)
            end = min(length, start + rng.randint(2, 10))
            contents = {"kind": "remove", "start": start, "end": end}
            length -= end - start
        elif r < 0.7:
            iid = rng.choice(live)
            start = rng.randint(0, max(0, length - 2))
            contents = {"kind": "intervalChange", "label": "default",
                        "id": iid, "start": start,
                        "end": min(length - 1,
                                   start + rng.randint(1, 12))}
        else:
            iid = live.pop(rng.randrange(len(live)))
            contents = {"kind": "intervalDelete", "label": "default",
                        "id": iid}
        ops.append(SequencedMessage(
            seq=seq, client_id=client, client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents=contents,
        ))
    window = ops[:n_msgs] if n_msgs is not None else ops
    return MergeTreeDocInput(
        doc_id=f"ivdoc{idx}", ops=window, final_seq=window[-1].seq,
        final_msn=0,
        cache_token=("bench-epoch", f"ivdoc{idx}", 0, ""),
    )


# ---------------------------------------------------------------------------
# The measurement passes
# ---------------------------------------------------------------------------


def _stage_row(stage: dict) -> dict:
    return {
        "stages_busy_sec": {
            k: round(v, 3) for k, v in sorted(stage.items())
            if k not in ("d2h_bytes", "h2d_bytes")
        },
        "h2d_bytes": int(stage.get("h2d_bytes", 0)),
        "d2h_bytes": int(stage.get("d2h_bytes", 0)),
    }


def _one_pass(replay, docs, total_ops, caches) -> tuple:
    stage = {"pack": 0.0, "upload": 0.0, "dispatch": 0.0,
             "device_wait": 0.0, "download": 0.0, "extract": 0.0,
             "d2h_bytes": 0, "h2d_bytes": 0}
    stats: dict = {}
    t0 = time.time()
    summaries = replay(docs, chunk_docs=CHUNK, stage=stage, stats=stats,
                       **caches)
    wall = time.time() - t0
    row = {
        "ops_per_sec": round(total_ops / wall, 1),
        "wall_sec": round(wall, 3),
        **_stage_row(stage),
        "stats": dict(sorted(stats.items())),
    }
    return [s.digest() for s in summaries], row


def run_tree_rebase() -> dict:
    """Cold → warm-exact → warm-grown tree rebase at 10k docs, full
    parity matrix, per-reason fallback accounting, and the service-tier
    warm catch-up gate."""
    from fluidframework_tpu.ops.tree_kernel import (
        oracle_fallback_summary,
        replay_tree_batch,
    )
    from fluidframework_tpu.ops.tree_pipeline import (
        pipelined_tree_replay,
        tree_device_cache,
        tree_pack_cache,
    )
    from fluidframework_tpu.service.catchup_cache import DeltaExportCache

    t0 = time.time()
    grow = max(2, TREE_EDITS // 8)
    streams = [synth_tree_messages(i, TREE_EDITS) for i in range(TREE_DOCS)]
    base_docs = [tree_doc(i, s, len(s) - grow)
                 for i, s in enumerate(streams)]
    grown_idx = set(range(0, TREE_DOCS, max(1, GROW_EVERY)))
    grown_docs = [
        tree_doc(i, s, len(s) if i in grown_idx else len(s) - grow)
        for i, s in enumerate(streams)
    ]
    gen_sec = time.time() - t0
    total_ops = sum(len(d.ops) for d in base_docs)
    print(f"tree: generated {TREE_DOCS} docs in {gen_sec:.1f}s",
          file=sys.stderr)

    pack, dev, delta = tree_pack_cache(), tree_device_cache(), \
        DeltaExportCache()
    caches = dict(pack_cache=pack, device_cache=dev, delta_cache=delta)
    cold_dig, cold = _one_pass(pipelined_tree_replay, base_docs,
                               total_ops, caches)
    warm_dig, warm = _one_pass(pipelined_tree_replay, base_docs,
                               total_ops, caches)
    assert warm_dig == cold_dig, "tree warm-exact changed bytes"
    grown_total = sum(len(d.ops) for d in grown_docs)
    grown_dig, grown = _one_pass(pipelined_tree_replay, grown_docs,
                                 grown_total, caches)

    # Parity matrix: caches-off over the WHOLE population, both windows.
    off_base_dig, off_base = _one_pass(pipelined_tree_replay, base_docs,
                                       total_ops, {})
    assert off_base_dig == cold_dig, "tree caches-on != caches-off"
    off_grown_dig, _row = _one_pass(pipelined_tree_replay, grown_docs,
                                    grown_total, {})
    assert off_grown_dig == grown_dig, \
        "tree grown caches-on != caches-off"
    batch_dig = [s.digest()
                 for s in replay_tree_batch(list(grown_docs))]
    assert batch_dig == grown_dig, "pipelined != replay_tree_batch"

    # Forced invalidation: sweep every epoch-keyed tier, then re-fold —
    # still byte-identical (and the tiers legitimately refill).
    delta.invalidate_epoch("other-epoch")
    dev.invalidate_epoch("other-epoch")
    inval_dig, inval = _one_pass(pipelined_tree_replay, grown_docs,
                                 grown_total, caches)
    assert inval_dig == grown_dig, "post-invalidation bytes changed"

    # dds oracle on the deterministic sample (every shape included).
    t0 = time.time()
    n_checked = 0
    for i in range(0, TREE_DOCS, max(1, ORACLE_EVERY)):
        assert grown_dig[i] == \
            oracle_fallback_summary(grown_docs[i]).digest(), (
                f"tree doc {i} ({tree_shape(i)}) != dds oracle")
        n_checked += 1
    oracle_sec = time.time() - t0
    print(f"tree: {n_checked} docs oracle-verified in {oracle_sec:.1f}s",
          file=sys.stderr)

    return {
        "docs": TREE_DOCS,
        "edits_per_doc": TREE_EDITS,
        "grown_docs": len(grown_idx),
        "shapes": {
            s: sum(1 for i in range(TREE_DOCS) if tree_shape(i) == s)
            for s in ("deep-move", "wide-container", "revive",
                      "multi_id_move", "max_depth")
        },
        "gen_sec": round(gen_sec, 1),
        "cold": cold,
        "warm_exact": warm,
        "warm_grown": grown,
        "caches_off": off_base,
        "post_invalidation": inval,
        "fallback_reasons": {
            k: v for k, v in sorted(grown["stats"].items())
            if k.startswith("fallback")
        },
        "pack_cache": pack.stats(),
        "device_cache": dev.stats(),
        "delta_cache": delta.stats(),
        "oracle_checked_docs": n_checked,
        "oracle_every": ORACLE_EVERY,
        "service_catchup": run_tree_catchup_service(),
    }


def build_tree_catchup_corpus(service, n_docs: int, n_edits: int):
    """Seed ``service`` with tree-channel documents: an empty seeded
    summary plus the pinned tree changeset tails appended to the op log
    in the runtime's groupedBatch envelope — the service-shaped twin of
    the tree bench corpus (mirrors ``bench.build_catchup_corpus``)."""
    from fluidframework_tpu.runtime.container import ContainerRuntime

    seeded = ContainerRuntime()
    seeded.create_datastore("ds").create_channel("tree-tpu", "tree")
    seed_tree = seeded.summarize()
    doc_ids = []
    for i in range(n_docs):
        doc_id = f"ctdoc{i}"
        service.storage.upload(doc_id, seed_tree, 0)
        for m in synth_tree_messages(i, n_edits):
            service.oplog.append(doc_id, SequencedMessage(
                seq=m.seq, client_id=m.client_id,
                client_seq=m.client_seq, ref_seq=m.ref_seq,
                min_seq=m.min_seq, type=MessageType.OP,
                contents={"type": "groupedBatch", "ops": [
                    {"ds": "ds", "channel": "tree",
                     "clientSeq": m.client_seq,
                     "contents": m.contents}]},
            ))
        doc_ids.append(doc_id)
    return doc_ids


def run_tree_catchup_service() -> dict:
    """The acceptance-criterion gate: warm tree catch-up through the
    REAL CatchupService serves pure tier-1 — ``cache_hit_rate`` 1.0 and
    ZERO bytes either way on exact hits — byte-identical to the cold
    fold."""
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService
    from fluidframework_tpu.tools.bench_harness import benchmark_cold_warm

    n_docs = int(os.environ.get(
        "BENCHK_CATCHUP_DOCS", str(min(TREE_DOCS, 2048))))
    service = LocalOrderingService()
    doc_ids = build_tree_catchup_corpus(service, n_docs, TREE_EDITS)
    svc = CatchupService(service)
    if svc.cache is None:
        print("catchup cache disabled by config gate; skipping tree "
              "cold/warm", file=sys.stderr)
        return {"catchup_docs": n_docs, "skipped": "cache-gate-off"}
    total_ops = n_docs * TREE_EDITS
    results = {}

    def fold():
        results["out"] = svc.catch_up(doc_ids, upload=False)

    before = svc.cache.counters.snapshot()
    pair = benchmark_cold_warm(fold, name="tree-catchup", warm_runs=2,
                               stage=svc.pipeline_stage)
    after = svc.cache.counters.snapshot()
    hit_rate = (after["hits"] - before["hits"]) \
        / max(1, n_docs * pair.warm_runs)
    assert hit_rate >= 1.0, f"tree warm catch-up hit rate {hit_rate}"
    assert pair.warm_h2d_bytes == 0 and pair.warm_d2h_bytes == 0, (
        f"tree warm hit moved bytes: h2d {pair.warm_h2d_bytes} "
        f"d2h {pair.warm_d2h_bytes}")
    print(f"tree catchup: {pair.report()} | hit rate {hit_rate:.3f}",
          file=sys.stderr)
    return {
        "catchup_docs": n_docs,
        "catchup_cold_ops_per_sec": round(total_ops / pair.cold_s, 1),
        "catchup_warm_ops_per_sec": round(total_ops / pair.warm_s, 1),
        "catchup_warm_speedup": round(pair.speedup, 1),
        "cache_hit_rate": round(hit_rate, 4),
        "catchup_warm_h2d_bytes": pair.warm_h2d_bytes,
        "catchup_warm_d2h_bytes": pair.warm_d2h_bytes,
        "catchup_cache": svc.cache.stats(),
        "tree_pack_cache": svc.tree_pack_cache.stats()
        if svc.tree_pack_cache is not None else None,
        "tree_device_cache": svc.tree_device_cache.stats()
        if svc.tree_device_cache is not None else None,
    }


def run_interval_stabbing() -> dict:
    """Cold → warm interval stabbing over 10k folded string docs with
    dense slide cascades, the merge-tree family's interval extraction
    path under the same schema."""
    from fluidframework_tpu.ops.device_cache import DevicePackCache
    from fluidframework_tpu.ops.pipeline import (
        PackCache,
        pipelined_mergetree_replay,
    )
    from fluidframework_tpu.service.catchup_cache import DeltaExportCache

    t0 = time.time()
    grow = max(2, IV_OPS // 8)
    base_docs = [synth_interval_doc(i, IV_OPS, n_msgs=IV_OPS - grow)
                 for i in range(IV_DOCS)]
    grown_idx = set(range(0, IV_DOCS, max(1, GROW_EVERY)))
    grown_docs = [
        synth_interval_doc(
            i, IV_OPS,
            n_msgs=IV_OPS if i in grown_idx else IV_OPS - grow)
        for i in range(IV_DOCS)
    ]
    gen_sec = time.time() - t0
    total_ops = sum(len(d.ops) for d in base_docs)
    iv_ops = sum(
        1 for d in base_docs for m in d.ops
        if m.contents["kind"].startswith("interval"))
    print(f"intervals: generated {IV_DOCS} docs ({iv_ops} interval ops) "
          f"in {gen_sec:.1f}s", file=sys.stderr)

    pack, dev, delta = PackCache(), DevicePackCache(), DeltaExportCache()
    caches = dict(pack_cache=pack, device_cache=dev, delta_cache=delta)
    cold_dig, cold = _one_pass(pipelined_mergetree_replay, base_docs,
                               total_ops, caches)
    warm_dig, warm = _one_pass(pipelined_mergetree_replay, base_docs,
                               total_ops, caches)
    assert warm_dig == cold_dig, "interval warm-exact changed bytes"
    grown_total = sum(len(d.ops) for d in grown_docs)
    grown_dig, grown = _one_pass(pipelined_mergetree_replay, grown_docs,
                                 grown_total, caches)
    off_dig, off = _one_pass(pipelined_mergetree_replay, grown_docs,
                             grown_total, {})
    assert off_dig == grown_dig, "interval caches-on != caches-off"
    delta.invalidate_epoch("other-epoch")
    dev.invalidate_epoch("other-epoch")
    inval_dig, inval = _one_pass(pipelined_mergetree_replay, grown_docs,
                                 grown_total, caches)
    assert inval_dig == grown_dig, \
        "interval post-invalidation bytes changed"

    from fluidframework_tpu.dds.sequence import SharedString

    t0 = time.time()
    n_checked = 0
    for i in range(0, IV_DOCS, max(1, ORACLE_EVERY)):
        replica = SharedString(grown_docs[i].doc_id)
        for m in grown_docs[i].ops:
            replica.process(m, local=False)
        replica.advance(grown_docs[i].final_seq, grown_docs[i].final_msn)
        assert replica.summarize().digest() == grown_dig[i], (
            f"interval doc {i} != SharedString oracle")
        n_checked += 1
    oracle_sec = time.time() - t0
    print(f"intervals: {n_checked} docs oracle-verified in "
          f"{oracle_sec:.1f}s", file=sys.stderr)

    return {
        "docs": IV_DOCS,
        "ops_per_doc": IV_OPS,
        "interval_ops": iv_ops,
        "grown_docs": len(grown_idx),
        "gen_sec": round(gen_sec, 1),
        "cold": cold,
        "warm_exact": warm,
        "warm_grown": grown,
        "caches_off": off,
        "post_invalidation": inval,
        "pack_cache": pack.stats(),
        "device_cache": dev.stats(),
        "delta_cache": delta.stats(),
        "oracle_checked_docs": n_checked,
        "oracle_every": ORACLE_EVERY,
    }


def _run(device: dict) -> dict:
    tree = run_tree_rebase()
    intervals = run_interval_stabbing()
    return {
        "metric": METRIC,
        "backend": device["platform"],
        "device_kind": device["device_kind"],
        "n_devices": device["n_devices"],
        "tree_rebase": tree,
        "interval_stabbing": intervals,
    }


def main() -> None:
    import bench
    from fluidframework_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    print(json.dumps(_run(bench.device_info())), flush=True)


if __name__ == "__main__":
    sys.exit(main())
