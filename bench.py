"""North-star benchmark: bulk SharedString catch-up replay, device vs oracle.

Workload per BASELINE.json: many documents' sequenced op tails folded to
summaries.  The CPU baseline is the oracle replay harness (BASELINE.md: the
1× denominator, pinned there — workload generator, oracle definition, and
the committed round-2 number); the device path is the merge-tree kernel
vmapped over the document axis on whatever backend jax selects (real TPU
under the driver).

The end-to-end path is the product pipeline (``ops/pipeline.py``): pack
and extract run on worker threads (C++, GIL released) and overlap with
device compute, while every device call — dispatch, async copy-to-host,
the trailing blocking fetch — stays on the calling thread.

Numbers reported:
- ``value`` / ``vs_baseline``: the HONEST END-TO-END rate — wall-clock from
  raw op streams to canonical summaries materialized host-side for every
  document, all stages included.
- ``steady_fold_ops_per_sec``: the device fold alone with device-resident
  inputs (uploaded once, compiled, export not fetched) — the rate a
  saturated device approaches.
- ``link``: an in-run microbenchmark of the host↔device link (per-RPC
  latency + MB/s each way) so the fold-vs-e2e gap is attributable.

Runs on whatever backend JAX selects; every result names its device.
Prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": ops/sec, "unit": "ops/sec", "vs_baseline": ratio,
     ...stage breakdown + link + fallback counts...}
Diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

import jax
import numpy as np

from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.ops.interning import Interner
from fluidframework_tpu.ops.mergetree_kernel import (
    MergeTreeDocInput,
    pack_mergetree_batch,
    replay_export,
    replay_mergetree_batch,
)
from fluidframework_tpu.ops.native_pack import (
    decode_string_ops,
    encode_string_ops,
    load_library,
)
from fluidframework_tpu.protocol.messages import MessageType, SequencedMessage

N_DOCS = int(os.environ.get("BENCH_DOCS", "10240"))
OPS_PER_DOC = int(os.environ.get("BENCH_OPS", "96"))
CPU_SAMPLE_DOCS = int(os.environ.get("BENCH_CPU_SAMPLE", "256"))
# Documents fold in fixed-size chunks: one compiled shape reused across
# dispatches, bounded per-transfer sizes, and the dispatch/compute balance
# measured best at 1024 docs/chunk on v5e (larger single batches degrade
# per-op throughput and >4k-doc transfers can trip device faults).
CHUNK_DOCS = int(os.environ.get("BENCH_CHUNK", "1024"))
PACK_THREADS = int(os.environ.get("BENCH_PACK_THREADS", "4"))
# Extraction parallelism: the C++ extractor runs under ctypes (GIL
# released for the foreign call), so chunks extract concurrently.  At the
# 50x target the serial extract stage alone (~1.7s busy at round-2 scale)
# would cap the pipeline below budget.
EXTRACT_THREADS = int(os.environ.get("BENCH_EXTRACT_THREADS", "3"))
ALPHABET = "abcdefghijklmnopqrstuvwxyz "


def synth_doc(doc_idx: int, n_ops: int) -> MergeTreeDocInput:
    """A valid sequenced op stream: 3 clients round-robin, mixed edits.
    70% of documents are pure insert/remove text traffic; 30% carry
    annotate ops with props.  ALL streams are ingested in the native binary
    record format (annotates ride encoder-local intern tables that packing
    translates to the batch-global spaces in C++).

    This generator is the PINNED workload of BASELINE.md config #1 — do not
    change its distribution without re-measuring the committed baseline."""
    rng = random.Random(doc_idx * 7919 + 13)
    annotating_doc = doc_idx % 10 >= 7
    ops, length = [], 0
    for i in range(n_ops):
        seq = i + 1
        client = f"client{i % 3}"
        r = rng.random()
        if not annotating_doc:
            r = min(r, 0.89)  # no annotates in pure-text docs
        if r < 0.62 or length < 4:
            pos = rng.randint(0, length)
            text = "".join(
                rng.choice(ALPHABET) for _ in range(rng.randint(1, 8))
            )
            contents = {"kind": "insert", "pos": pos, "text": text}
            length += len(text)
        elif r < 0.9:
            start = rng.randint(0, length - 2)
            end = min(length, start + rng.randint(1, 8))
            contents = {"kind": "remove", "start": start, "end": end}
            length -= end - start
        else:
            start = rng.randint(0, length - 2)
            end = min(length, start + rng.randint(1, 8))
            contents = {
                "kind": "annotate", "start": start, "end": end,
                "props": {"f": rng.randint(0, 3)},
            }
        ops.append(
            SequencedMessage(
                seq=seq, client_id=client, client_seq=seq, ref_seq=seq - 1,
                min_seq=0, type=MessageType.OP, contents=contents,
            )
        )
    clients, keys, vals = Interner(), Interner(), Interner()
    blob = encode_string_ops(ops, clients, keys, vals)
    return MergeTreeDocInput(
        doc_id=f"doc{doc_idx}", ops=[], binary_ops=blob,
        binary_clients=list(clients.values),
        binary_prop_keys=list(keys.values) or None,
        binary_values=list(vals.values) or None,
        final_seq=n_ops, final_msn=0,
    )


def doc_ops(doc):
    return decode_string_ops(
        doc.binary_ops, list(doc.binary_clients),
        prop_keys=doc.binary_prop_keys, values=doc.binary_values,
    )


def oracle_replay(doc):
    replica = SharedString(doc.doc_id)
    for msg in doc_ops(doc):
        replica.process(msg, local=False)
    return replica


METRIC_NAME = "sharedstring_catchup_replay_ops_per_sec"
# Service-shaped corpus for the catch-up cache cold/warm metric: smaller
# than the raw-stream e2e by default (it adds two full service folds to
# the run), overridable like the rest of the workload knobs.
CATCHUP_DOCS = int(os.environ.get(
    "BENCH_CATCHUP_DOCS", str(min(N_DOCS, 2048))))


def build_catchup_corpus(service, n_docs: int, ops_per_doc: int):
    """Seed ``service`` with ``n_docs`` single-string documents: an empty
    seeded summary at seq 0 plus the PINNED synth_doc op tail appended
    straight to the op log (each op wrapped in the groupedBatch container
    envelope the runtime emits) — the service-shaped twin of the bench
    corpus, cheap enough to build at full scale.  Returns the doc ids."""
    from fluidframework_tpu.runtime.container import ContainerRuntime

    seeded = ContainerRuntime()
    seeded.create_datastore("ds").create_channel("sequence-tpu", "text")
    seed_tree = seeded.summarize()
    doc_ids = []
    for i in range(n_docs):
        doc_id = f"cdoc{i}"
        service.storage.upload(doc_id, seed_tree, 0)
        for m in doc_ops(synth_doc(i, ops_per_doc)):
            service.oplog.append(doc_id, SequencedMessage(
                seq=m.seq, client_id=m.client_id, client_seq=m.client_seq,
                ref_seq=m.ref_seq, min_seq=m.min_seq, type=MessageType.OP,
                contents={"type": "groupedBatch", "ops": [
                    {"ds": "ds", "channel": "text",
                     "clientSeq": m.client_seq,
                     "contents": m.contents}]},
            ))
        doc_ids.append(doc_id)
    return doc_ids


def catchup_oracle_digest(service, doc_id: str) -> str:
    """CPU container fold of one corpus doc — the byte-identity oracle
    for the cached catch-up section."""
    from fluidframework_tpu.runtime.container import ContainerRuntime

    runtime = ContainerRuntime()
    summary, ref_seq = service.storage.latest(doc_id)
    runtime.load(summary)
    for msg in service.oplog.get(doc_id, from_seq=ref_seq):
        runtime.process(msg)
    return runtime.summarize().digest()


def run_catchup_cache_bench(n_docs: int, ops_per_doc: int) -> dict:
    """Steady-state re-catch-up: fold a service corpus twice through
    CatchupService and report cold vs warm rates plus cache health.  The
    warm pass must be pure tier-1 hits (zero pack/fold/extract) — the
    repeated-read serving shape the two-tier cache exists for."""
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.service.catchup import CatchupService
    from fluidframework_tpu.tools.bench_harness import benchmark_cold_warm

    service = LocalOrderingService()
    doc_ids = build_catchup_corpus(service, n_docs, ops_per_doc)
    svc = CatchupService(service)
    if svc.cache is None:
        # Operator disabled the gate (Catchup.Cache=off): the cold/warm
        # pair would measure nothing — keep the artifact schema stable
        # and say so instead of crashing the hardened bench.
        print("catchup cache disabled by config gate; skipping cold/warm",
              file=sys.stderr)
        return {
            "catchup_docs": n_docs,
            "catchup_cold_ops_per_sec": None,
            "catchup_warm_ops_per_sec": None,
            "catchup_warm_speedup": None,
            "cache_hit_rate": None,
            "catchup_cache": None,
            "pack_cache": None,
            "delta_cache": None,
            "device_cache": None,
            "catchup_stages_busy_sec": {},
            "catchup_d2h_bytes": None,
            "catchup_cold_d2h_bytes": None,
            "catchup_warm_d2h_bytes": None,
            "catchup_h2d_bytes": None,
            "catchup_cold_h2d_bytes": None,
            "catchup_warm_h2d_bytes": None,
        }
    total_ops = n_docs * ops_per_doc

    results = {}

    def fold():
        results["out"] = svc.catch_up(doc_ids, upload=False)

    before = svc.cache.counters.snapshot()
    pair = benchmark_cold_warm(fold, name="catchup", warm_runs=2,
                               stage=svc.pipeline_stage)
    after = svc.cache.counters.snapshot()
    warm_lookups = n_docs * pair.warm_runs
    hit_rate = (after["hits"] - before["hits"]) / max(1, warm_lookups)

    # Byte identity: the warm (cached) result equals the cold fold AND
    # the CPU container oracle on sampled docs.
    sample = [doc_ids[0], doc_ids[len(doc_ids) // 2], doc_ids[-1]]
    for doc_id in sample:
        handle, _seq = results["out"][doc_id]
        assert handle == catchup_oracle_digest(service, doc_id), (
            f"catchup cache: {doc_id} cached fold != container oracle"
        )
    out = {
        "catchup_docs": n_docs,
        "catchup_cold_ops_per_sec": round(total_ops / pair.cold_s, 1),
        "catchup_warm_ops_per_sec": round(total_ops / pair.warm_s, 1),
        "catchup_warm_speedup": round(pair.speedup, 1),
        "cache_hit_rate": round(hit_rate, 4),
        "catchup_cache": svc.cache.stats(),
        "pack_cache": (svc._pack_cache.stats()
                       if svc._pack_cache is not None else None),
        "delta_cache": (svc.delta_cache.stats()
                        if svc.delta_cache is not None else None),
        "device_cache": (svc.device_cache.stats()
                         if svc.device_cache is not None else None),
        "catchup_stages_busy_sec": {
            k: round(v, 3) for k, v in sorted(svc.pipeline_stage.items())
            if k not in ("d2h_bytes", "h2d_bytes")
        },
        "catchup_d2h_bytes": int(svc.pipeline_stage.get("d2h_bytes", 0)),
        # Warm tier-1 hits never reach the pipeline: warm bytes must be 0
        # each way.
        "catchup_cold_d2h_bytes": pair.cold_d2h_bytes,
        "catchup_warm_d2h_bytes": pair.warm_d2h_bytes,
        "catchup_h2d_bytes": int(svc.pipeline_stage.get("h2d_bytes", 0)),
        "catchup_cold_h2d_bytes": pair.cold_h2d_bytes,
        "catchup_warm_h2d_bytes": pair.warm_h2d_bytes,
    }
    print(f"catchup cache: {pair.report()} | hit rate {hit_rate:.3f}",
          file=sys.stderr)
    return out


# Delta-download (tier 0) workload knobs: a full-scale corpus whose tails
# grow on a fraction of documents between the cold fill and the warm
# re-fold — the steady maintenance shape where corpus size >> churn.
DELTA_DOCS = int(os.environ.get("BENCH_DELTA_DOCS", str(N_DOCS)))
DELTA_GROW_EVERY = int(os.environ.get("BENCH_DELTA_GROW_EVERY", "8"))


def run_delta_download_bench(n_docs: int, ops_per_doc: int) -> dict:
    """Warm grown-tail maintenance at full scale, BOTH link directions
    (ISSUE 6 d2h + ISSUE 13 h2d): fold a tokened message-list corpus
    cold (tiers 0/2/2.5 fill), grow every Nth document's tail, then
    re-fold warm twice — once with the cache stack ON (digest plane +
    changed rows only cross d2h; resident buffers + donated suffix
    splices keep the upload to the new rows) and once with it OFF (the
    full-transfer reference) — asserting the two runs are byte-identical
    and reporting the byte and busy-second drop each way."""
    from fluidframework_tpu.ops.device_cache import DevicePackCache
    from fluidframework_tpu.ops.pipeline import (
        PackCache,
        pipelined_mergetree_replay,
    )
    from fluidframework_tpu.service.catchup_cache import DeltaExportCache

    base_ops = max(2, (ops_per_doc * 5) // 6)
    streams = [doc_ops(synth_doc(i, ops_per_doc)) for i in range(n_docs)]

    def window(i, n_ops):
        msgs = streams[i][:n_ops]
        return MergeTreeDocInput(
            doc_id=f"ddoc{i}", ops=msgs, final_seq=msgs[-1].seq,
            final_msn=0, cache_token=("bench-epoch", f"ddoc{i}", 0, ""),
        )

    docs_base = [window(i, base_ops) for i in range(n_docs)]
    grown_idx = set(range(0, n_docs, max(1, DELTA_GROW_EVERY)))
    docs_grown = [
        window(i, ops_per_doc if i in grown_idx else base_ops)
        for i in range(n_docs)
    ]

    def one_pass(docs, delta_cache, pack_cache, device_cache=None):
        stage = {"d2h_bytes": 0, "h2d_bytes": 0}
        stats: dict = {}
        t0 = time.time()
        summaries = pipelined_mergetree_replay(
            docs, chunk_docs=CHUNK_DOCS, pack_threads=PACK_THREADS,
            extract_threads=EXTRACT_THREADS, stage=stage, stats=stats,
            delta_cache=delta_cache, pack_cache=pack_cache,
            device_cache=device_cache,
        )
        return summaries, stage, stats, time.time() - t0

    # BOTH warm runs ride an identically-warmed pack cache, so the fold
    # configuration (suffix-extended packs — whose arena-tail offsets
    # legitimately force the wide export layout at full scale) is the
    # same and ONLY the transfer policy differs; the reference would
    # otherwise fresh-pack narrow and the byte comparison would measure
    # the transfer encoding, not the cache tiers.
    delta, pack, dev = DeltaExportCache(), PackCache(), DevicePackCache()
    full_pack = PackCache()
    _cold, stage_cold, _st, cold_wall = one_pass(docs_base, delta, pack,
                                                 dev)
    one_pass(docs_base, None, full_pack)
    warm, stage_delta, stats_delta, delta_wall = one_pass(
        docs_grown, delta, pack, dev)
    full, stage_full, _st2, full_wall = one_pass(
        docs_grown, None, full_pack)
    assert [s.digest() for s in warm] == [s.digest() for s in full], (
        "delta-download summaries != full-download summaries"
    )
    reduction = stage_full["d2h_bytes"] / max(1, stage_delta["d2h_bytes"])
    h2d_reduction = stage_full["h2d_bytes"] / max(
        1, stage_delta["h2d_bytes"])
    out = {
        "delta_docs_total": n_docs,
        "delta_docs_grown": len(grown_idx),
        "delta_base_ops": base_ops,
        "delta_d2h_bytes_full": int(stage_full["d2h_bytes"]),
        "delta_d2h_bytes_delta": int(stage_delta["d2h_bytes"]),
        "delta_d2h_reduction": round(reduction, 2),
        # The upload mirror (tier 2.5): full re-upload vs resident
        # buffers + donated suffix splices on the same warm corpus.
        "resident_h2d_bytes_full": int(stage_full["h2d_bytes"]),
        "resident_h2d_bytes_delta": int(stage_delta["h2d_bytes"]),
        "resident_h2d_reduction": round(h2d_reduction, 2),
        "delta_docs_served": stats_delta.get("delta_docs", 0),
        "delta_warm_wall_sec": round(delta_wall, 3),
        "delta_full_wall_sec": round(full_wall, 3),
        "delta_cold_wall_sec": round(cold_wall, 3),
        "delta_stages_busy_sec": {
            k: round(v, 3) for k, v in sorted(stage_delta.items())
            if k not in ("d2h_bytes", "h2d_bytes")
        },
        "delta_full_stages_busy_sec": {
            k: round(v, 3) for k, v in sorted(stage_full.items())
            if k not in ("d2h_bytes", "h2d_bytes")
        },
        "delta_cache_stats": delta.stats(),
        "device_cache_stats": dev.stats(),
    }
    print(
        f"delta download: d2h {stage_full['d2h_bytes']/1e6:.1f} MB full "
        f"-> {stage_delta['d2h_bytes']/1e6:.2f} MB delta "
        f"({reduction:.1f}x less), {stats_delta.get('delta_docs', 0)}"
        f"/{n_docs} docs served without download | resident upload: h2d "
        f"{stage_full['h2d_bytes']/1e6:.1f} MB full -> "
        f"{stage_delta['h2d_bytes']/1e6:.2f} MB "
        f"({h2d_reduction:.1f}x less)",
        file=sys.stderr,
    )
    return out


def device_info() -> dict:
    """The device every result line names (platform, kind, count)."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind.replace(" ", "_"),
        "n_devices": len(devices),
    }


# Peak single-chip HBM bandwidth by device kind (GB/s), for the roofline.
# Source: public TPU spec sheets (Google Cloud documentation, one page per
# generation; "TPU v5e": 819 GB/s).  Keys are ``device_kind`` with spaces
# as underscores — JAX reports a v5e as "TPU v5 lite".  A kind missing
# here is an error, never a default.
HBM_GBPS = {
    "TPU_v4": 1228.0,
    "TPU_v5_lite": 819.0,
    "TPU_v5e": 819.0,
    "TPU_v5p": 2765.0,
    "TPU_v5": 2765.0,
    "TPU_v6_lite": 1640.0,
    "TPU_v6e": 1640.0,
}


def roofline(S: int, K: int, device_kind: str) -> dict:
    """HBM roofline for the merge-tree fold (VERDICT r3 item 5).

    The scan's carried state per document is 12 int32 [S] columns plus an
    [S, K] int32 props plane; each scan step (one applied op per doc under
    vmap) must stream that state out of HBM and write it back at least
    once — the op row itself is negligible.  So the OPTIMISTIC (perfect
    XLA fusion into one read + one write pass per step) bytes-per-op is

        bytes_per_op = 2 * S * (12 + K) * 4

    and the bandwidth-bound rate is HBM_GBps / bytes_per_op.  The real
    kernel makes several masked passes per step (two boundary splits each
    shuffling every column, the visible-length prefix sums, the stamp
    selects), so measured/bound below ~30% can still mean "fused about as
    well as the pass structure allows"; the number's job is to separate a
    kernel-shaped problem (low pct AND healthy link) from a link-shaped
    one (VERDICT r3: 'fast or just correct' must be answerable)."""
    if device_kind not in HBM_GBPS:
        raise KeyError(f"no HBM peak on record for device kind "
                       f"{device_kind!r}; add it to bench.HBM_GBPS with "
                       f"its source")
    gbps = HBM_GBPS[device_kind]
    bytes_per_op = 2 * S * (12 + K) * 4
    return {
        "S": S,
        "props_plane_K": K,
        "bytes_per_op_optimistic": bytes_per_op,
        "hbm_GBps": gbps,
        "device_kind": device_kind,
        "bound_ops_per_sec": round(gbps * 1e9 / bytes_per_op, 1),
    }


def exec_latency_probe() -> float:
    """Best-of-3 trivial-program round trip, re-run AFTER the e2e: a
    rise over the pre-e2e value says the e2e left the device client
    slower per call (round 5 saw 0.1 ms → 70-90 ms after the legacy
    pipeline's concurrent fetch+dispatch)."""
    tiny = jax.jit(lambda x: x * 2)
    h = jax.device_put(np.zeros((1,), np.int32))
    jax.block_until_ready(tiny(h))  # compile/warm outside the timing
    lat = float("inf")
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(tiny(h))
        lat = min(lat, time.time() - t0)
    return lat


def link_microbench() -> dict:
    """Measure the host↔device link in-run: per-RPC latency (best of 3
    one-element round trips) and MB/s each way on a 16MB default-layout
    buffer.  Bandwidth subtracts the latency floor but never more than 80%
    of the measured transfer time, so a jittery latency sample cannot
    inflate MB/s to absurdity."""
    small = np.zeros((1,), np.int32)
    big = np.zeros((4 << 20,), np.int32)  # 16 MiB
    np.asarray(jax.device_put(small))  # warm the path
    lat_up = lat_down = float("inf")
    for _ in range(3):
        t0 = time.time()
        h = jax.device_put(small)
        jax.block_until_ready(h)
        lat_up = min(lat_up, time.time() - t0)
        t0 = time.time()
        np.asarray(h)
        lat_down = min(lat_down, time.time() - t0)
    # Trivial-program execution latency: separates a sick COMPUTE path
    # (dispatch/executor degradation) from a sick TRANSFER path when the
    # fold rate collapses — without this the two are indistinguishable in
    # the stage breakdown.
    lat_exec = exec_latency_probe()
    t0 = time.time()
    hb = jax.device_put(big)
    jax.block_until_ready(hb)
    up = time.time() - t0
    t0 = time.time()
    np.asarray(hb)
    down = time.time() - t0
    mb = big.nbytes / 1e6
    return {
        "rpc_latency_up_s": round(lat_up, 4),
        "rpc_latency_down_s": round(lat_down, 4),
        "exec_latency_s": round(lat_exec, 6),
        "h2d_MBps": round(mb / max(up - lat_up, up * 0.2, 1e-9), 1),
        "d2h_MBps": round(mb / max(down - lat_down, down * 0.2, 1e-9), 1),
    }


def run_e2e(docs):
    """Pipelined end-to-end: returns
    (summaries, stats, stage_times, wall, packed_chunks).

    Runs the PRODUCT pipeline (fluidframework_tpu.ops.pipeline) with the
    bench's instrumentation hooks attached — the harness measures the
    same code the catch-up service runs, not a private copy of it.
    Stage times are per-stage BUSY seconds (they overlap); ``wall`` is the
    honest end-to-end wall-clock the throughput number uses.
    ``packed_chunks`` [(state_or_None, ops, meta, S)] lets the
    steady-fold section reuse the pack work (warm chunks keep their base
    state so the re-timed fold runs the e2e's own executable)."""
    from fluidframework_tpu.ops.pipeline import pipelined_mergetree_replay

    stage = {"pack": 0.0, "dispatch": 0.0, "upload": 0.0,
             "device_wait": 0.0, "download": 0.0, "extract": 0.0,
             "d2h_bytes": 0, "h2d_bytes": 0}
    packed_chunks: list = []
    stats: dict = {}
    wall0 = time.time()
    summaries = pipelined_mergetree_replay(
        docs,
        chunk_docs=CHUNK_DOCS,
        pack_threads=PACK_THREADS,
        extract_threads=EXTRACT_THREADS,
        fetch_depth=int(os.environ.get("BENCH_FETCH_DEPTH", "2")),
        schedule=True,
        stats=stats,
        stage=stage,
        packed_out=packed_chunks,
    )
    return summaries, stats, stage, time.time() - wall0, packed_chunks


def main() -> None:
    """Prints the one result line; any failure propagates with its
    traceback and a non-zero exit."""
    from fluidframework_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    print(json.dumps(_run_bench(device_info())), flush=True)


def _run_bench(device: dict) -> dict:
    t0 = time.time()
    docs = [synth_doc(d, OPS_PER_DOC) for d in range(N_DOCS)]
    total_ops = N_DOCS * OPS_PER_DOC
    print(
        f"generated {N_DOCS} docs x {OPS_PER_DOC} ops in {time.time()-t0:.1f}s "
        f"(backend={jax.default_backend()}, "
        f"native={'yes' if load_library() is not None else 'NO'})",
        file=sys.stderr,
    )

    # --- CPU oracle baseline (the 1x denominator; definition pinned in
    # BASELINE.md: per-op SharedString.process over the same streams) ---
    t0 = time.time()
    for doc in docs[:CPU_SAMPLE_DOCS]:
        oracle_replay(doc)
    cpu_time = time.time() - t0
    cpu_ops_per_sec = CPU_SAMPLE_DOCS * OPS_PER_DOC / cpu_time
    print(
        f"cpu oracle: {CPU_SAMPLE_DOCS * OPS_PER_DOC} ops in {cpu_time:.2f}s "
        f"= {cpu_ops_per_sec:,.0f} ops/s",
        file=sys.stderr,
    )

    # --- link microbenchmark (attributes the fold-vs-e2e gap) ---
    link = link_microbench()
    print(f"link: {link}", file=sys.stderr)

    # --- fact-homogeneous chunk schedule: group annotate-free docs
    # together so their chunks fold with the props plane traced away
    # (has_props chunk fact, ~20% fold speedup on the 70% pure-text
    # volume).  A service-side BATCHING choice, not a workload change —
    # the oracle denominator above sampled the original pinned order.
    docs_sched = sorted(docs, key=lambda d: d.binary_prop_keys is not None)

    # --- warm the compile cache outside the timed run (a fresh process
    # pays XLA compilation once; steady service operation does not).
    # Warm slices are ALIGNED TO THE E2E CHUNK GRID and cover every fact
    # signature the schedule can produce: the first chunk (props-free
    # majority), the group-boundary chunk (mixed when the pure count is
    # not a chunk multiple — without warming it, its executable would
    # compile INSIDE the timed e2e), and the last chunk (props group).
    starts = list(range(0, len(docs_sched), CHUNK_DOCS))
    n_pure = sum(1 for d in docs_sched if d.binary_prop_keys is None)
    boundary = min((n_pure // CHUNK_DOCS) * CHUNK_DOCS, starts[-1])
    S = None
    roof_k_eff = roof_group = None
    for lo in sorted({0, boundary, starts[-1]}):
        warm_docs = docs_sched[lo:lo + CHUNK_DOCS]
        warm_state, warm_ops, warm_meta = pack_mergetree_batch(warm_docs)
        s_warm = warm_state.tstart.shape[1]
        if S is None:
            # Roofline pins the FIRST chunk's shape — the majority group
            # (props-free chunks stream no props plane: effective K = 0).
            S = s_warm
            carried = bool(warm_meta.get("has_props", True))
            roof_k_eff = int(warm_state.props.shape[-1]) if carried else 0
            roof_group = "props-carried" if carried else "props-free"
        t0 = time.time()
        jax.block_until_ready(
            replay_export(None, warm_ops, warm_meta, S=s_warm)
        )
        warm_time = time.time() - t0
        print(
            f"compile+first fold {warm_time:.1f}s "
            f"(chunk@{lo}, S={s_warm}, "
            f"i16={'yes' if warm_meta['i16_ok'] else 'no'}, "
            f"i8={'yes' if warm_meta.get('i8_ok') else 'no'}, "
            f"ob_rows={'yes' if warm_meta.get('ob_rows', True) else 'ELIDED'}, "
            f"ov_slots={warm_meta.get('ov_slots', 1)}, "
            f"props={'carried' if warm_meta.get('has_props', True) else 'ELIDED'})",
            file=sys.stderr,
        )

    # --- HONEST END-TO-END: raw streams → host-side canonical summaries,
    # stages pipelined (see run_e2e) ---
    summaries, stats, stage, e2e_time, packed_chunks = run_e2e(docs_sched)
    # Did the e2e leave the device client slower per call?
    link["exec_latency_after_e2e_s"] = round(exec_latency_probe(), 6)
    assert len(summaries) == N_DOCS
    e2e_ops_per_sec = total_ops / e2e_time
    fallbacks = stats.get("fallback_docs", 0)
    print(
        f"end-to-end {e2e_time:.2f}s = {e2e_ops_per_sec:,.0f} ops/s "
        f"(busy: pack {stage['pack']:.2f} | dispatch {stage['dispatch']:.2f}"
        f" | upload {stage.get('upload', 0.0):.2f}"
        f" | device_wait {stage['device_wait']:.2f}"
        f" | download {stage['download']:.2f} | extract+summarize "
        f"{stage['extract']:.2f} | h2d {stage['h2d_bytes']/1e6:.1f} MB"
        f" | d2h {stage['d2h_bytes']/1e6:.1f} MB)"
        f" | oracle fallbacks {fallbacks}/{N_DOCS}",
        file=sys.stderr,
    )

    # --- steady-state device fold: inputs uploaded once (device-resident,
    # reusing the e2e run's pack work), export computed but not fetched —
    # the saturated-device rate ---
    from fluidframework_tpu.ops.mergetree_kernel import narrow_ops_for_upload

    resident = []
    upload_bytes = 0
    for chunk_state, ops, meta, s in packed_chunks:
        ops_n = narrow_ops_for_upload(ops, meta)  # same stream e2e uploads
        upload_bytes += sum(np.asarray(x).nbytes for x in ops_n)
        ops_dev = jax.device_put(ops_n)
        jax.block_until_ready(ops_dev)
        # Warm chunks re-time with their base state resident too — the
        # SAME executable the e2e dispatched, not a cold rebuild.
        state_dev = None
        if chunk_state is not None:
            state_dev = jax.device_put(chunk_state)
            jax.block_until_ready(state_dev)
            upload_bytes += sum(
                np.asarray(x).nbytes for x in chunk_state)
        resident.append((state_dev, ops_dev, meta, s))
    print(
        f"op-stream upload (narrowed where i16_ok): "
        f"{upload_bytes / 1e6:.1f} MB",
        file=sys.stderr,
    )
    fold_time = float("inf")
    for _rep in range(3):
        t0 = time.time()
        finals = [
            replay_export(state_dev, ops_dev, meta, S=s)
            for state_dev, ops_dev, meta, s in resident
        ]
        for final in finals:
            jax.block_until_ready(final)
        fold_time = min(fold_time, time.time() - t0)
    fold_ops_per_sec = total_ops / fold_time
    print(
        f"steady fold {fold_time:.3f}s = {fold_ops_per_sec:,.0f} ops/s "
        f"(device-resident inputs, export not fetched)",
        file=sys.stderr,
    )

    # --- HBM roofline: is the fold fast, or just correct? (only
    # meaningful on a real TPU; the cpu backend has no pinned HBM figure)
    roof = None
    if device["platform"] == "tpu":
        # (S, K) pinned together from the FIRST warm chunk — the majority
        # fact-group — so the bound describes a configuration that really
        # executes (K is the PADDED carried width; 0 when the props plane
        # is traced away on props-free chunks).
        roof = roofline(S, roof_k_eff, device["device_kind"])
        roof["group"] = roof_group
        roof["steady_fold_pct_of_bound"] = round(
            100.0 * fold_ops_per_sec / roof["bound_ops_per_sec"], 2
        )
        print(f"roofline: {roof}", file=sys.stderr)

    # --- sanity: device bytes == oracle bytes on sampled docs ---
    sample = [docs[0], docs[7], docs[N_DOCS // 2]]
    for doc, dev_summary in zip(sample, replay_mergetree_batch(sample)):
        assert dev_summary.digest() == oracle_replay(doc).summarize().digest(), (
            f"bench sanity: {doc.doc_id} device summary != oracle"
        )
    # and against the end-to-end pipeline output (chunk-scheduled order)
    assert summaries[0].digest() == \
        oracle_replay(docs_sched[0]).summarize().digest()
    assert summaries[-1].digest() == \
        oracle_replay(docs_sched[-1]).summarize().digest()
    print("sanity: device summaries byte-identical to oracle", file=sys.stderr)

    # --- steady-state re-catch-up (the serving shape): the same corpus
    # folded twice through the SERVICE path — cold pays pack+fold+extract,
    # warm must serve from the seq-anchored cache with zero device work.
    catchup = run_catchup_cache_bench(CATCHUP_DOCS, OPS_PER_DOC)

    # --- digest-gated delta download (tier 0): the warm grown-tail
    # maintenance shape — corpus size >> churn, so d2h must scale with
    # what CHANGED, not with the corpus.
    delta = run_delta_download_bench(DELTA_DOCS, OPS_PER_DOC)

    return {
        "metric": METRIC_NAME,
        "backend": device["platform"],
        "device_kind": device["device_kind"],
        "n_devices": device["n_devices"],
        "value": round(e2e_ops_per_sec, 1),
        "unit": "ops/sec",
        "vs_baseline": round(e2e_ops_per_sec / cpu_ops_per_sec, 2),
        "steady_fold_ops_per_sec": round(fold_ops_per_sec, 1),
        "steady_fold_vs_baseline": round(
            fold_ops_per_sec / cpu_ops_per_sec, 2
        ),
        "cpu_baseline_ops_per_sec": round(cpu_ops_per_sec, 1),
        "roofline": roof,
        "link": link,
        "stages_busy_sec": {
            "pack": round(stage["pack"], 3),
            "fold_dispatch": round(stage["dispatch"], 3),
            # Explicit resident-tier transfers only; without the tier
            # the upload rides the dispatch jit (and h2d_bytes still
            # counts the host arrays it pushes).
            "upload": round(stage.get("upload", 0.0), 3),
            # "download" used to absorb the async fold wait (CPU d2h is
            # hundreds of GB/s yet "download" read as 12 s in r05c);
            # device_wait now carries the wait, download the copy alone.
            "device_wait": round(stage["device_wait"], 3),
            "download": round(stage["download"], 3),
            "extract_summarize": round(stage["extract"], 3),
        },
        "d2h_bytes": int(stage["d2h_bytes"]),
        "h2d_bytes": int(stage["h2d_bytes"]),
        "end_to_end_sec": round(e2e_time, 3),
        "oracle_fallback_docs": fallbacks,
        **catchup,
        **delta,
        "op_upload_MB": round(upload_bytes / 1e6, 1),
        "n_docs": N_DOCS,
        "ops_per_doc": OPS_PER_DOC,
    }


if __name__ == "__main__":
    main()
