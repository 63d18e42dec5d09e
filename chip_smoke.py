"""Chip smoke: the served catch-up fold, end to end, on a TPU.

The quickest proof that the system still starts on the chip.  It drives
the main path once through the entry points a client uses, at BASELINE.json
config #1's full size (10,240 documents x 96 ops of ``bench.synth_doc``):

1. ``catchup`` — an in-process ``OrderingServer`` over a
   ``LocalOrderingService`` seeded with the corpus; a client asks for
   catch-up over TCP in batches (``NetworkDocumentServiceFactory``), then
   repeats one batch warm.  Every answer must say it folded on the TPU
   (``platform``), with ``cpuDocs == 0``, and a sample of summaries must
   equal the CPU container oracle (``bench.catchup_oracle_digest``).
2. ``kernels`` — a small corpus through each other device kernel (map,
   matrix, tree), through the batch entry points ``CatchupService`` calls,
   each summary checked against its DDS oracle.

``--chips 4`` runs only the mesh path: the same corpus through
``CatchupService`` on the auto 4-device doc mesh, against the same corpus
on one device (``Catchup.Mesh`` off) and the oracle sample.

Earlier lines are set-up information: wall and compile seconds are not
speed metrics.  The last stdout line is the one JSON result; any failure
exits non-zero before it, as does a run where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: BASELINE.json config #1
CORPUS_DOCS = 10240
OPS_PER_DOC = 96
BATCH_DOCS = 2048
#: per-kernel corpus of the short phase, at tools/bench_configs.py's
#: per-doc sizes
KERNEL_DOCS = 256
KERNEL_OPS = {"map": 96, "matrix": 64, "tree": 48}
ORACLE_SAMPLE = 64

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Backend compiles, their seconds, and persistent-cache hits, as JAX
    reports them (``jax.monitoring``), while the meter is entered."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_sec = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.compile_sec += duration_secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_sec, self.cache_hits


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter, report: dict):
    """Record one phase's wall seconds and the compiles inside it."""
    c0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    c1, s1, h1 = meter.snapshot()
    report[name] = {"wall_sec": wall, "compiles": c1 - c0,
                    "compile_sec": s1 - s0, "cache_hits": h1 - h0}
    log(f"phase {name}: wall {wall}s, {c1 - c0} backend compiles taking "
        f"{s1 - s0}s, {h1 - h0} persistent-cache hits "
        f"(set-up information, not speed)")


def _log_compile_total(meter: CompileMeter) -> None:
    compiles, seconds, hits = meter.snapshot()
    log(f"compile total: {compiles} backend compiles taking {seconds}s, "
        f"{hits} persistent-cache hits (set-up information)")


def _sample(doc_ids, k: int) -> list:
    """At least ``k`` documents spread evenly, the first and last
    included."""
    n = len(doc_ids)
    picks = {0, n - 1} | {i * (n - 1) // max(1, k - 1) for i in range(k)}
    return [doc_ids[i] for i in sorted(picks)]


def _seed_corpus(n_docs: int, ops_per_doc: int, sample: int):
    """(service, doc ids, oracle digests of the sample), the oracle taken
    before any catch-up moves the stored summaries."""
    import bench
    from fluidframework_tpu.service import LocalOrderingService

    service = LocalOrderingService()
    doc_ids = bench.build_catchup_corpus(service, n_docs, ops_per_doc)
    oracle = {d: bench.catchup_oracle_digest(service, d)
              for d in _sample(doc_ids, sample)}
    return service, doc_ids, oracle


def _check_oracle(results: dict, oracle: dict, what: str) -> None:
    wrong = [d for d, want in oracle.items() if results[d][0] != want]
    if wrong:
        raise AssertionError(f"{what}: {len(wrong)}/{len(oracle)} sampled "
                             f"summaries differ from the oracle: "
                             f"{wrong[:5]}")


def _check_answer(answer: dict, batch: list, platform: str,
                  lane: str) -> None:
    """One catch-up RPC answer: every document served, on ``platform``,
    nothing folded on the CPU container path."""
    if answer["platform"] != platform:
        raise AssertionError(f"catch-up folded on {answer['platform']!r}, "
                             f"not {platform!r}")
    if answer["lane"] != lane or answer["skipped"]:
        raise AssertionError(f"lane {answer['lane']!r} (want {lane!r}), "
                             f"skipped {answer['skipped'][:5]}")
    if sorted(answer["docs"]) != sorted(batch):
        raise AssertionError("catch-up answer does not cover the batch")
    if answer["cpuDocs"] != 0:
        raise AssertionError(f"cpuDocs = {answer['cpuDocs']}")
    if lane == "fold" and answer["deviceDocs"] != len(batch):
        raise AssertionError(f"deviceDocs {answer['deviceDocs']} + cpuDocs "
                             f"0 != {len(batch)} documents asked for")


def run_catchup_phase(n_docs: int, ops_per_doc: int, batch_docs: int,
                      sample: int, platform: str, meter: CompileMeter,
                      report: dict) -> dict:
    """The served path: seed, then catch up over TCP in batches, then one
    batch again warm."""
    from fluidframework_tpu.drivers.network_driver import (
        NetworkDocumentServiceFactory,
    )
    from fluidframework_tpu.service.server import OrderingServer

    with phase("seed", meter, report):
        service, doc_ids, oracle = _seed_corpus(n_docs, ops_per_doc, sample)
    log(f"corpus: {len(doc_ids)} docs x {ops_per_doc} ops, oracle sample "
        f"{len(oracle)} docs")
    srv = OrderingServer(service, port=0)
    srv.start_in_thread()
    factory = NetworkDocumentServiceFactory(port=srv.port)
    batches = [doc_ids[i:i + batch_docs]
               for i in range(0, len(doc_ids), batch_docs)]
    results: dict = {}
    platforms = set()
    device_docs = fallback_channels = 0
    try:
        for k, batch in enumerate(batches):
            with phase(f"catchup-cold-{k}", meter, report):
                answer = factory._rpc.request("catchup", {"docs": batch},
                                              timeout=900)
            _check_answer(answer, batch, platform, "fold")
            platforms.add(answer["platform"])
            device_docs += answer["deviceDocs"]
            fallback_channels += answer["fallbackChannels"]
            results.update(answer["docs"])
            log(f"batch {k}: {len(batch)} docs, deviceDocs "
                f"{answer['deviceDocs']}, cpuDocs {answer['cpuDocs']}, "
                f"fallbackChannels {answer['fallbackChannels']}, "
                f"platform {answer['platform']}")
        with phase("catchup-warm-0", meter, report):
            warm = factory._rpc.request("catchup", {"docs": batches[0]},
                                        timeout=900)
        _check_answer(warm, batches[0], platform, "warm")
        platforms.add(warm["platform"])
        if any(warm["docs"][d] != results[d] for d in batches[0]):
            raise AssertionError("warm answer differs from the cold fold")
        log(f"warm batch 0: {len(warm['docs'])} docs served, lane "
            f"{warm['lane']}, platform {warm['platform']}")
    finally:
        factory.close()
        asyncio.run_coroutine_threadsafe(
            srv.drain_and_seal(timeout=10), srv.loop).result(timeout=30)
    stages = srv._catchup.pipeline_stage
    log("pipeline busy seconds over the cold batches (set-up information): "
        + ", ".join(f"{k} {v}" for k, v in sorted(stages.items())))
    log(f"catch-up total: deviceDocs {device_docs} + cpuDocs 0 = "
        f"{device_docs} of {len(doc_ids)} docs asked for, "
        f"fallbackChannels {fallback_channels}, platforms "
        f"{sorted(platforms)}")
    _check_oracle(results, oracle, "served catch-up")
    log(f"oracle: {len(oracle)} sampled summaries byte-identical "
        f"(first {doc_ids[0]}, last {doc_ids[-1]})")
    return {"docs": len(doc_ids), "deviceDocs": device_docs, "cpuDocs": 0,
            "fallbackChannels": fallback_channels,
            "platforms": sorted(platforms), "oracle_sample": len(oracle)}


def run_kernel_phase(n_docs: int, meter: CompileMeter,
                     report: dict) -> dict:
    """Map, matrix and tree folds through their batch entry points, every
    summary against its DDS oracle."""
    from fluidframework_tpu.ops.map_kernel import replay_map_batch
    from fluidframework_tpu.ops.matrix_kernel import replay_matrix_batch
    from fluidframework_tpu.ops.tree_kernel import replay_tree_batch
    from tools import bench_configs as cfg

    kernels = {
        "map": (cfg.gen_map_doc, replay_map_batch, cfg.oracle_map),
        "matrix": (cfg.gen_matrix_doc, replay_matrix_batch,
                   cfg.oracle_matrix),
        "tree": (cfg.gen_tree_doc, replay_tree_batch, cfg.oracle_tree),
    }
    out = {}
    for name, (gen, fold, oracle) in kernels.items():
        docs = [gen(i, KERNEL_OPS[name]) for i in range(n_docs)]
        stats: dict = {}
        with phase(f"kernel-{name}", meter, report):
            summaries = fold(docs, stats=stats)
        wrong = [d.doc_id for d, s in zip(docs, summaries)
                 if s.digest() != oracle(d).digest()]
        if wrong or len(summaries) != len(docs):
            raise AssertionError(f"{name}: {len(wrong)}/{len(docs)} "
                                 f"summaries differ from the oracle")
        if not stats.get("device_docs"):
            raise AssertionError(f"{name}: no document folded on the "
                                 f"device ({stats})")
        out[name] = {"docs": len(docs),
                     "device_docs": stats.get("device_docs", 0),
                     "fallback_docs": stats.get("fallback_docs", 0)}
        log(f"kernel {name}: {len(docs)} docs byte-identical to the "
            f"oracle, device_docs {out[name]['device_docs']}, "
            f"fallback_docs {out[name]['fallback_docs']}")
    return out


def run_smoke(n_docs: int = CORPUS_DOCS, ops_per_doc: int = OPS_PER_DOC,
              batch_docs: int = BATCH_DOCS, kernel_docs: int = KERNEL_DOCS,
              sample: int = ORACLE_SAMPLE, platform: str = "tpu") -> dict:
    """The one-chip smoke at the given sizes; raises on any failure."""
    from fluidframework_tpu.ops.native_pack import load_library

    if load_library() is None:
        raise RuntimeError("native packer (native/oppack.cpp) did not "
                           "build or load")
    log("native packer: loaded")
    report: dict = {}
    with CompileMeter() as meter:
        served = run_catchup_phase(n_docs, ops_per_doc, batch_docs, sample,
                                   platform, meter, report)
        kernels = run_kernel_phase(kernel_docs, meter, report)
    _log_compile_total(meter)
    return {"catchup": served, "kernels": kernels, "phases": report}


def run_mesh(n_docs: int = CORPUS_DOCS, ops_per_doc: int = OPS_PER_DOC,
             sample: int = ORACLE_SAMPLE, n_devices: int = 4,
             platform: str = "tpu") -> dict:
    """The mesh path alone: the corpus through ``CatchupService`` on the
    auto doc mesh vs ``Catchup.Mesh`` off on one device."""
    from fluidframework_tpu.service.catchup import CatchupService
    from fluidframework_tpu.utils.telemetry import (
        ConfigProvider,
        MonitoringContext,
    )

    report: dict = {}
    with CompileMeter() as meter:
        with phase("seed", meter, report):
            service, doc_ids, oracle = _seed_corpus(n_docs, ops_per_doc,
                                                    sample)
        folds = {}
        for name, mc in (
                ("mesh", None),
                ("single", MonitoringContext(config=ConfigProvider(
                    {"Catchup.Mesh": "off"})))):
            svc = CatchupService(service, mc=mc)
            stats: dict = {}
            with phase(f"catchup-{name}", meter, report):
                results = svc.catch_up(doc_ids, upload=False, stats=stats)
            size = 1 if svc._mesh is None else svc._mesh.size
            per_device = {k: v for k, v in sorted(svc.pipeline_stats.items())
                          if k.startswith("docs_on_device_")}
            log(f"{name}: {size} device(s) on {svc.fold_platform}, "
                f"deviceDocs {stats['deviceDocs']}, cpuDocs "
                f"{stats['cpuDocs']}, fallbackChannels "
                f"{stats['fallbackChannels']}, per device {per_device}")
            if svc.fold_platform != platform or stats["cpuDocs"] \
                    or stats["deviceDocs"] != len(doc_ids):
                raise AssertionError(f"{name} fold: {stats}, platform "
                                     f"{svc.fold_platform}")
            _check_oracle(results, oracle, f"{name} catch-up")
            folds[name] = (results, size, per_device)
    _log_compile_total(meter)
    mesh_results, mesh_size, per_device = folds["mesh"]
    single_results, single_size, _ = folds["single"]
    if mesh_size != n_devices or single_size != 1:
        raise AssertionError(f"mesh over {mesh_size} devices, single over "
                             f"{single_size}")
    if len(per_device) != n_devices or not all(per_device.values()):
        raise AssertionError(f"a device folded no documents: {per_device}")
    if mesh_results != single_results:
        raise AssertionError("mesh digests differ from single-device")
    log(f"mesh == single-device on all {len(doc_ids)} digests; both == "
        f"oracle on {len(oracle)} sampled docs; docs per device "
        f"{per_device}")
    return {"docs": len(doc_ids), "docs_per_device": per_device,
            "oracle_sample": len(oracle), "phases": report}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the mesh path and its "
                             "one-device comparison")
    args = parser.parse_args(argv)
    sys.path.insert(0, REPO)
    from fluidframework_tpu.utils.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {found!r}, "
              f"{len(devices)} device(s)); refusing to run elsewhere",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from importlib.metadata import version

    log(f"device: {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, libtpu {version('libtpu')}, compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_mesh(n_devices=4)
    else:
        run_smoke()
    log(f"total wall {time.perf_counter() - t0}s (set-up information)")
    print(json.dumps({"ok": True, "device": {
        "platform": found, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
