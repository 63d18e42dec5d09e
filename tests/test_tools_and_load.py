"""Tooling (replay CLI, bench harness) and the load/stress harness."""

import json
import subprocess
import sys

import pytest

from fluidframework_tpu.drivers import FileDocumentServiceFactory
from fluidframework_tpu.loader import Loader
from fluidframework_tpu.testing.load import LoadSpec, run_load
from fluidframework_tpu.tools.bench_harness import (
    benchmark,
    benchmark_memory,
)
from fluidframework_tpu.tools.replay import replay


# --- replay tool -------------------------------------------------------------


def _make_store(tmp_path):
    root = str(tmp_path / "store")
    factory = FileDocumentServiceFactory(root)
    loader = Loader(factory)

    def build(rt):
        ds = rt.create_datastore("ds")
        ds.create_channel("sequence-tpu", "text")

    a = loader.create("doc", "alice", build)
    text = a.runtime.get_datastore("ds").get_channel("text")
    seqs = []
    for i in range(5):
        text.insert_text(0, f"[{i}]")
        a.drain()
        seqs.append((a.runtime.ref_seq, text.text))
    factory.close()
    return root, seqs


def test_replay_tool_reconstructs_history(tmp_path):
    root, seqs = _make_store(tmp_path)
    for seq, expected_text in seqs:
        report = replay(root, "doc", to_seq=seq)
        runtime = report.pop("_runtime")
        assert report["seq"] == seq
        channel = runtime.get_datastore("ds").get_channel("text")
        assert channel.text == expected_text
    head = replay(root, "doc")
    assert head["seq"] == seqs[-1][0]
    assert head["datastores"] == {"ds": {"text": "sequence-tpu"}}


def test_replay_cli(tmp_path):
    root, seqs = _make_store(tmp_path)
    out = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.tools.replay",
         root, "doc", "--json"],
        capture_output=True, text=True, check=True, cwd="/root/repo",
    )
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["seq"] == seqs[-1][0]
    assert report["summaryDigest"]

    shown = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.tools.replay",
         root, "doc", "--show", "ds/text"],
        capture_output=True, text=True, check=True, cwd="/root/repo",
    )
    assert seqs[-1][1] in shown.stdout


# --- bench harness -----------------------------------------------------------


def test_benchmark_statistics():
    calls = []
    result = benchmark(lambda: calls.append(1), name="noop",
                       min_runs=5, min_time_s=0.0, warmup_runs=1)
    assert result.runs >= 5
    assert len(calls) == result.runs + 1  # warmup included
    assert result.mean >= 0
    assert result.p50 <= result.p95 or result.runs < 3
    assert "noop" in result.report()


def test_benchmark_setup_untimed():
    def setup():
        return list(range(1000))

    timed = benchmark(lambda data: sum(data), min_runs=3, min_time_s=0,
                      warmup_runs=0, setup=setup)
    assert timed.runs == 3


def test_benchmark_memory():
    result = benchmark_memory(lambda: bytearray(5_000_000), name="alloc")
    assert result.peak_bytes > 4_000_000
    assert "alloc" in result.report()


# --- load harness ------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_load_run_converges(seed):
    result = run_load(LoadSpec(seed=seed, clients=3, steps=120))
    assert result.edits > 0
    assert result.sequenced_ops > 0
    assert result.final_clients >= 1
    assert len(result.summary_digest) == 64


def test_load_run_with_heavy_faults_converges():
    spec = LoadSpec(seed=7, clients=4, steps=200, edit_weight=0.5,
                    sync_weight=0.2, disconnect_weight=0.15,
                    stash_weight=0.1, late_join_weight=0.05)
    result = run_load(spec)
    assert result.disconnects > 0
    assert result.rehydrates + result.late_joins > 0


def test_devtools_inspector_snapshot():
    """The runtime inspector renders live state read-only: channels, quorum,
    proposals, connection and summarizer stats — and inspecting twice gives
    the same snapshot (no mutation)."""
    import json as _json

    from fluidframework_tpu.runtime.container import ContainerRuntime
    from fluidframework_tpu.runtime.summarizer import (
        SummarizerOptions,
        SummaryManager,
    )
    from fluidframework_tpu.service import LocalOrderingService
    from fluidframework_tpu.tools.devtools import inspect_runtime

    service = LocalOrderingService()
    ep = service.create_document("doc")
    rt = ContainerRuntime()
    ds = rt.create_datastore("ds")
    ds.create_channel("sequence-tpu", "text")
    ds.create_channel("map-tpu", "kv")
    ds.create_channel("counter-tpu", "n")
    rt.connect(ep, "alice")
    rt.drain()
    mgr = SummaryManager(rt, service.storage, "doc",
                         SummarizerOptions(ops_per_summary=1000))
    rt.get_datastore("ds").get_channel("text").insert_text(0, "hello")
    rt.get_datastore("ds").get_channel("kv").set("k", 1)
    rt.get_datastore("ds").get_channel("n").increment(2)
    rt.propose("code", "v1")
    rt.drain()

    snap = inspect_runtime(rt, summary_manager=mgr)
    _json.dumps(snap)  # JSON-safe
    assert snap["clientId"] == "alice"
    assert snap["quorum"] == ["alice"]
    channels = snap["datastores"]["ds"]["channels"]
    assert channels["text"]["preview"] == "hello"
    assert channels["kv"]["preview"] == {"k": 1}
    assert channels["n"]["value"] == 2
    assert snap["proposals"]["pending"] or snap["proposals"]["accepted"]
    assert snap["summarizer"]["isSummarizer"] is True
    assert inspect_runtime(rt, summary_manager=mgr) == snap  # read-only


def test_wire_soak_1k_docs_through_catchup_rpc(tmp_path):
    """Scale soak (SURVEY §4 load/stress; VERDICT r3 #8): >=1k mixed-channel
    documents seeded by client SUBPROCESSES against the standalone server,
    folded centrally through the catchup RPC — device routing must dominate
    (device_docs >> cpu_docs) and sampled fresh loads must reproduce the
    seeders' summaries byte-identically with zero catch-up replay."""
    import os
    import subprocess
    import sys
    import time

    n_docs = int(os.environ.get("SOAK_DOCS", "1024"))
    procs = 4
    edits = 6
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    srv = subprocess.Popen(
        [sys.executable, "-m", "fluidframework_tpu.service.server",
         "--dir", str(tmp_path / "store"), "--port", "0",
         "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=repo,
        # the entry point's compile cache goes here, not into the repo
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")},
    )
    try:
        port = None
        for _ in range(400):
            line = srv.stdout.readline()
            if "listening" in line:
                port = int(line.rsplit(":", 1)[-1].strip())
                break
        assert port, "server did not report a port"
        # Keep draining the merged stdout/stderr pipe: server logging
        # under 1k-doc load could otherwise fill the OS pipe buffer and
        # block the event loop (deadlocking the whole soak).
        import threading

        threading.Thread(target=lambda: [None for _ in srv.stdout],
                         daemon=True).start()

        t0 = time.time()
        per = n_docs // procs
        workers = [
            subprocess.Popen(
                [sys.executable, "-m", "fluidframework_tpu.testing.load",
                 "--wire-worker", "127.0.0.1", str(port), str(w * per),
                 # last worker takes the remainder so any SOAK_DOCS works
                 str(n_docs if w == procs - 1 else (w + 1) * per),
                 str(edits), "42"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=repo,
            )
            for w in range(procs)
        ]
        expected = {}
        for w in workers:
            out, err = w.communicate(timeout=600)
            assert w.returncode == 0, err[-2000:]
            expected.update(json.loads(out.strip().splitlines()[-1]))
        seed_time = time.time() - t0
        assert len(expected) == n_docs

        from fluidframework_tpu.drivers.network_driver import (
            NetworkDocumentServiceFactory,
        )

        # The bulk fold of 1k docs takes minutes on the CPU backend
        # (XLA-emulated kernels + compiles): size the RPC timeout to the
        # workload, not the default interactive 30s.
        f = NetworkDocumentServiceFactory(host="127.0.0.1", port=port,
                                          timeout=600.0)
        try:
            t0 = time.time()
            res = f._rpc.request("catchup", {})
            fold_time = time.time() - t0
            assert len(res["docs"]) == n_docs
            # Device routing must dominate: every doc here is a pure
            # kernel-channel doc (string/map/matrix/tree).
            assert res["deviceDocs"] >= 0.95 * n_docs, (
                res["deviceDocs"], res["cpuDocs"])

            # Sampled fresh loads: zero catch-up replay, byte-identical to
            # the seeders' read-only summaries.
            sample = sorted({min(i, n_docs - 1)
                             for i in (0, 1, 2, 3, 4, n_docs // 2,
                                       n_docs - 1)})
            loader = Loader(f)
            for i in sample:
                doc = f"soak{i:05d}"
                c = loader.resolve(doc)
                assert c.catchup_ops == 0, (doc, c.catchup_ops)
                assert c.runtime.summarize().digest() == expected[doc], doc
                c.close()
            print(f"wire soak: {n_docs} docs, {procs} procs, seed "
                  f"{seed_time:.1f}s, catchup fold {fold_time:.1f}s, "
                  f"device {res['deviceDocs']} / cpu {res['cpuDocs']}")
        finally:
            f.close()
    finally:
        srv.terminate()
        srv.wait(timeout=15)


# --- chip preflight gate -----------------------------------------------------


def test_tpu_preflight_exits_zero_on_cpu():
    """The preflight must be green on CPU (interpret mode): it is the
    gate that keeps a chip call from being spent on failures the CPU
    could already report (kernel lint, fold parity, bench schema)."""
    import os
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "tpu_preflight.py")],
        capture_output=True, text=True, cwd=str(root),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["preflight_ok"] is True
    assert set(doc["gates"]) == {"kernel_lint", "mergetree_parity",
                                 "tree_parity", "bench_schema"}
    assert all(g["ok"] for g in doc["gates"].values())
