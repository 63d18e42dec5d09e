"""The metric readers' arithmetic, from the workload alone."""

import pytest

from benchmark import harness


def reader(name):
    return harness.metric_reader(name)


def _run(**kw):
    run = {"setup_s": 12.5, "window_s": 30.0, "latencies_s": [0.02] * 10,
           "requests": 10, "docs_attempted": 20480, "ops_folded": 1_966_080,
           "docs_fresh": 20480, "summary_bytes": 40_000_000,
           "answers": {"cpuDocs": 0, "fallbackChannels": 512},
           "server": {"catchup.shed": 3}, "stage": {"device_wait": 40.0,
                                                     "pack": 5.0},
           "trace": {"busy_s": 12.0, "window_s": 30.0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    run.update(kw)
    return run


def test_roofline_bytes_come_from_the_work_alone():
    roof = harness.metric_reader("fold_roofline")
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "roof", os.path.join(harness.BENCH_DIR, "metrics",
                             "fold_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # 96 ops of 32-byte records per document plus the summary bytes
    assert mod.OP_RECORD_BYTES == 32
    assert mod.least_bytes(2048 * 96, 4_000_000) == 2048 * 96 * 32 + 4_000_000
    run = _run()
    least = (1_966_080 * 32 + 40_000_000) / 819e9
    assert roof(run) == pytest.approx(100 * least / 12.0)
    assert roof(_run(trace=None)) is None
    assert roof(_run(trace={"busy_s": 0.0, "window_s": 30.0})) is None


def test_rates_and_shares():
    assert reader("fold_ops_per_s")(_run()) == pytest.approx(1_966_080 / 30)
    assert reader("device_wait_s_per_mop.bulk")(_run()) == pytest.approx(
        40.0 / 1.96608)
    assert reader("pack_s_per_mop.bulk")(_run()) == pytest.approx(
        5.0 / 1.96608)
    assert reader("fallback_doc_share.bulk")(_run()) == pytest.approx(
        100 * 512 / 20480)
    assert reader("sheds_per_request.open")(_run()) == pytest.approx(0.3)
    assert reader("device_idle_share.bulk")(_run()) == pytest.approx(60.0)
    assert reader("device_idle_share.open")(_run(trace=None)) is None
    assert reader("setup_s")(_run()) == 12.5


def test_latency_percentiles_are_over_all_requests():
    lat = [0.010] * 90 + [0.050] * 5 + [0.200] * 5
    run = _run(latencies_s=lat, requests=100)
    assert reader("catchup_p50_ms")(run) == pytest.approx(10.0)
    assert reader("catchup_p95_ms.open")(run) == pytest.approx(50.0)
