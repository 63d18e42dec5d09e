"""The readers of the program's spans and server counters: their
arithmetic, and no reading where a program records none of them."""

import pytest

from benchmark import harness

SPAN_METRICS = ("catchup_host_s_per_mop.bulk", "fallback_s_per_mop.bulk",
                "wait_ms_per_request.open", "serve_host_ms_per_request.open",
                "shed_hold_ms_per_request.open")


def _run(server=None, stage=None):
    return {"requests": 40, "ops_folded": 2_000_000,
            "server": server if server is not None else {
                "catchup.shed": 3, "catchup.requests": 43,
                "catchup.admitted": 40, "catchup.queued_s": 0.086,
                "catchup.serve_s": 1.2, "catchup.retry_after_s": 0.9},
            "stage": stage if stage is not None else {
                "pack": 5.0, "device_wait": 0.4, "extract": 3.0,
                "fallback": 1.5, "serial_wait": 0.0,
                "prepare": 2.0, "assemble": 0.5, "publish": 1.0}}


def test_span_readers_arithmetic():
    def read(name):
        return harness.metric_reader(name)(_run())

    assert read("catchup_host_s_per_mop.bulk") == pytest.approx(3.5 / 2)
    assert read("fallback_s_per_mop.bulk") == pytest.approx(1.5 / 2)
    assert read("wait_ms_per_request.open") == pytest.approx(86.0 / 43)
    assert read("serve_host_ms_per_request.open") == pytest.approx(
        800.0 / 40)
    assert read("shed_hold_ms_per_request.open") == pytest.approx(
        900.0 / 40)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_readers_read_nothing_without_the_spans(name):
    # A program without the spans and counters: the counters the server
    # always had, and the six pipeline stages.
    run = _run(server={"catchup.shed": 0, "catchup.requests": 40,
                       "catchup.admitted": 40},
               stage={"pack": 5.0, "upload": 0.1, "dispatch": 0.1,
                      "device_wait": 0.4, "download": 0.2, "extract": 3.0})
    assert harness.metric_reader(name)(run) is None
