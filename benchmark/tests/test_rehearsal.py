"""CPU rehearsals of each cell at tiny size, through the whole harness
(the measuring entry still refuses the CPU), the control, and the faults
that ``correct`` has to catch."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.tests.tiny import ROOT_DIR, TINY, run_tiny


@pytest.mark.parametrize("workload", sorted(TINY))
def test_each_cell_rehearses_correct_on_the_cpu(workload):
    result, out, err = run_tiny(workload)
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    cell = harness.find_cell(harness.load_spec(), workload)
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert list(result)[-1] == "compared"
    assert all(v["value"] == 0 for v in result["compared"].values())
    # the earlier lines a run promises
    assert "'catchup.degraded': 0" in out   # stale serving is off
    for needle in ("device: platform cpu", "set-up split", "lanes:",
                   "sheds held and resent", "compiles inside the window",
                   "generator lateness", "corpus ran out"):
        assert needle in out
    # the compared numbers close standard error
    tail = err.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") for line in tail)


def test_traced_rehearsal_reports_the_per_layer_metrics():
    result, out, _err = run_tiny("string-10k.bulk", trace=True)
    assert result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: the trace metrics read 100% idle and
    # the roofline finds nothing to read, so it is left out
    assert "fold_roofline" not in result["metrics"]
    assert {"fallback_doc_share.bulk", "device_wait_s_per_mop.bulk",
            "pack_s_per_mop.bulk"} <= set(result["metrics"])


def test_the_measuring_entry_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "string-10k.bulk", "--seed", "5", "--seconds", "1"],
        cwd=ROOT_DIR, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


def test_a_checkout_of_the_benchmark_alone_refuses_to_run(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT_DIR, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT_DIR, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "string-10k.bulk", "--seed", "5", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())


@pytest.mark.parametrize("workload", sorted(TINY))
def test_the_control_is_not_correct(workload):
    """The control breaks the configuration's freshness guarantee: every
    answer folds all of its tail but the last op."""
    result, out, _err = run_tiny(workload, fault="stale_tail")
    assert result["correct"] is False
    assert result["compared"]["failed_docs"]["value"] == result["attempted"]
    assert "'wrong_docs': %d" % result["attempted"] in out


@pytest.mark.parametrize("fault,kind", [
    ("unchanged_state", "wrong_docs"),
    ("half_batch", "skipped_docs"),
    ("altered_answer", "wrong_docs"),
])
@pytest.mark.parametrize("workload", sorted(TINY))
def test_each_fault_turns_correct_false(workload, fault, kind):
    result, out, _err = run_tiny(workload, fault=fault)
    assert result["correct"] is False, out
    assert result["compared"]["failed_docs"]["value"] > 0
    assert result["failed"] == result["compared"]["failed_docs"]["value"]
    line = next(l for l in out.splitlines()
                if l.startswith("failed documents by kind"))
    assert f"'{kind}': 0" not in line


def test_a_wrong_answer_is_named_on_an_earlier_line():
    _result, out, _err = run_tiny("string-10k.bulk", fault="altered_answer")
    line = next(l for l in out.splitlines() if l.startswith("reference"))
    assert "state differs" in line
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
