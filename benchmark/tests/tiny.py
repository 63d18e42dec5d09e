"""Tiny sizes for CPU rehearsals of each cell, and a runner that captures
the run's output."""

from __future__ import annotations

import contextlib
import io
import json
import os

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: a peaks entry for the CPU, which the real table rightly lacks
CPU_PEAKS = {"cpu": {"hbm_bytes_per_s": 1e11}}

TINY = {
    "string-10k.bulk": {
        "config": {"docs": 96},
        "traffic": {"docs_per_request": 16,
                    "warmup": {"docs_per_request": 16, "min_requests": 1,
                               "max_requests": 3}}},
    "string-10k.open-cold": {
        "config": {"docs": 64},
        "traffic": {"rate_per_s": 6, "connections": 8,
                    "warmup": {"docs_per_request": 1, "min_requests": 2,
                               "max_requests": 6}}},
}


def run_tiny(workload: str, seed: int = 3_000_000_019, seconds: float = 2,
             trace: bool = False, fault: str = None,
             overrides: dict = None) -> tuple:
    """(result, stdout, stderr) of one CPU run of ``workload`` at its
    tiny size."""
    from benchmark import harness

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        result = harness.run_cell(
            workload, seed, seconds, trace, platform="cpu",
            overrides=overrides or TINY[workload], peaks_table=CPU_PEAKS,
            fault=fault)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    return result, out.getvalue(), err.getvalue()
