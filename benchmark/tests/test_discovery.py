"""Name-to-file discovery, and a throwaway cell added as new files plus
new entries alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.corpus import generator
from benchmark.tests.tiny import ROOT_DIR


def test_every_cell_finds_its_files_by_name():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        found = harness.find_cell(spec, cell["name"])
        config_name, traffic_name = cell["name"].split(".", 1)
        assert found["config"]["name"] == config_name == cell["config"]
        assert traffic_name == cell["traffic"]
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "traffic", f"{traffic_name}.json"))
        generator(found["config"]["corpus"])
        for m in found["end_to_end"] + found["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))


def test_unknown_names_fail_loudly():
    spec = harness.load_spec()
    with pytest.raises(KeyError):
        harness.find_cell(spec, "string-10k.nope")
    with pytest.raises(KeyError):
        harness.metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        generator("no_such_generator")
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _digests(root):
    out = {}
    for d, _s, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


THROWAWAY = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
r = harness.run_cell("string-1k.bulk-small", 7, 1, True, platform="cpu",
                     peaks_table={"cpu": {"hbm_bytes_per_s": 1e11}})
print(json.dumps({"throwaway": r}))
'''


def test_a_throwaway_cell_needs_only_new_files_and_entries(tmp_path):
    shutil.copy(os.path.join(ROOT_DIR, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT_DIR, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("fluidframework_tpu", "native"):
        os.symlink(os.path.join(ROOT_DIR, name), tmp_path / name)
    before = _digests(tmp_path / "benchmark")
    bench = tmp_path / "benchmark"
    # a configuration, a traffic mix and a per-layer metric, as new files
    cfg = json.loads((bench / "configs" / "string-10k.json").read_text())
    cfg.update(name="string-1k", docs=48)
    (bench / "configs" / "string-1k.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "bulk.json").read_text())
    traffic.update(docs_per_request=8, warmup={
        "docs_per_request": 8, "min_requests": 1, "max_requests": 3})
    (bench / "traffic" / "bulk-small.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "docs_per_request.small.py").write_text(
        "def read(run):\n    return run['docs_attempted'] / run['requests']\n")
    # and new entries in BENCHMARK.json
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "string-1k", "source": "https://example.org/throwaway",
        "file": "benchmark/configs/string-1k.json", "reduced": ["docs"],
        "why": "throwaway"})
    spec["workloads"].append({
        "name": "string-1k.bulk-small", "config": "string-1k",
        "traffic": "bulk-small", "chips": 1, "why": "throwaway"})
    spec["end_to_end"][0]["workloads"].append("string-1k.bulk-small")
    spec["per_layer"].append({
        "name": "docs_per_request.small", "unit": "docs/req",
        "better": "higher", "source": "host_clock", "layer": "throwaway",
        "moves": "fold_ops_per_s", "workloads": ["string-1k.bulk-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", THROWAWAY], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])["throwaway"]
    assert result["correct"] is True
    assert result["metrics"]["docs_per_request.small"]["value"] == 8
    after = _digests(tmp_path / "benchmark")
    changed = {k for k in before if before[k] != after.get(k)}
    assert not changed, f"existing benchmark files edited: {changed}"
