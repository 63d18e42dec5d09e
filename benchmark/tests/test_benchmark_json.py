"""``BENCHMARK.json`` against the limits of its format."""

import json
import os
import re

from benchmark.tests.tiny import ROOT_DIR

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _spec():
    path = os.path.join(ROOT_DIR, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _line(text):
    assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_and_names():
    spec = _spec()
    assert set(spec) == KEYS
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert spec["paths"] == ["benchmark"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        _line(c["source"]), _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert os.path.exists(os.path.join(ROOT_DIR, c["file"]))
        names.add(c["name"])
    used = set()
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        _line(w["why"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == names and len(pairs) == len(spec["workloads"])
    cells = {w["name"] for w in spec["workloads"]}
    metric_names = set()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in metric_names
        metric_names.add(m["name"])
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        _line(m["layer"])
        mover = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in mover.get("workloads", cells)
    # every cell reports setup_s, another end-to-end metric, and a
    # per-layer one
    for cell in cells:
        reported = [m for m in spec["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in spec["per_layer"])
    # at most half the cells (rounded down), or one, on four chips
    fours = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert fours <= max(1, len(cells) // 2)


def test_a_full_check_fits_its_time():
    spec = _spec()
    runs = 2 + 14 * 24
    total = runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
