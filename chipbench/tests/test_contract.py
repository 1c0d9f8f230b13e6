"""BENCHMARK.json is well formed, and every name in it finds its file."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"][1].startswith("chipbench/")
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    # a full check: 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell
    # to compile, 1200 s spare, all within 43200 s even with 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert cells <= 24


def test_names_and_files(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg["assumed"] for k in c["reduced"])
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.exists(os.path.join(BENCH, sub, f"{name}.json"))
        with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as fh:
            driver = json.load(fh)["driver"]
        assert os.path.exists(os.path.join(BENCH, f"drive_{driver}.py"))
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(
        bench["workloads"])


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"][0]
    assert setup["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{m['name']}.py"))
        moved = [x for x in bench["end_to_end"] if x["name"] == m["moves"]][0]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_size(bench):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
