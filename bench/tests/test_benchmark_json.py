"""BENCHMARK.json keeps to the contract's names, units and files."""

import json
import os
import re

import pytest

from bench.harness import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(not p.startswith("/") and ".." not in p for p in bench["paths"])


def test_names_and_units(bench):
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        names.append(w["name"])
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len(names) == len(set(names))


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_name_has_its_file(bench):
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        e2e = {e["name"] for e in bench["end_to_end"]}
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = [m for m in bench["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert {"setup_s"} < {m["name"] for m in e2e}
        moved = {m["name"] for m in e2e}
        assert any(m["moves"] in moved and w["name"] in m.get("workloads", [w["name"]])
                   for m in bench["per_layer"])
