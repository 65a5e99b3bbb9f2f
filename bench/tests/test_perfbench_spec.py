"""``BENCHMARK.json`` against the benchmark's rules: names, units, keys,
files, which cells report which metrics, and the check's time budget."""
import json
import os
import re

import pytest

from perfbench_paths import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(bench["command"]) <= 32
    assert all(one_line(w) for w in bench["command"])
    for w in bench["command"][1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


@pytest.mark.parametrize("part", sorted(KEYS))
def test_entries_keys_and_names(bench, part):
    entries = bench[part]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for e in entries:
        extra = {"workloads"} if part in ("end_to_end", "per_layer") else set()
        assert KEYS[part] <= set(e) <= KEYS[part] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for k in ("why", "layer", "source"):
            if k in e and part != "end_to_end":
                assert one_line(e[k]), (e["name"], k)


def test_configs_and_cells(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert 1 <= len(configs) <= 24
    used = set()
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(ROOT, "bench", "checks",
                                           w["name"] + ".json"))
    assert used == set(configs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics_reported_per_cell(bench):
    cells = {w["name"] for w in bench["workloads"]}

    def where(m):
        return set(m.get("workloads", cells))

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert where(m) <= cells
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert where(m) <= where(e2e[m["moves"]])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for cell in cells:
        assert any(cell in where(m) for m in bench["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(cell in where(m) for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    moves = {}
    for m in bench["per_layer"]:
        if "mfu" in m["name"] or m["name"].split(".")[0].endswith(
                "_roofline"):
            assert m["unit"] == "%"
            moves.setdefault(m["moves"], set()).add(m["name"])
    for metric, names in moves.items():
        assert any("mfu" in n for n in names), metric


def test_check_fits_its_time_budget(bench):
    rs = bench["run_seconds"]
    full = 2 + 14 * 24
    assert full * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
