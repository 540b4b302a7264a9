"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files that serve it."""
import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(_dim|_rank)$|^(d_model|d_ff|n_heads|head|hidden|"
                    r"intermediate|latent|state|projection|expansion|top_k)")

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    M = json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"]
    assert 1 <= len(M["command"]) <= 32 and all(map(_line, M["command"]))
    assert os.path.isfile(os.path.join(REPO, M["command"][1]))
    assert M["command"][1].startswith("bench/")
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) <= 64 * 1024


def test_check_fits_its_time_with_every_cell():
    runs = 2 + 14 * 24
    total = runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_configurations():
    assert 1 <= len(M["configs"]) <= 24
    used = {w["config"] for w in M["workloads"]}
    files = set()
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert body["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTHS.search(k)
            assert k in body["assumed"]


def test_cells():
    assert 1 <= len(M["workloads"]) <= 24
    pairs = set()
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            entry = json.load(f)["entry"]
        assert os.path.isfile(os.path.join(BENCH, "entries", entry + ".py"))
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)
    assert len({w["name"] for w in M["workloads"]}) == len(M["workloads"])


def _reported(cell):
    e2e = {m["name"] for m in M["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    layer = {m["name"] for m in M["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in e2e)}
    return e2e, layer


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_reports_what_it_must(cell):
    e2e, layer = _reported(cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer


def test_metrics():
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"] for m in M["end_to_end"]}
    assert 1 <= len(M["end_to_end"]) <= 16 and "setup_s" in e2e
    assert 1 <= len(M["per_layer"]) <= 128
    layers = {}
    for m in M["end_to_end"] + M["per_layer"]:
        per_layer = m in M["per_layer"]
        keys = {"name", "unit", "better", "source"} | (
            {"layer", "moves"} if per_layer else {"bound"})
        assert set(m) - {"workloads"} == keys
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", ())) <= cells
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        assert spec is not None and os.path.isfile(path)
        if per_layer:
            assert m["moves"] in e2e and _line(m["layer"])
            for cell in m.get("workloads", ()):
                assert m["moves"] in _reported(cell)[0]
            if "roofline" in m["name"] or "mfu" in m["name"]:
                assert m["unit"] == "%"
        else:
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for k, v in layers.items() if k != "mfu")
