"""BENCHMARK.json against the limits its contract sets, and against the
files it names."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"][:2] == ["python3", "benchmarks/run.py"]
    cells = len(bench["workloads"])
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, cells // 4)


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(bench):
    def of(group, cell):
        return [m["name"] for m in bench[group]
                if "workloads" not in m or cell in m["workloads"]]

    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        e2e = of("end_to_end", cell)
        assert "setup_s" in e2e and len(e2e) >= 2 and of("per_layer", cell)
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e_names
        for cell in m.get("workloads", cells):
            assert cell in cells and m["moves"] in of("end_to_end", cell), (m, cell)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_every_name_has_its_file(bench):
    root = os.path.join(REPO, "benchmarks")
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert c["file"].startswith("benchmarks/")
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        # depth is the one cut; every other published key stands as published
        assert cfg["reduced"] == ["num_hidden_layers"]
        assert cfg["num_hidden_layers"] < cfg["published"]["num_hidden_layers"]
        assert cfg["use_parallel_residual"] is True and cfg["departures"]
        assert os.path.exists(os.path.join(root, "rehearsal", "configs", c["name"] + ".json"))
    for w in bench["workloads"]:
        with open(os.path.join(root, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(root, "lib", kind + ".py"))
        assert os.path.exists(os.path.join(root, "rehearsal", "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        with open(os.path.join(root, "metrics", m["name"] + ".json")) as f:
            meta = json.load(f)
        # a metric file holds what only it knows; BENCHMARK.json the rest
        assert set(meta) <= {"reader", "args"}
        module, _, fn = meta["reader"].partition(":")
        with open(os.path.join(root, "readers", module + ".py")) as f:
            assert f"def {fn}(ctx" in f.read()


def test_widths_are_the_published_ones():
    want = {"pythia-1.4b": (2048, 16, 8192, 50304), "pythia-6.9b": (4096, 32, 16384, 50432)}
    for name, sizes in want.items():
        with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
            c = json.load(f)
        assert (c["hidden_size"], c["num_attention_heads"], c["intermediate_size"],
                c["vocab_size"]) == sizes
        assert c["hidden_size"] // c["num_attention_heads"] == 128
