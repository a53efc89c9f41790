"""Lint of ``BENCHMARK.json`` against the contract's limits and against the
files it names."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _manifest()


def _reader_files():
    return {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
            if f.endswith(".py")}


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in _manifest()["per_layer"]} | _reader_files()))
def test_every_per_layer_entry_has_its_reader_and_every_reader_its_entry(
        name, manifest):
    """The contract between the manifest and ``layer_metrics/``, whatever
    the order of the entries: one entry, one file with one ``read(run)``,
    in cells that report the end-to-end metric it moves."""
    rows = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(rows) == 1, "%s: %d per_layer entries" % (name, len(rows))
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    assert os.path.isfile(path), "no reader file for %s" % name
    with open(path) as f:
        assert re.search(r"^def read\(run\):", f.read(), re.M), path
    (row,) = rows
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == row["moves"])
    cells = [w["name"] for w in manifest["workloads"]]
    assert set(row.get("workloads", cells)) <= set(
        moved.get("workloads", cells))


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"][-1].startswith("benchmark/")
    assert isinstance(manifest["run_seconds"], int)
    assert 10 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["workloads"]) <= 24
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines(manifest):
    rows = manifest["configs"] + manifest["workloads"] \
        + manifest["end_to_end"] + manifest["per_layer"]
    for row in rows:
        assert NAME.match(row["name"]), row["name"]
        for key in ("why", "layer", "source"):
            if key in row and row[key] not in SOURCES:
                assert 1 <= len(row[key]) <= 200 and "\n" not in row[key] \
                    and "\t" not in row[key], (row["name"], key)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [r["name"] for r in manifest[group]]
        assert len(names) == len(set(names))
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_file_exists(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           cfg["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "reference",
                                           cfg["reference"]))
        assert "limits" in cfg
    for w in manifest["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_each_cell_reports_what_its_layer_metrics_move(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
        assert cells_of(m) <= set(cells)
    for cell in cells:
        assert sum(cell in cells_of(m) for m in manifest["end_to_end"]) >= 2
        assert any(cell in cells_of(m) for m in manifest["per_layer"])
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
