"""``BENCHMARK.json`` resolves, cell by cell (tier-1; ROADMAP.md C5): every
cell finds its configuration, traffic mix, reference and readers by
name, and each reader states the layer, unit, end-to-end metric and
source its entry states.  Nothing is run: the cells themselves are
rehearsed by ``benchmarks/tests`` (not tier-1) and measured on the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def harness_on_path():
    """A reader imports ``harness.*`` as the benchmark's own run does."""
    sys.path.insert(0, BENCH)
    yield
    sys.path.remove(BENCH)


def _metrics_of(cell: str, kind: str) -> list[dict]:
    return [m for m in BENCHMARK[kind]
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_configuration_traffic_reference_and_readers(
        cell, harness_on_path):
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    cfg = next(c for c in BENCHMARK["configs"]
               if c["name"] == entry["config"])
    assert cfg["file"].startswith(BENCHMARK["paths"][0] + "/")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    assert config["name"] == cfg["name"]
    assert config["chips"] == entry["chips"]
    assert set(cfg["reduced"]) <= set(config["reduced"])
    assert config["guarantees"]["copies_compared"] == (
        config["pool"]["k"] + config["pool"]["m"]
        if config["pool"]["type"] == "erasure" else config["pool"]["size"])
    with open(os.path.join(BENCH, "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["object_bytes"] > 0 and traffic["in_flight"] > 0
    loop = traffic["loop"] or {"op": "write_full"}
    assert loop["op"] in ("write_full", "read", "write")
    if loop["op"] == "write":   # a block device's writes: whole blocks
        assert traffic["prefill_objects"] > 0 and loop["offsets"] == "uniform"
        assert traffic["object_bytes"] % loop["io_bytes"] == 0
    if traffic.get("counter"):      # the cell's end-to-end metric
        assert traffic["counter_metric"] in {
            m["name"] for m in _metrics_of(cell, "end_to_end")}
    # the plain reference: a configuration's own, else the harness's
    ref_path = os.path.join(
        BENCH, "references", config["reference"] + ".py") \
        if config.get("reference") else os.path.join(
            BENCH, "harness", "reference.py")
    with open(ref_path) as f:
        assert "ceph" + "_tpu" not in f.read()  # nothing of the program
    ref = _load(ref_path, "reference_under_test_" + cell)
    assert callable(ref.expected_copies)
    e2e = _metrics_of(cell, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per_layer = _metrics_of(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        reader = _load(os.path.join(BENCH, "layer_metrics",
                                    m["name"] + ".py"),
                       "layer_metric_" + m["name"].replace(".", "_"))
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert callable(reader.compute)
        assert m["moves"] in {x["name"] for x in e2e}, m["name"]


def test_the_file_keeps_to_the_form_and_every_reader_has_an_entry():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in BENCHMARK[kind]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        assert len({e["name"] for e in BENCHMARK[kind]}) == \
            len(BENCHMARK[kind])
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCHMARK["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(BENCH, "layer_metrics")) if f.endswith(".py")}
    assert on_disk == {m["name"] for m in BENCHMARK["per_layer"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
