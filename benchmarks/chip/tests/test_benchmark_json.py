"""Every name in BENCHMARK.json resolves to the files the harness reads."""

import json
import re
from pathlib import Path

import pytest

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    for trace in (False, True):
        c = harness.load_cell(cell["name"], trace)
        assert c.chips in (1, 4)
        assert c.metrics, f"{cell['name']} reports no metric at trace={trace}"
        for m in c.metrics:
            assert callable(harness.reader(m["name"]))
    e2e = [m["name"] for m in harness.load_cell(cell["name"], False).metrics]
    assert "setup_s" in e2e and len(e2e) >= 2


def test_names_and_metric_links():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.fullmatch(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_source(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["name"] == conf["name"] and cfg["reduced"] == conf["reduced"]
    assert cfg["source"] == conf["source"] and cfg["assumed"]
