"""Each configuration reaches its deployment through the module it names
(``"module"``, ``model`` when absent), and every module the benchmark
names has the interface the harness calls."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import data, harness, model, reference, work

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
INTERFACE = ("build", "artifact", "answers", "events", "widths")


def cell_of(cfg):
    return harness.Cell("c", 1, cfg, {}, [])


def test_a_config_without_a_module_resolves_to_model():
    assert cell_of({"n_in": 784}).module is model


def test_a_config_naming_a_module_resolves_to_it(stand_in):
    mod = stand_in()
    assert cell_of({"module": "stand_in"}).module is mod


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_names_a_module_with_the_interface(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mod = cell_of(cfg).module
    assert all(callable(getattr(mod, f, None)) for f in INTERFACE)
    widths = mod.widths(cfg)
    assert widths and all(n_in > 0 and n_out > 0 for n_in, n_out in widths)


def test_load_cell_gives_the_cell_its_module():
    cell = harness.load_cell(BENCH["workloads"][0]["name"], trace=False)
    assert cell.module is model


def test_one_layer_events_are_the_event_path_count():
    cfg = json.loads((ROOT / BENCH["configs"][0]["file"]).read_text())
    dep = model.Deployment(cfg, None, None, 1.0, 0.0)
    x, _ = data.generate(40, 3)
    x[0] = 1.0                          # every pixel in step 0: over E_max
    ev = model.events(dep, x)
    times = reference.encode(x, cfg["T"], cfg["x_min"])
    assert ev.shape == (40, 1)
    assert np.array_equal(ev[:, 0], work.events(times, cfg["T"], cfg["e_max"]))
    assert ev[0, 0] == cfg["e_max"]
