"""Operation and byte counts against hand counts, and the peaks table."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name, per_image, per_call", [
    # 522 events x 150 adds + 32 steps x 150 neurons x 5 LIF ops
    ("ttfs-784x150", 522 * 150 + 32 * 150 * 5,
     # 784 x 150 int8 weights + 64 x 522 event ids x 4 B + 64 labels x 4 B
     784 * 150 + 64 * 522 * 4 + 64 * 4),
    ("ttfs-784x1600", 522 * 1600 + 32 * 1600 * 5,
     784 * 1600 + 64 * 522 * 4 + 64 * 4),
])
def test_counts_match_hand_counts(name, per_image, per_call):
    c = cfg(name)
    assert work.ops_per_image(522, c["n_out"], c["T"]) == per_image
    assert work.bytes_per_call(c["n_in"], c["n_out"], 64 * 522, 64) == per_call


def test_events_cap_each_step_at_e_max():
    T = 4
    times = np.array([[0, 0, 0, 1, 4, 3],
                      [4, 4, 4, 4, 4, 4]])
    assert work.events(times, T, e_max=2).tolist() == [4, 0]
    assert work.events(times, T, e_max=8).tolist() == [5, 0]


def test_roofline_names_the_binding_bound():
    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = work.roofline(1.0, ops=1e11, nbytes=1e6, peak=peak)
    assert bound == "compute" and share == pytest.approx(10.0)
    share, bound = work.roofline(2.0, ops=1e6, nbytes=1e9, peak=peak)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_peaks_table_has_v5e_with_its_source():
    table = json.loads(work.PEAKS.read_text())
    assert "Google Cloud" in table["source"]
    v5e = work.peak_for("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        work.peak_for("cpu")
