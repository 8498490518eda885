"""Operation and byte counts against hand counts, for one layer and for a
stack; the two readers that count work, on one layer, against the formulas
they used before the counts took a list of layers; and the peaks table."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import devtrace, harness, load, model, work

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
    widths = model.widths(c)
    assert work.ops_per_image(np.array([522]), widths, c["T"]) == per_image
    assert work.bytes_per_call(widths, 64 * 522, 64) == per_call


def test_a_stack_sums_its_layers():
    # 784-400-10: 522 input events into the hidden layer and 37 hidden
    # spikes into the output layer; an image with no events pays the LIF
    widths = [(784, 400), (400, 10)]
    events = np.array([[522, 37], [0, 0]])
    assert work.ops_per_image(events, widths, 32).tolist() == [
        522 * 400 + 32 * 400 * 5 + 37 * 10 + 32 * 10 * 5,
        32 * 400 * 5 + 32 * 10 * 5]
    assert work.bytes_per_call(widths, 64 * (522 + 37), 64) == (
        784 * 400 + 400 * 10 + 64 * (522 + 37) * 4 + 64 * 4)


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


KERNEL = '%fused_event_lif_decode.1 = s32[64]{0} custom-call(), ' \
         'custom_call_target="tpu_custom_call"'


def synthetic_run(name, widths=None):
    """A run of 200 requests over a pool of 50 images, hand-written: some
    outside the window, some failed, some wrong; 30 calls, one kernel event
    per layer of each."""
    rng = np.random.RandomState(7)
    cell = harness.Cell(name, 1, cfg(name), {}, [])
    widths = widths or model.widths(cell.cfg)
    n_layers = len(widths)
    n = 200
    rec = load.Records.empty(n)
    rec.image[:] = rng.randint(0, 50, n)
    rec.done[:] = 1.0 + rng.uniform(-0.2, 1.2, n)
    rec.error[:] = rng.uniform(size=n) < 0.05
    ops = [(KERNEL, 1e9 + 1e6 * i, 1e9 + 1e6 * i + 1.5e5 + 997 * i)
           for i in range(30 * n_layers)]
    return harness.Run(
        cell=cell, seconds=1.0, t0=1.0, setup_s=0.0, records=rec,
        correct_rows=~rec.error & (rng.uniform(size=n) < 0.97),
        events=rng.randint(0, 700, (50, n_layers)).astype(np.int64),
        widths=widths, stats={}, peak=work.peak_for("TPU v5 lite"),
        trace=devtrace.Trace(ops, 1, (1e9, 2e9)))


def one_layer_serve_mfu(run):
    """``serve_mfu_pct`` as it counted one layer, from ``cfg["n_out"]``."""
    c = run.cell.cfg
    rows = run.in_window() & run.correct_rows
    n_events = run.events[:, 0][run.records.image[rows]]
    ops = float(np.sum(n_events * c["n_out"] + 5 * c["T"] * c["n_out"]))
    return 100.0 * ops / run.seconds / run.peak["int8_ops_per_s"]


def one_layer_roofline(run):
    """``fused_event_lif_roofline`` as it counted one layer."""
    evs = devtrace.kernel_events(run.trace, "fused_event_lif")
    kernel_s = sum(e - s for _, s, e in evs) / 1e9
    c = run.cell.cfg
    served = ~run.records.error
    n_events = run.events[:, 0][run.records.image[served]]
    ops = float(np.sum(n_events * c["n_out"] + 5 * c["T"] * c["n_out"]))
    nbytes = (len(evs) * (c["n_in"] * c["n_out"] + 4 * 0 + 4 * 0)
              + (0 * 0 + 4 * int(n_events.sum()) + 4 * int(served.sum())))
    share, _ = work.roofline(kernel_s, ops, nbytes, run.peak)
    return share


@pytest.mark.parametrize("name", ["ttfs-784x150", "ttfs-784x1600"])
def test_one_layer_readers_are_bit_identical_to_the_one_layer_formulas(name):
    run = synthetic_run(name)
    mfu = harness.reader("serve_mfu_pct.sat")(run)
    roof = harness.reader("fused_event_lif_roofline")(run)
    assert mfu == one_layer_serve_mfu(run) and mfu > 0
    assert roof == one_layer_roofline(run) and roof > 0


def test_the_stack_readers_sum_the_layers_hand_counts():
    """784-400-10 at T=32, the kernel once per layer: 60 kernel events are
    30 calls of the stack, each reading both blocks once."""
    run = synthetic_run("ttfs-784x150", widths=[(784, 400), (400, 10)])
    ev = run.events[run.records.image]
    per_image = ev[:, 0] * 400 + 32 * 400 * 5 + ev[:, 1] * 10 + 32 * 10 * 5
    rows = run.in_window() & run.correct_rows
    assert harness.reader("serve_mfu_pct.sat")(run) == (
        100.0 * float(np.sum(per_image[rows])) / 1.0 / 393e12)
    evs = devtrace.kernel_events(run.trace, "fused_event_lif")
    kernel_s = sum(e - s for _, s, e in evs) / 1e9
    served = ~run.records.error
    nbytes = (30 * (784 * 400 + 400 * 10) + 4 * int(ev[served].sum())
              + 4 * int(served.sum()))
    want, bound = work.roofline(kernel_s, float(np.sum(per_image[served])),
                                nbytes, run.peak)
    assert bound == "memory"
    assert harness.reader("fused_event_lif_roofline")(run) == want
