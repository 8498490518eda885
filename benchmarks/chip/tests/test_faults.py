"""A whole run of the harness on the CPU, at a tiny size, with the look for
a chip skipped: clean, it is correct; with the served path broken underneath
in each way a serving cell can break, ``correct`` comes out false. The run
builds, serves and checks through the deployment module its configuration
names."""

import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def tiny_cell(latency_mode: bool, **over) -> harness.Cell:
    cfg = json.loads((CONFIGS / "ttfs-784x150.json").read_text())
    cfg.update(n_out=30, per_group=3, train_images=256, train_steps=8, **over)
    load = ({"kind": "poisson", "rate_per_s": 400} if latency_mode
            else {"kind": "closed", "outstanding": 32})
    traffic = {"pool_images": 128, "load": load,
               "serve": {"spec": "accelerator-event", "kernel": "fused",
                         "workers": 1, "max_batch": 16, "max_wait_us": 2000,
                         "latency_mode": latency_mode}}
    return harness.Cell("tiny", 1, cfg, traffic, [])


@pytest.fixture
def run(monkeypatch):
    from repro.core.lowering import ProgramCache, install
    prev = install(ProgramCache())          # bundles traced afresh
    monkeypatch.setattr(harness, "find_chips", lambda chips: (
        jax.devices("cpu")[:chips], work.peak_for("TPU v5 lite")))

    def go(latency_mode=False, **over):
        cell = tiny_cell(latency_mode, **over)
        monkeypatch.setattr(harness, "load_cell", lambda w, t: cell)
        out = harness.run("tiny", 2**31 + 9, 0.5, False, time.perf_counter())
        assert out["attempted"] > 0
        return out

    yield go
    install(prev)


@pytest.mark.parametrize("latency_mode", [False, True])
def test_clean_run_is_correct(run, latency_mode):
    out = run(latency_mode)
    assert out["correct"] and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_run_goes_through_the_configs_module(run, stand_in):
    mod = stand_in()
    out = run(module="stand_in")
    assert out["correct"]
    assert sorted(set(mod.calls)) == ["answers", "artifact", "build",
                                      "events", "widths"]


def test_correct_is_the_named_modules_reference(run, stand_in):
    """A module whose reference answers otherwise fails every request."""
    stand_in(lie=True)
    out = run(module="stand_in")
    assert not out["correct"]
    assert out["checks"]["wrong_labels"]["value"] == out["attempted"]


def _ops():
    from repro.kernels.fused_event_lif import ops
    return ops


def test_label_altered_where_produced(run, monkeypatch):
    ops = _ops()
    orig = ops.fused_event_lif_decode

    def broken(*a, **k):
        res, labels = orig(*a, **k)
        return res, labels.at[0].set((labels[0] + 1) % k["n_groups"])

    monkeypatch.setattr(ops, "fused_event_lif_decode", broken)
    out = run()
    assert not out["correct"] and out["checks"]["wrong_labels"]["value"] > 0


def test_half_of_the_batch_left_out(run, monkeypatch):
    ops = _ops()
    orig = ops.fused_event_lif_decode

    def broken(*a, **k):
        res, labels = orig(*a, **k)
        half = np.arange(labels.shape[0]) < labels.shape[0] // 2
        return res, jax.numpy.where(half, labels, 0)

    monkeypatch.setattr(ops, "fused_event_lif_decode", broken)
    out = run()
    assert not out["correct"] and out["checks"]["wrong_labels"]["value"] > 0


def test_step_count_altered_where_produced(run, monkeypatch):
    ops = _ops()
    orig = ops.fused_event_lif_early_exit

    def broken(*a, **k):
        res, steps = orig(*a, **k)
        return res, steps.at[0].add(1)

    monkeypatch.setattr(ops, "fused_event_lif_early_exit", broken)
    out = run(latency_mode=True)
    assert not out["correct"] and out["checks"]["wrong_steps"]["value"] > 0


def test_answers_handed_to_the_wrong_requests(run, monkeypatch):
    from repro.serving import scheduler
    orig = scheduler._Lane._serve_event

    def broken(self, images, k):
        delta = orig(self, images, k)
        delta["labels"][:k] = np.roll(delta["labels"][:k], 1)
        return delta

    monkeypatch.setattr(scheduler._Lane, "_serve_event", broken)
    out = run()
    assert not out["correct"] and out["checks"]["wrong_labels"]["value"] > 0
