"""The plain reference agrees with the program's own integer reference on
the benchmark's deployments, and its control (int4 weights) does not; the
deployment module's ``answers`` gives the same as the reference it wraps."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import data, model, reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def small(name, **over):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(train_images=256, train_steps=8, **over)
    return cfg


@pytest.fixture(scope="module", params=["ttfs-784x150", "ttfs-784x1600"])
def deployment(request):
    return model.build(small(request.param), seed=2**32 + 3)


def test_reference_matches_the_program_reference(deployment):
    from repro.core.reference import SNNReference
    from repro.core.lowering import ProgramCache, install
    prev = install(ProgramCache())
    try:
        x, _ = data.generate(96, 5)
        labels, steps = reference.answers(deployment, x, latency_mode=False)
        out = SNNReference(model.artifact(deployment)).forward(x)
        assert np.array_equal(labels, np.asarray(out.labels))
        assert np.all(steps == deployment.cfg["T"])
        m_labels, m_steps = model.answers(deployment, x, latency_mode=False)
        assert np.array_equal(m_labels, np.asarray(out.labels))
        assert np.array_equal(m_steps, steps)
    finally:
        install(prev)


def test_latency_steps_follow_the_first_spike(deployment):
    from repro.core.lowering import ProgramCache, install
    from repro.core.runtimes import make_runtime
    prev = install(ProgramCache())
    try:
        x, _ = data.generate(64, 6)
        labels, steps = reference.answers(deployment, x, latency_mode=True)
        rt = make_runtime(model.artifact(deployment), "accelerator-event",
                          kernel="fused", latency_mode=True)
        out = rt.forward(x, latency_mode=True)
        assert np.array_equal(labels, np.asarray(out.labels))
        assert np.array_equal(steps, np.asarray(out.steps))
        m_labels, m_steps = model.answers(deployment, x, latency_mode=True)
        assert np.array_equal(m_labels, np.asarray(out.labels))
        assert np.array_equal(m_steps, np.asarray(out.steps))
    finally:
        install(prev)


def test_overflow_rows_take_all_steps(deployment):
    cfg = deployment.cfg
    x = np.ones((2, cfg["n_in"]), np.float32)   # every pixel at step 0
    _, steps = reference.answers(deployment, x, latency_mode=True)
    assert np.all(steps == cfg["T"])
    _, steps = model.answers(deployment, x, latency_mode=True)
    assert np.all(steps == cfg["T"])


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_int4_control_is_not_correct(deployment, seed):
    """The control: the reference at int4 weights, one step below the int8
    the configuration states, gives labels that differ on the cell's
    traffic, so the limit of 0 wrong labels fails it."""
    x, _ = data.generate(512, seed)
    want, want_steps = reference.answers(deployment, x, latency_mode=True)
    got, got_steps = reference.answers(
        deployment, x, latency_mode=True,
        weights=reference.int4_weights(deployment.w_int8))
    assert np.sum(got != want) > 0
    assert np.sum(got_steps != want_steps) > 0
    m_got, m_got_steps = model.answers(deployment, x, latency_mode=True,
                                       control=True)
    assert np.array_equal(m_got, got)
    assert np.array_equal(m_got_steps, got_steps)


def test_int4_weights_have_sixteen_levels():
    w = np.arange(-127, 128, dtype=np.int8).reshape(-1, 1)
    w4 = reference.int4_weights(w)
    assert len(np.unique(w4)) == 16 and np.all(w4 % 16 == 0)
