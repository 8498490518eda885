"""Shared test fakes for the runtime registry.

``DivergentRuntime`` wraps the software reference and silently flips one
label and one first-spike time — the exact drift the agreement harness and
the conformance oracles exist to catch. ``BrokenRuntime`` raises on every
forward, as a program the device's compiler refuses would. ``registered_family`` temporarily
installs a factory in ``runtimes._REGISTRY`` and guarantees cleanup, so a
test cannot leak a fake family into the rest of the suite (which would fail
the registry-consistency oracle everywhere else). ``tiny_emax_artifact``
clones an artifact with an event-buffer depth far too small, which forces
the overflow → dense-reroute path.
"""

from __future__ import annotations

import contextlib
import copy

import numpy as np

from repro.core import runtimes
from repro.core.artifact import Artifact
from repro.core.reference import SNNOutput, SNNReference


class DivergentRuntime:
    def __init__(self, art):
        self._ref = SNNReference(art)

    def forward(self, images):
        out = self._ref.forward(images)
        labels = np.asarray(out.labels).copy()
        labels[0] = (labels[0] + 1) % max(2, int(labels.max()) + 1)
        first = np.asarray(out.first_spike).copy()
        first[0, 0] += 1
        return SNNOutput(labels, first, np.asarray(out.v_final),
                         np.asarray(out.steps))


@contextlib.contextmanager
def registered_family(name: str, factory):
    runtimes._REGISTRY[name] = factory
    try:
        yield
    finally:
        del runtimes._REGISTRY[name]


@contextlib.contextmanager
def divergent_family(name: str = "divergent"):
    with registered_family(name, lambda art, opts, **kw: DivergentRuntime(art)):
        yield


class BrokenRuntime:
    def __init__(self, art):
        self.program = art

    def forward(self, images):
        raise RuntimeError("program does not run on this device")


@contextlib.contextmanager
def broken_family(name: str = "broken"):
    with registered_family(name, lambda art, opts, **kw: BrokenRuntime(art)):
        yield


def tiny_emax_artifact(art: Artifact, e_max: int = 8) -> Artifact:
    """In-memory clone whose calibrated event-buffer depth is far too small —
    forces the overflow → dense-fallback path."""
    clone = Artifact(copy.deepcopy(art.meta), dict(art.arrays))
    clone.meta["events"]["e_max"] = e_max
    return clone
