"""Make the benchmark and the program importable from the repo root."""

import sys
import types
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parents[3]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def stand_in(monkeypatch):
    """Makes ``benchmarks.chip.stand_in``: a deployment module that wraps
    ``model`` and records the name of each function called, in ``calls``.
    With ``lie=True`` its reference answers every label one group off."""
    from benchmarks.chip import model

    def make(lie: bool = False) -> types.ModuleType:
        mod = types.ModuleType("benchmarks.chip.stand_in")
        mod.calls = []

        def wrap(name, after=lambda out: out):
            def call(*args, **kw):
                mod.calls.append(name)
                return after(getattr(model, name)(*args, **kw))
            setattr(mod, name, call)

        for name in ("build", "artifact", "answers", "events", "widths"):
            wrap(name)
        if lie:
            wrap("answers", lambda out: ((out[0] + 1) % 10, out[1]))
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
        return mod

    return make
