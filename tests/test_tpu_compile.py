"""Compile rehearsal: each Pallas kernel of the serving path, at the deployed
widths, compiled for a described TPU v5e chip with no chip attached.

Interpret mode (every other kernel test) cannot see the TPU's tiling rules;
the TPU compiler can, and refuses what the chip would refuse. The deployed
widths: B = 64 (``SNNServeEngine``'s default ``max_batch``), T = 32,
N_in = 784, N_pad = 256, E_max = 128, 150 outputs in 10 groups of 15. The
served event program (TTFS encode, device packing, fused kernel) is also
compiled whole, at N_pad 256 and 1664.

The topology is described inside a module fixture (never at import), so
only the pytest worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, T, N_IN, N_PAD, E_MAX, G, P = 64, 32, 784, 256, 128, 10, 15
LEAK = 4
I8, I32 = jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _case(name):
    """(kernel callable, [(shape, dtype)...]) at the deployed widths."""
    from repro.kernels.event_accum.kernel import event_accum_kernel
    from repro.kernels.fused_event_lif import kernel as fk
    from repro.kernels.lif.kernel import lif_fused_kernel
    from repro.kernels.spike_matmul.kernel import spike_matmul_kernel
    from repro.kernels.ttfs_decode.kernel import ttfs_decode_kernel
    events = [((B, T, E_MAX), I32), ((B, T), I32), ((N_IN, N_PAD), I8),
              ((N_PAD,), I32)]
    k_pad = 896                              # N_in padded to the MXU tile
    return {
        "fused_event_lif": (
            lambda *a: fk.fused_event_lif_kernel(*a, LEAK, interpret=False),
            events),
        "fused_event_lif_decode": (
            lambda *a: fk.fused_event_lif_decode_kernel(
                *a, LEAK, n_out=G * P, n_groups=G, per_group=P,
                interpret=False),
            events),
        "fused_event_lif_early_exit": (
            lambda *a: fk.fused_event_lif_early_exit_kernel(
                *a, LEAK, interpret=False),
            events),
        "lif": (
            lambda c, t: lif_fused_kernel(c, t, LEAK, interpret=False),
            [((T, B, N_PAD), I32), ((N_PAD,), I32)]),
        "event_accum": (
            lambda i, w: event_accum_kernel(i, w, interpret=False),
            [((B, T, E_MAX), I32), ((N_IN, N_PAD), I8)]),
        "ttfs_decode": (
            lambda f, v: ttfs_decode_kernel(f, v, n_groups=G, per_group=P,
                                            sentinel=T, interpret=False),
            [((B, G * P), I32), ((B, G * P), I32)]),
        "spike_matmul": (
            lambda x, w: spike_matmul_kernel(x, w, interpret=False),
            [((B * T, k_pad), I8), ((k_pad, N_PAD), I8)]),
    }[name]


@pytest.mark.parametrize("name", [
    "fused_event_lif", "fused_event_lif_decode", "fused_event_lif_early_exit",
    "lif", "event_accum", "ttfs_decode", "spike_matmul"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_rule() -> re.Pattern:
    """The benchmark's rule for the fused kernel's custom call."""
    import json
    from pathlib import Path
    rules = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                        / "chip" / "kernels.json").read_text())
    return re.compile(rules["kernels"]["fused_event_lif"]["match"])


@pytest.mark.parametrize("name", ["fused_event_lif_decode",
                                  "fused_event_lif_early_exit"])
def test_served_kernel_keeps_the_name_the_benchmark_reads(
        name, one_chip, no_persistent_cache):
    """The benchmark finds the fused kernel in a profiler trace by a rule on
    the custom call's HLO instruction (``benchmarks/chip/kernels.json``).
    The kernel's ``name`` fixes that instruction's name whatever jitted
    function calls it, so a refactor cannot silently leave the roofline
    with nothing to read."""
    rule = _kernel_rule()
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lines = [line.strip().removeprefix("ROOT ") for line in
             jax.jit(fn).lower(*args).compile().as_text().splitlines()]
    calls = [line for line in lines
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert calls[0].startswith(f"%{name}.") or calls[0].startswith(
        f"%{name} ")
    assert rule.search(calls[0])
    assert not [line for line in lines if line not in calls
                and rule.search(line)]


def _program(cell_config: str):
    """The lowered program of a benchmark configuration, its int8 weights
    and thresholds drawn at random."""
    import json
    from pathlib import Path

    import numpy as np

    from benchmarks.chip import model
    from repro.core.lowering import lower
    cfg = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                      / "chip" / "configs" / f"{cell_config}.json").read_text())
    rng = np.random.RandomState(0)
    dep = model.Deployment(
        cfg, rng.randint(-127, 128, (cfg["n_in"], cfg["n_out"])).astype(
            np.int8), rng.randint(1, 2000, cfg["n_out"]).astype(np.int32),
        0.01, 0.0)
    return lower(model.artifact(dep), cache=False)


@pytest.mark.parametrize("cell_config,n_pad", [("ttfs-784x150", N_PAD),
                                              ("ttfs-784x1600", 1664)],
                         ids=["n_pad_256", "n_pad_1664"])
@pytest.mark.parametrize("entry", ["event_images", "event_images_latency"])
def test_device_packed_event_program_compiles_for_v5e(
        entry, cell_config, n_pad, one_chip, no_persistent_cache,
        monkeypatch):
    """The served program: TTFS encode, the device packer's int8 matmuls
    and the fused kernel, as one jitted entry of the accelerator's bundle
    over a (64, 784) float32 image batch. The packer leaves no gather,
    sort or scatter in it: on a v5e a gather of the frames' 262,144 ids
    took 2.6 ms a batch, more than four times the kernel."""
    from repro.core.accelerator import _build_bundle
    prog = _program(cell_config)
    assert prog.w_padded.shape == (N_IN, n_pad)
    assert (prog.T, prog.e_max) == (T, E_MAX)
    # the kernel wrappers pick the Pallas path by the default backend,
    # which is the CPU here; the program is compiled for the v5e
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = _build_bundle(prog, "event", "fused")[entry]
    images = jax.ShapeDtypeStruct((B, N_IN), jnp.float32, sharding=one_chip)
    text = fn.lower(images).compile().as_text()
    assert not re.search(r"\s(gather|sort|scatter)\(", text)
    # one kernel call, under the name the benchmark's roofline reads
    rule = _kernel_rule()
    lines = [line.strip().removeprefix("ROOT ") for line in text.splitlines()]
    calls = [line for line in lines
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and rule.search(calls[0])
