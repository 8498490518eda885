"""Compile rehearsal: the fused event kernels each cell serves, at the
cell's widths (B = max_batch, T, E_max, n_in x n_pad), compiled for a
described TPU v5e with no chip attached.

The topology is described inside a module fixture, never at import, so
only the pytest worker that runs this file loads the TPU library.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
MAX_BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        before = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", before)
            cc.reset_cache()


@pytest.mark.parametrize("name", ["ttfs-784x150", "ttfs-784x1600"])
@pytest.mark.parametrize("variant", ["decode", "early_exit"])
def test_fused_kernel_compiles_for_v5e(one_chip, name, variant):
    from repro.core.codesign import pad_to_lane
    from repro.kernels.fused_event_lif import kernel as fk
    c = json.loads((CONFIGS / f"{name}.json").read_text())
    n_pad = pad_to_lane(c["n_out"], c["lane"])
    shapes = [((MAX_BATCH, c["T"], c["e_max"]), jnp.int32),
              ((MAX_BATCH, c["T"]), jnp.int32),
              ((c["n_in"], n_pad), jnp.int8), ((n_pad,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if variant == "decode":
        def fn(*a):
            return fk.fused_event_lif_decode_kernel(
                *a, c["leak_shift"], n_out=c["n_out"],
                n_groups=c["n_groups"], per_group=c["per_group"],
                fallback=c["fallback"], interpret=False)
    else:
        def fn(*a):
            return fk.fused_event_lif_early_exit_kernel(
                *a, c["leak_shift"], interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= c["n_in"] * n_pad
