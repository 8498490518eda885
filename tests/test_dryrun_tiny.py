"""Dry-run machinery integration test (subprocess: needs its own jax device
count, 8 placeholder CPU devices, mesh (2,2,2) pod/data/model).

Validates the exact pipeline launch/dryrun.py runs at production scale:
abstract ShapeDtypeStruct inputs + resolver shardings -> lower -> compile ->
memory/cost analysis -> while-scaled collective parse, for a train cell and
a decode cell of a reduced config — plus the kv_seqshard §Perf variant."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs.registry import get_config, reduced
from repro.configs.shapes import ShapeCell
from repro.distributed import sharding as SH, hloparse as HP
from repro.launch import specs as SP
from repro.launch.mesh import make_test_mesh
from repro.models.model import LM
from repro.training import lm_step, optim as O

mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced(get_config("yi-6b"))
lm = LM(cfg, constrain=SH.make_constrainer(mesh))
pspec = lm.param_specs(jnp.float32)
p_sh = SH.to_shardings(mesh, SH.param_pspecs(mesh, pspec))
out = {}

# --- train cell -----------------------------------------------------------
optimizer = O.get(cfg.optimizer, 1e-3)
opt_spec = jax.eval_shape(optimizer.init, pspec)
o_sh = SH.to_shardings(mesh, SH.param_pspecs(mesh, opt_spec))
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
b_sh = SH.to_shardings(mesh, SH.batch_pspec(mesh, batch))
step = jax.jit(lm_step.make_train_step(lm, optimizer),
               in_shardings=(p_sh, o_sh, b_sh))
with mesh:
    compiled = step.lower(pspec, opt_spec, batch).compile()
    hlo = compiled.as_text()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
coll = HP.collective_bytes_scaled(hlo)
out["train"] = {"flops": float(cost.get("flops", 0)),
                "coll_kinds": sorted(coll),
                "coll_total": sum(coll.values()),
                "temp_bytes": int(mem.temp_size_in_bytes)}

# --- decode cell (baseline + kv_seqshard variant) ---------------------------
for name, seq_shard in (("decode", False), ("decode_seqshard", True)):
    cache = lm.init_cache(8, 64, dtype=jnp.float32, abstract=True)
    c_sh = SH.to_shardings(mesh, SH.cache_pspecs(mesh, cache,
                                                 seq_shard=seq_shard))
    t_sh = SH.to_shardings(mesh, SH.batch_pspec(
        mesh, jax.ShapeDtypeStruct((8, 1), jnp.int32)))
    dstep = jax.jit(lm_step.make_serve_step(lm),
                    in_shardings=(p_sh, c_sh, t_sh))
    with mesh:
        compiled = dstep.lower(pspec, cache,
                               jax.ShapeDtypeStruct((8, 1), jnp.int32)).compile()
    coll = HP.collective_bytes_scaled(compiled.as_text())
    out[name] = {"coll_total": sum(coll.values())}

print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_pipeline_tiny_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    # train cell compiled, produced collectives, fits in (tiny) memory
    assert out["train"]["flops"] > 0
    assert out["train"]["coll_total"] > 0
    assert out["train"]["temp_bytes"] > 0
    # both decode shardings compile; both produce some collective traffic
    assert out["decode"]["coll_total"] >= 0
    assert out["decode_seqshard"]["coll_total"] >= 0
