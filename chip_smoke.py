"""Chip smoke test: serve the MNIST-TTFS classifier on one TPU through the
fused Pallas event path, and check it bit-exact against the reference.

    python chip_smoke.py

One process, one chip. Steps:

  1. Refuse to run unless JAX's first device is a TPU.
  2. Build the deployment artifact from seeds, with committed code only:
     procedural MNIST (8,192 training images), a few epochs of the dense
     proxy trainer on the chip, then ``deploy.export`` into a fresh
     ``results/chip_smoke/``.
  3. Pack the 10,000 test images' events twice, on the chip by the served
     packer and on the host by ``pack_events_batched``, and compare the
     frames element for element.
  4. Serve 10,000 procedural test images through ``SNNServeEngine`` with
     its defaults (``accelerator-event-fused``, ``max_batch=64``), full-T
     and then in latency mode; run the dense ``accelerator-batch`` runtime
     on the same images.
  5. Check every served label, and the first-spike times of the same specs
     run through ``make_runtime``, elementwise against ``SNNReference`` on
     the chip and on the host CPU; check the fused Pallas kernel is in the
     served program (``tpu_custom_call``) and that serving saw no fault.

Exits non-zero on any mismatch. The timings it prints are a smoke reading,
not a benchmark. The last line of stdout is the JSON verdict.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_TRAIN, N_TEST, EPOCHS = 8192, 10_000, 3
REF_CHUNK = 2000          # reference / dense rows per call
OUT_DIR = ROOT / "results" / "chip_smoke"


def require_tpu():
    """Print what JAX sees; exit non-zero unless it is a TPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"jax {jax.__version__}: platform={d.platform} "
          f"device_kind={d.device_kind!r} count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {d.platform!r}")
    return d, len(devs)


def build_artifact():
    """Seeded data -> dense-proxy training -> export into a fresh dir."""
    from repro.core import deploy
    from repro.core.artifact import Artifact
    from repro.data import mnist
    from repro.training.ttfs_trainer import train_dense_proxy
    t0 = time.perf_counter()
    xtr, ytr = mnist.generate(N_TRAIN, seed=1)
    res = train_dense_proxy(xtr, ytr, epochs=EPOCHS, seed=0)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    OUT_DIR.mkdir(parents=True)
    path = OUT_DIR / "mnist_ttfs.npz"
    deploy.export(res.model, str(path), calib_images=xtr, calib_labels=ytr)
    print(f"artifact: {res.steps} train steps, dense train acc "
          f"{res.train_acc:.4f}, exported to {path} in "
          f"{time.perf_counter() - t0:.1f}s")
    return Artifact.load(str(path))


def run_chunked(fn, images, chunk=REF_CHUNK):
    """fn(images chunk) -> SNNOutput; host arrays of (labels, first, steps)."""
    import numpy as np
    outs = [fn(images[i:i + chunk]) for i in range(0, len(images), chunk)]
    return tuple(np.concatenate([np.asarray(getattr(o, k)) for o in outs])
                 for k in ("labels", "first_spike", "steps"))


def reference(art, images, device=None):
    """SNNReference on ``device`` (default: the chip). A fresh program
    cache makes the host run lower its own copy of the program there."""
    import jax
    from repro.core import lowering
    from repro.core.reference import SNNReference
    if device is None:
        return run_chunked(SNNReference(art).forward, images)
    prev = lowering.install(lowering.ProgramCache())
    try:
        with jax.default_device(device):
            ref = SNNReference(art)
            probe = ref.forward(images[:1]).labels
            assert probe.devices() == {device}, probe.devices()
            return run_chunked(ref.forward, images)
    finally:
        lowering.install(prev)


def early_exit_view(first, T):
    """What latency mode must report, derived from a full-T run: only the
    neurons that fired at the earliest spike step keep their time, and the
    step count is that step + 1 (T when nothing fired)."""
    import numpy as np
    t_star = first.min(axis=1, keepdims=True)
    exp_first = np.where(first == t_star, first, T)
    steps = np.where(t_star[:, 0] < T, t_star[:, 0] + 1, T)
    return exp_first, steps


def served_path_outputs(art, images, max_batch, latency):
    """The engine's policy, replayed through ``make_runtime``: each
    ``max_batch`` chunk runs ``accelerator-event-fused``; rows whose events
    overflow the artifact's E_max take ``accelerator-batch`` (the documented
    reroute). Returns (labels, first, steps, overflow row mask)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import ttfs
    from repro.core.events import pack_events_batched
    from repro.core.lowering import lower
    from repro.core.runtimes import make_runtime
    prog = lower(art)
    fused = make_runtime(art, "accelerator-event-fused")
    dense = make_runtime(art, "accelerator-batch")
    labels, first, steps, over_rows = [], [], [], []
    for i in range(0, len(images), max_batch):
        x = np.zeros((max_batch, images.shape[1]), np.float32)
        k = len(images[i:i + max_batch])
        x[:k] = images[i:i + max_batch]
        times = np.asarray(ttfs.encode_ttfs(jnp.asarray(x), prog.T,
                                            prog.x_min))
        frames = pack_events_batched(times, prog.T, prog.e_max)
        out = fused.forward(frames=frames, latency_mode=latency,
                            check_overflow=False)
        lab, fst, stp = (np.array(out.labels), np.array(out.first_spike),
                         np.array(out.steps))
        over = np.asarray(frames.overflow)
        if over[:k].any():
            d = dense.forward(x)
            lab[over] = np.asarray(d.labels)[over]
            fst[over] = np.asarray(d.first_spike)[over]
            stp[over] = np.asarray(d.steps)[over]
        over_rows.append(over[:k])
        labels.append(lab[:k])
        first.append(fst[:k])
        steps.append(stp[:k])
    return (np.concatenate(labels), np.concatenate(first),
            np.concatenate(steps), np.concatenate(over_rows))


def kernel_in_program(eng, latency) -> bool:
    """Does the engine's compiled event program (encode, device packing,
    kernel over a ``max_batch`` image buffer) hold a Pallas TPU kernel?"""
    import jax
    import jax.numpy as jnp
    acc = eng.accel
    fn = acc._fwd_images_latency if latency else acc._fwd_images
    images = jax.ShapeDtypeStruct((eng.max_batch, acc.program.n_in),
                                  jnp.float32)
    return "tpu_custom_call" in fn.lower(images).compile().as_text()


def time_batch_ms(eng, images, latency, iters=50):
    """Median device time of one warm ``max_batch`` event call (the image
    buffer already on the device; encode and packing included), ended by
    block_until_ready."""
    import numpy as np
    import jax
    acc = eng.accel
    buf = jax.device_put(np.asarray(images[:eng.max_batch]))
    run = acc._fwd_images_latency if latency else acc._fwd_images
    jax.block_until_ready(run(buf))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(run(buf))
        ts.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(ts))


def device_pack_mismatches(art, images, max_batch):
    """Pack every ``max_batch`` chunk of ``images`` twice from the same
    spike times, encoded on the chip: on the chip by the served packer
    (``pack_events_device``) and on the host by ``pack_events_batched``.
    Returns the rows whose frames differ in ids, count, overflow or event
    total, and the overflowing rows."""
    import numpy as np
    import jax
    from repro.core import ttfs
    from repro.core.events import pack_events_batched, pack_events_device
    from repro.core.lowering import lower
    prog = lower(art)
    encode = jax.jit(lambda x: ttfs.encode_ttfs(x, prog.T, prog.x_min))
    pack = jax.jit(lambda t: pack_events_device(t, prog.T, prog.e_max))
    bad = {"ids": 0, "count": 0, "overflow": 0, "events": 0}
    overflow_rows = 0
    for i in range(0, len(images), max_batch):
        x = np.zeros((max_batch, images.shape[1]), np.float32)
        k = len(images[i:i + max_batch])
        x[:k] = images[i:i + max_batch]
        times = encode(x)
        ids, count, over, per_row = jax.device_get(pack(times))
        times = np.asarray(times)
        host = pack_events_batched(times, prog.T, prog.e_max)
        want = {"ids": np.asarray(host.ids), "count": np.asarray(host.count),
                "overflow": np.asarray(host.overflow),
                "events": np.count_nonzero(times < prog.T, axis=1)}
        got = {"ids": ids, "count": count, "overflow": over,
               "events": per_row}
        for key in bad:
            diff = got[key][:k] != want[key][:k]
            bad[key] += int(np.sum(diff.reshape(k, -1).any(axis=1)))
        overflow_rows += int(np.sum(want["overflow"][:k]))
    return bad, overflow_rows


def mismatches(got, want):
    """(label rows that differ, first-spike rows with any differing time,
    step rows that differ)."""
    import numpy as np
    return {"labels": int(np.sum(got[0] != want[0])),
            "first_spike": int(np.sum(np.any(got[1] != want[1], axis=1))),
            "steps": int(np.sum(got[2] != want[2]))}


def main() -> int:
    # the host-CPU reference needs JAX's CPU backend next to the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and plats != "cpu" and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    dev, count = require_tpu()
    import jax
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    from repro.core.runtimes import make_runtime
    from repro.data import mnist
    from repro.serving.snn_engine import SNNServeEngine

    art = build_artifact()
    T = int(art.m("encode", "T"))
    xte, yte = mnist.generate(N_TEST, seed=2)

    t0 = time.perf_counter()
    ref = reference(art, xte)
    print(f"reference on chip: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    ref_cpu = reference(art, xte, device=jax.devices("cpu")[0])
    print(f"reference on host CPU: {time.perf_counter() - t0:.1f}s")
    bad = {"chip reference vs CPU reference": mismatches(ref_cpu, ref)}

    t0 = time.perf_counter()
    pack_bad, over_rows = device_pack_mismatches(art, xte, 64)
    print(f"device-packed vs host-packed frames: {N_TEST} images, "
          f"{over_rows} overflow rows, {time.perf_counter() - t0:.1f}s")
    bad["device-packed vs host-packed frames"] = pack_bad

    print("timings below are a smoke reading, not a benchmark")
    for latency in (False, True):
        mode = "latency" if latency else "full-T"
        t0 = time.perf_counter()
        eng = SNNServeEngine(art, latency_mode=latency)
        compile_s = time.perf_counter() - t0
        if not kernel_in_program(eng, latency):
            sys.exit(f"chip_smoke: no tpu_custom_call in the {mode} "
                     "event program — the fused Pallas kernel is not served")
        print(f"[{mode}] served program holds tpu_custom_call; engine "
              f"build + warm-up (compile) {compile_s:.2f}s")
        t0 = time.perf_counter()
        labels = eng.classify(xte)
        serve_s = time.perf_counter() - t0
        st = eng.stats()
        batch_ms = time_batch_ms(eng, xte, latency)
        eng.close()
        faults = {k: st[k] for k in ("errors", "lane_faults", "quarantines",
                                     "breaker_degraded")}
        print(f"[{mode}] {len(labels)} images in {st['batches']} batches, "
              f"{serve_s:.2f}s served ({1e3 * serve_s / st['batches']:.3f} "
              f"ms/batch end to end); warm device call "
              f"{batch_ms:.3f} ms/batch of {eng.max_batch}; accuracy "
              f"{np.mean(labels == yte):.4f}; overflow_fallbacks "
              f"{st['overflow_fallbacks']}; {faults}")
        if any(faults.values()):
            sys.exit(f"chip_smoke: serving recorded faults: {faults}")
        got = served_path_outputs(art, xte, eng.max_batch, latency)
        over = got[3]
        print(f"[{mode}] make_runtime replay: {int(over.sum())} overflow rows "
              "rerouted to accelerator-batch")
        if not np.array_equal(got[0], labels):
            sys.exit(f"chip_smoke: make_runtime replay labels differ from "
                     f"the engine's in {int(np.sum(got[0] != labels))} rows")
        for name, want in (("chip", ref), ("CPU", ref_cpu)):
            w = want
            if latency:
                # rerouted rows ran the dense path: full-T, no early exit
                first, steps = early_exit_view(want[1], T)
                first[over], steps[over] = want[1][over], want[2][over]
                w = (want[0], first, steps)
            bad[f"{mode} engine vs {name} reference"] = mismatches(
                (labels, got[1], got[2]), w)

    dense = make_runtime(art, "accelerator-batch")
    got = run_chunked(dense.forward, xte)
    print(f"[accelerator-batch] accuracy {np.mean(got[0] == yte):.4f}")
    for name, want in (("chip", ref), ("CPU", ref_cpu)):
        bad[f"accelerator-batch vs {name} reference"] = mismatches(got, want)

    print(f"mismatch counts over {N_TEST} images (rows):")
    for k, v in bad.items():
        print(f"  {k}: {v}")
    if any(n for v in bad.values() for n in v.values()):
        sys.exit("chip_smoke: outputs differ from the reference")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
