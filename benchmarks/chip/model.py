"""The deployed classifier, made by the benchmark from the run's seed, and
handed to the program as its deployment artifact.

Each output neuron's weights start as a class prototype (the mean of a few
training images of its group's class, less the mean training image) and are
then trained as the program's dense proxy is: cross-entropy on the group-mean
logits of W x, with Adam, on the device in one call. They are held as int8
per tensor. Each neuron's threshold is a quantile of its peak membrane
over the training images times a scale; of the configuration's
(quantile, scale) pairs the one with the best training accuracy wins. The
weights and thresholds are the benchmark's own integers: the reference reads
them from here, never from the program.

This is the first deployment module. A configuration file names its module
under ``"module"`` (a module of ``benchmarks/chip/``; ``model`` when the key
is absent), and the harness reaches the deployment only through these five
functions of it:

  * ``build(cfg, seed) -> dep``: the deployment from the seed; ``dep`` has
    ``train_accuracy``;
  * ``artifact(dep) -> Artifact``: the program's deployment artifact;
  * ``answers(dep, images, latency_mode, control=False) -> (labels, steps)``:
    the plain reference's label and step count of every image; with
    ``control=True``, the control's (one precision step below the stated
    one) in its place;
  * ``events(dep, images) -> (B, L)`` ints: the events each image feeds
    into each of the ``L`` layers on the event path;
  * ``widths(cfg) -> [(n_in, n_out), ...]``: one pair per layer, real
    neurons only.

A new configuration joins with its config file, its module and the module's
own plain reference, and edits no file the benchmark has. This module's
reference is ``reference.py``: one TTFS layer, ``L = 1``.
"""

from __future__ import annotations

import dataclasses

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import data, reference, work

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


@functools.partial(jax.jit, static_argnames=("n_groups", "lr"))
def _train(w, x, y, batches, n_groups: int, lr: float):
    """Adam on the group-mean-logit cross-entropy, one step per row of
    ``batches`` (image indices)."""
    def loss(w, xb, yb):
        z = xb @ w
        logits = z.reshape(z.shape[0], n_groups, -1).mean(axis=-1)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, yb[:, None], axis=1).mean()

    def step(carry, idx):
        w, m, v, t = carry
        g = jax.grad(loss)(w, x[idx], y[idx])
        t = t + 1
        m = ADAM_B1 * m + (1 - ADAM_B1) * g
        v = ADAM_B2 * v + (1 - ADAM_B2) * g * g
        mh = m / (1 - ADAM_B1 ** t)
        vh = v / (1 - ADAM_B2 ** t)
        return (w - lr * mh / (jnp.sqrt(vh) + ADAM_EPS), m, v, t), None

    z = jnp.zeros_like(w)
    (w, _, _, _), _ = jax.lax.scan(step, (w, z, z, jnp.float32(0)), batches)
    return w


@dataclasses.dataclass(frozen=True)
class Deployment:
    cfg: dict
    w_int8: np.ndarray       # (n_in, n_out) int8
    thresholds: np.ndarray   # (n_out,) int32
    scale: float             # float weight = int8 weight * scale
    train_accuracy: float


def build(cfg: dict, seed: int) -> Deployment:
    """Weights and thresholds for ``cfg`` from ``seed``."""
    seed_images, seed_pick = data.sub_seeds(seed, 2)
    x, y = data.generate(cfg["train_images"], seed_images)
    rng = np.random.RandomState(seed_pick)
    n_in, n_out, per = cfg["n_in"], cfg["n_out"], cfg["per_group"]
    k = cfg["prototype_images"]
    by_class = [np.flatnonzero(y == g) for g in range(cfg["n_groups"])]
    w = np.empty((n_in, n_out), np.float32)
    for n in range(n_out):
        pick = rng.choice(by_class[n // per], k, replace=False)
        w[:, n] = x[pick].mean(axis=0)
    w -= x.mean(axis=0)[:, None]
    batches = rng.randint(0, len(x), (cfg["train_steps"], cfg["train_batch"]))
    w = np.asarray(_train(jnp.asarray(w), jnp.asarray(x), jnp.asarray(y),
                          jnp.asarray(batches), cfg["n_groups"],
                          cfg["train_lr"]))
    qmax = 2 ** (cfg["weight_bits"] - 1) - 1
    scale = float(np.abs(w).max()) / qmax
    w_int8 = np.clip(np.round(w / scale), -qmax, qmax).astype(np.int8)

    T = cfg["T"]
    times = reference.encode(x, T, cfg["x_min"])
    never = np.full(n_out, np.iinfo(np.int32).max, np.int32)
    _, peak, _ = reference.layer(times, w_int8, never, T, cfg["leak_shift"])
    cands = np.stack([
        np.maximum(1, np.quantile(peak, q, axis=0) * s).astype(np.int32)
        for q in cfg["threshold_quantiles"]
        for s in cfg["threshold_scales"]])
    first, _, v_final = reference.layer(times, w_int8, cands, T,
                                        cfg["leak_shift"])
    acc = [float(np.mean(reference.decode(
        f, v_final, cfg["n_groups"], per, T, cfg["fallback"]) == y))
        for f in first]
    best = int(np.argmax(acc))
    return Deployment(cfg, w_int8, cands[best], scale, acc[best])


def artifact(dep: Deployment):
    """The program's deployment artifact for ``dep``, laid out by the
    program's own planner as its export would lay it out."""
    from repro.core import codesign
    from repro.core.artifact import Artifact
    c = dep.cfg
    gids = np.repeat(np.arange(c["n_groups"], dtype=np.int32),
                     c["per_group"])
    report = codesign.plan(c["n_in"], c["n_out"])
    if report.lane != c["lane"]:
        raise ValueError(f"planner lane {report.lane} != config lane "
                         f"{c['lane']}")
    meta = {
        "model": {"topology": "linear-ttfs", "n_in": c["n_in"],
                  "n_out": c["n_out"]},
        "encode": {"T": c["T"], "x_min": c["x_min"]},
        "lif": {"leak_shift": c["leak_shift"], "v_init": 0},
        "readout": {"n_groups": c["n_groups"], "per_group": c["per_group"],
                    "fallback": c["fallback"]},
        "quant": {"scale": dep.scale, "bits": c["weight_bits"],
                  "scheme": "symmetric-per-tensor"},
        "events": {"e_max": c["e_max"], "pad": -1},
        "codesign": {"lane": report.lane, "n_pad": report.n_pad,
                     "n_blocks": report.n_blocks,
                     "vmem_util": report.vmem_util,
                     "limiter": report.limiter},
    }
    arrays = {"w_float": dep.w_int8.astype(np.float32) * np.float32(dep.scale),
              "w_int8": dep.w_int8, "thresholds": dep.thresholds,
              "group_ids": gids,
              **codesign.blocked_layout(dep.w_int8, dep.thresholds, gids,
                                        report.lane)}
    return Artifact(meta, arrays)


def answers(dep: Deployment, images: np.ndarray, latency_mode: bool,
            control: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """The reference's (label, step count) of every image; with ``control``,
    the reference's at int4 weights."""
    weights = reference.int4_weights(dep.w_int8) if control else None
    return reference.answers(dep, images, latency_mode, weights=weights)


def events(dep: Deployment, images: np.ndarray) -> np.ndarray:
    """(B, 1): the events each image feeds into the one layer."""
    c = dep.cfg
    times = reference.encode(images, c["T"], c["x_min"])
    return work.events(times, c["T"], c["e_max"])[:, None]


def widths(cfg: dict) -> list[tuple[int, int]]:
    return [(cfg["n_in"], cfg["n_out"])]
