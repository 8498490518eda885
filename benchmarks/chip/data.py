"""The benchmark's own inputs: procedural MNIST images and arrival schedules.

``generate`` is a copy of the program's procedural-MNIST generator
(``repro.data.mnist.generate``), kept here so that a change to the program's
data module cannot move the yardstick; ``tests/test_data.py`` checks the copy
still matches the original image for image. Every draw comes from the seed.
"""

from __future__ import annotations

import numpy as np

_GLYPHS = {  # 7x5 classic bitmap font
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00010", "00100", "01000", "11111"],
    3: ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}


def _glyph_images() -> np.ndarray:
    """(10, 28, 28) float32 smoothed glyph templates."""
    out = np.zeros((10, 28, 28), np.float32)
    for d, rows in _GLYPHS.items():
        bmp = np.array([[int(c) for c in r] for r in rows], np.float32)
        big = np.kron(bmp, np.ones((3, 3), np.float32))
        img = np.zeros((28, 28), np.float32)
        img[3:24, 6:21] = big
        pad = np.pad(img, 1)
        img = sum(pad[i:i + 28, j:j + 28] for i in range(3) for j in range(3)) / 9
        out[d] = np.clip(img * 1.6, 0, 1)
    return out


def _affine_batch(imgs: np.ndarray, rng: np.random.RandomState) -> np.ndarray:
    """Random affine per image with bilinear resampling."""
    B = imgs.shape[0]
    ang = rng.uniform(-0.30, 0.30, B)
    scale = rng.uniform(0.80, 1.20, B)
    shear = rng.uniform(-0.25, 0.25, B)
    tx = rng.uniform(-2.5, 2.5, B)
    ty = rng.uniform(-2.5, 2.5, B)
    c, s = np.cos(ang) / scale, np.sin(ang) / scale
    yy, xx = np.mgrid[0:28, 0:28].astype(np.float32)
    xc, yc = (xx - 13.5).ravel(), (yy - 13.5).ravel()
    sx = c[:, None] * xc + (s[:, None] + shear[:, None]) * yc + 13.5 - tx[:, None]
    sy = -s[:, None] * xc + c[:, None] * yc + 13.5 - ty[:, None]
    x0 = np.floor(sx).astype(np.int32)
    y0 = np.floor(sy).astype(np.int32)
    fx, fy = sx - x0, sy - y0

    def grab(yi, xi):
        yi = np.clip(yi, 0, 27)
        xi = np.clip(xi, 0, 27)
        return imgs[np.arange(B)[:, None], yi, xi]

    out = (grab(y0, x0) * (1 - fx) * (1 - fy) + grab(y0, x0 + 1) * fx * (1 - fy)
           + grab(y0 + 1, x0) * (1 - fx) * fy + grab(y0 + 1, x0 + 1) * fx * fy)
    return out.reshape(B, 28, 28)


def generate(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(images (n, 784) float32 in [0, 1], labels (n,) int32)."""
    rng = np.random.RandomState(seed)
    glyphs = _glyph_images()
    labels = rng.randint(0, 10, n).astype(np.int32)
    imgs = _affine_batch(glyphs[labels], rng)
    imgs *= rng.uniform(0.7, 1.0, (n, 1, 1))
    imgs += rng.normal(0, 0.08, imgs.shape)
    imgs = np.clip(imgs, 0, 1).astype(np.float32)
    return imgs.reshape(n, 784), labels


def sub_seeds(seed: int, n: int) -> list[int]:
    """``n`` independent 32-bit seeds from one run seed of any size."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(n)]


def poisson_schedule(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson arrival process at
    ``rate_per_s``, conditioned on exactly ``round(rate * seconds)`` arrivals
    in the window: every seed offers the same number of requests, in a
    different order in time. Exponential gaps, normalised to the window."""
    n = int(round(rate_per_s * seconds))
    gaps = np.random.RandomState(seed).exponential(1.0, size=n + 1)
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()
