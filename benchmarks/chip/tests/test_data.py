"""The benchmark's copy of the procedural-MNIST generator still matches the
program's, and the arrival schedule is what the cells assume."""

import numpy as np
import pytest

from benchmarks.chip import data


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1])
def test_generator_copy_matches_program(seed):
    from repro.data import mnist
    x, y = data.generate(64, seed)
    x_prog, y_prog = mnist.generate(64, seed)
    assert np.array_equal(y, y_prog)
    assert np.array_equal(x, x_prog)


def test_sub_seeds_take_seeds_past_32_bits():
    a = data.sub_seeds(2**33 + 5, 4)
    assert a == data.sub_seeds(2**33 + 5, 4)
    assert a != data.sub_seeds(5, 4)
    assert all(0 <= s < 2**32 for s in a)
    with pytest.raises(ValueError):
        data.sub_seeds(-1, 2)


def test_poisson_schedule_offers_a_fixed_count_in_the_window():
    a = data.poisson_schedule(500, 10, seed=1)
    b = data.poisson_schedule(500, 10, seed=2)
    assert len(a) == len(b) == 5000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 10
    assert not np.array_equal(a, b)
    gaps = np.diff(a)
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    assert abs(gaps.mean() - 1 / 500) < 1e-4
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
