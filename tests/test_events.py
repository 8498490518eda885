"""Event packing: roundtrip, determinism, overflow policy, calibration."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import events, ttfs


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip(seed):
    rng = np.random.RandomState(seed % 2**32)
    B, N, T = 3, 60, 8
    times = rng.randint(0, T + 1, (B, N)).astype(np.int32)
    e_max = events.calibrate_e_max(times, T, lane=8)
    frames = events.pack_events_batched(times, T, e_max)
    assert not np.any(np.asarray(frames.overflow))
    raster = np.asarray(events.unpack_to_raster(frames, N))
    expect = np.asarray(ttfs.frames_from_times(jnp.asarray(times), T))
    assert np.array_equal(raster, expect)


def test_batched_equals_loop_packer():
    rng = np.random.RandomState(0)
    times = rng.randint(0, 9, (4, 50)).astype(np.int32)
    a = events.pack_events(times, 8, 64)
    b = events.pack_events_batched(times, 8, 64)
    # same sets of ids per (b, t) — order within a step is id-sorted in both
    for bi in range(4):
        for t in range(8):
            ia = np.sort(np.asarray(a.ids[bi, t]))
            ib = np.sort(np.asarray(b.ids[bi, t]))
            assert np.array_equal(ia, ib)
    assert np.array_equal(np.asarray(a.count), np.asarray(b.count))


def test_overflow_flagged():
    times = np.zeros((1, 40), np.int32)        # all spike at t=0
    frames = events.pack_events_batched(times, 4, 16)
    assert bool(frames.overflow[0])
    full = events.pack_events_batched(times, 4, 64)
    assert not bool(full.overflow[0])


def test_overflow_boundary_exact_fit_is_not_overflow():
    """count == e_max exactly fills the buffer — NOT an overflow; one more
    event flips the flag. Guards the off-by-one at the buffer boundary."""
    T, e_max = 4, 16
    exact = np.full((1, e_max), 0, np.int32)       # 16 events at t=0
    frames = events.pack_events_batched(exact, T, e_max)
    assert not bool(frames.overflow[0])
    assert int(frames.count[0, 0]) == e_max
    assert not np.any(np.asarray(frames.ids[0, 0]) == events.PAD)

    over = np.full((1, e_max + 1), 0, np.int32)    # 17 events at t=0
    frames = events.pack_events_batched(over, T, e_max)
    assert bool(frames.overflow[0])
    assert int(frames.count[0, 0]) == e_max        # deterministic truncation
    # the kept ids are the e_max lowest (stable (time, id) order)
    assert np.array_equal(np.asarray(frames.ids[0, 0]), np.arange(e_max))


def test_overflow_boundary_loop_packer_matches():
    """The reference loop packer applies the same boundary rule."""
    T, e_max = 3, 8
    times = np.zeros((2, e_max + 1), np.int32)
    times[0, -1] = T                               # row 0: exactly e_max at t=0
    a = events.pack_events(times, T, e_max)
    b = events.pack_events_batched(times, T, e_max)
    assert np.array_equal(np.asarray(a.overflow), np.asarray(b.overflow))
    assert np.array_equal(np.asarray(a.overflow), [False, True])
    assert np.array_equal(np.asarray(a.count), np.asarray(b.count))


def test_calibrate_e_max_exact_lane_boundary_rounding():
    """A peak exactly on a lane multiple must NOT round up a whole extra
    lane; one past it must."""
    lane = 8
    times = np.zeros((1, lane), np.int32)          # peak == lane exactly
    assert events.calibrate_e_max(times, T=2, lane=lane) == lane
    times = np.zeros((1, lane + 1), np.int32)      # peak == lane + 1
    assert events.calibrate_e_max(times, T=2, lane=lane) == 2 * lane
    # headroom scaling rounds up through the boundary too
    times = np.zeros((1, lane), np.int32)
    assert events.calibrate_e_max(times, T=2, lane=lane,
                                  headroom=1.25) == 2 * lane


def test_calibrate_e_max_lane_aligned():
    rng = np.random.RandomState(1)
    times = rng.randint(0, 17, (16, 784)).astype(np.int32)
    e = events.calibrate_e_max(times, 16, lane=128)
    assert e % 128 == 0
    peak = max(int((times == t).sum(1).max()) for t in range(16))
    assert e >= peak


# ----------------------------------------- packer equivalence, adversarial
def _assert_packers_identical(times: np.ndarray, T: int, e_max: int) -> None:
    """ids, count AND overflow must match elementwise — not just as sets:
    the serving tier relies on deterministic (time, id)-ordered packing."""
    a = events.pack_events(times, T, e_max)
    b = events.pack_events_batched(times, T, e_max)
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert np.array_equal(np.asarray(a.count), np.asarray(b.count))
    assert np.array_equal(np.asarray(a.overflow), np.asarray(b.overflow))


def test_packers_identical_all_spikes_one_timestep():
    """Every input lands in a single step — first, last, and an interior
    one — at 3x the buffer depth, so truncation order matters."""
    T, e_max, N = 6, 16, 48
    for t in (0, T // 2, T - 1):
        times = np.full((3, N), t, np.int32)
        _assert_packers_identical(times, T, e_max)


def test_packers_identical_exact_emax_boundary():
    """Rows straddling the buffer boundary: e_max-1, e_max, and e_max+1
    events in one step (only the last may overflow)."""
    T, e_max = 4, 8
    for n_ev in (e_max - 1, e_max, e_max + 1):
        times = np.full((1, e_max + 4), T, np.int32)   # never-spike filler
        times[0, :n_ev] = 1
        _assert_packers_identical(times, T, e_max)


def test_packers_identical_all_never_spike_rows():
    """Rows of pure sentinel (time == T) mixed with live rows: no events,
    no counts, no overflow — and no contamination of neighbours."""
    T, e_max = 5, 8
    times = np.full((4, 20), T, np.int32)
    times[2, :5] = np.arange(5) % T                    # one live row
    _assert_packers_identical(times, T, e_max)
    frames = events.pack_events_batched(times, T, e_max)
    assert int(np.asarray(frames.count)[0].sum()) == 0
    assert np.all(np.asarray(frames.ids)[0] == events.PAD)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_packers_identical_tie_heavy_property(seed):
    """Property sweep biased toward ties: times drawn from a tiny palette
    {0, 1, T-1, T} so nearly every event collides with many others, with a
    deliberately small e_max so overflow is common."""
    rng = np.random.RandomState(seed % 2**32)
    B, N, T, e_max = 3, 40, 7, 8
    palette = np.array([0, 1, T - 1, T], np.int32)
    times = palette[rng.randint(0, len(palette), (B, N))]
    # sprinkle a few uniform times so steps besides the palette are hit too
    mask = rng.rand(B, N) < 0.2
    times = np.where(mask, rng.randint(0, T + 1, (B, N)), times)
    times = times.astype(np.int32)
    _assert_packers_identical(times, T, e_max)


# ------------------------------------------ device packer against the host's
def _device_pack_case(name: str):
    """(times, T, e_max) of one equality case."""
    rng = np.random.RandomState(11)
    if name == "random_images":
        images = rng.rand(8, 784).astype(np.float32)
        return np.asarray(ttfs.encode_ttfs(jnp.asarray(images), 32)), 32, 128
    if name == "same_tick_flood":        # 3x the depth in one step, per row
        T = 6
        times = np.full((3, 48), T, np.int32)
        for row, t in enumerate((0, T // 2, T - 1)):
            times[row] = t
        return times, T, 16
    if name == "exact_e_max":            # e_max - 1, e_max, e_max + 1 events
        T, e_max = 4, 8
        times = np.full((3, e_max + 4), T, np.int32)
        for row, n_ev in enumerate((e_max - 1, e_max, e_max + 1)):
            times[row, :n_ev] = 1
        return times, T, e_max
    if name == "never_spike_rows":
        T = 5
        times = np.full((4, 20), T, np.int32)
        times[2, :5] = np.arange(5) % T
        return times, T, 8
    if name == "zero_pad_rows":          # a served batch: 2 real rows of 8
        images = np.zeros((8, 784), np.float32)
        images[:2] = rng.rand(2, 784)
        return np.asarray(ttfs.encode_ttfs(jnp.asarray(images), 32)), 32, 128
    raise ValueError(name)


@pytest.mark.parametrize("name", ["random_images", "same_tick_flood",
                                  "exact_e_max", "never_spike_rows",
                                  "zero_pad_rows"])
def test_device_packer_equals_host_packer(name):
    """``pack_events_device`` under jit gives ``pack_events_batched``'s
    frames element for element, and each row's unclipped event count."""
    import jax
    times, T, e_max = _device_pack_case(name)
    want = events.pack_events_batched(times, T, e_max)
    ids, count, overflow, per_row = jax.jit(
        events.pack_events_device, static_argnums=(1, 2))(
            jnp.asarray(times, jnp.int32), T, e_max)
    assert np.array_equal(np.asarray(ids), np.asarray(want.ids))
    assert np.array_equal(np.asarray(count), np.asarray(want.count))
    assert np.array_equal(np.asarray(overflow), np.asarray(want.overflow))
    assert np.array_equal(np.asarray(per_row),
                          np.count_nonzero(times < T, axis=1))
    assert ids.dtype == jnp.int32 and count.dtype == jnp.int32
