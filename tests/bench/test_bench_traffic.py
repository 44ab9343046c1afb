"""The open-loop schedule: deterministic by seed, the same sizes and gaps
for every seed, inside the window."""
import json

import numpy as np

from benchmarks.chip.drivers.open_loop import schedule
from benchmarks.chip.harness import HERE, seed_rng

MIX = json.loads((HERE / "traffic" / "serve_poisson.json").read_text())


def test_same_seed_same_schedule():
    a = schedule(MIX, 2.0, 2**33 + 1)
    b = schedule(MIX, 2.0, 2**33 + 1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_seeds_share_sizes_and_gaps_in_another_order():
    due_a, size_a, off_a = schedule(MIX, 2.0, 1)
    due_b, size_b, off_b = schedule(MIX, 2.0, 2)
    assert len(due_a) == len(due_b) == round(MIX["rate_rps"] * 2.0)
    np.testing.assert_array_equal(np.sort(size_a), np.sort(size_b))
    assert not np.array_equal(size_a, size_b)
    gaps = lambda due: np.diff(np.append(due, 2.0))
    np.testing.assert_allclose(np.sort(gaps(due_a)), np.sort(gaps(due_b)),
                               rtol=1e-9, atol=1e-12)
    assert not np.array_equal(off_a, off_b)


def test_every_seed_offers_the_same_rows_in_every_block():
    block = MIX["shuffle_block"]
    sums = []
    for seed in (1, 2, 2**33 + 5):
        due, sizes, _ = schedule(MIX, 4.0, seed)
        sums.append(np.add.reduceat(sizes, np.arange(0, len(sizes), block)))
        gaps = np.diff(np.append(due, 4.0))
        # the gaps of a block span the same time for every seed
        sums.append(np.round(np.add.reduceat(gaps, np.arange(0, len(gaps), block)), 9))
    np.testing.assert_array_equal(sums[0], sums[2])
    np.testing.assert_array_equal(sums[0], sums[4])
    np.testing.assert_allclose(sums[1], sums[3], atol=1e-9)
    np.testing.assert_allclose(sums[1], sums[5], atol=1e-9)


def test_schedule_stays_in_the_window_and_the_pool():
    due, sizes, offsets = schedule(MIX, 3.0, 5)
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 3.0
    assert sizes.min() >= MIX["size_min"] and sizes.max() <= MIX["size_max"]
    assert np.all(offsets + sizes <= MIX["pool_rows"])
    # lognormal with median size_median
    assert abs(np.median(sizes) - MIX["size_median"]) <= 1


def test_seed_rng_takes_large_and_negative_seeds():
    a = seed_rng(2**40 + 3).random(3)
    assert not np.array_equal(a, seed_rng(2**40 + 4).random(3))
    assert not np.array_equal(seed_rng(-5).random(3), seed_rng(5).random(3))
