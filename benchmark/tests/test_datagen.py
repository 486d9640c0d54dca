import numpy as np

from lib import datagen

TRAFFIC = {"base_samples": 8, "foreground": [0.02, 0.14]}


def test_same_seed_same_bytes_and_large_seeds():
    seed = 2**31 + 12345
    a = datagen.client_pool(seed, 1, 20, 32, TRAFFIC)
    b = datagen.client_pool(seed, 1, 20, 32, TRAFFIC)
    c = datagen.client_pool(seed + 1, 1, 20, 32, TRAFFIC)
    assert np.array_equal(a["images"], b["images"]) and np.array_equal(a["masks"], b["masks"])
    assert not np.array_equal(a["images"], c["images"])
    assert a["images"].dtype == np.uint8 and a["masks"].dtype == np.uint8
    assert set(np.unique(a["masks"])) <= {0, 1}


def test_rows_all_differ_and_foreground_spreads():
    pool = datagen.client_pool(7, 0, 40, 32, TRAFFIC)
    flat = pool["images"].reshape(40, -1)
    assert len(np.unique(flat, axis=0)) == 40
    assert 0.01 < pool["foreground"].min() < pool["foreground"].max() < 0.3


def test_feed_is_pure_in_the_round_and_orders_each_batch_by_foreground():
    pools = [datagen.client_pool(7, c, 24, 32, TRAFFIC) for c in range(2)]
    feed = datagen.RoundFeed(pools, 7, steps=6, batch=4)
    first = [x.copy() for x in feed(3)]
    other = [x.copy() for x in feed(4)]
    again = feed(3)
    assert all(np.array_equal(a, b) for a, b in zip(first, again))
    assert not np.array_equal(first[0], other[0])
    assert first[0].shape == (2, 6, 4, 32, 32, 3) and first[1].shape == (2, 6, 4, 32, 32, 1)
    share = first[1].reshape(2, 6, 4, -1).mean(axis=-1)
    assert np.all(np.diff(share, axis=-1) >= 0)
    # A round uses every sample of the pool once.
    idx = datagen.round_indices(pools[0], 7, 0, 3, 6, 4)
    assert sorted(idx.tolist()) == list(range(24))
