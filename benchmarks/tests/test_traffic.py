"""The one generator: the same seed gives the same requests; another seed
gives the same sizes in another order."""

import numpy as np

from harness import traffic

MIX = {"prompt_len": {"dist": "log_uniform", "min": 64, "max": 1024, "levels": 8},
       "output_len": {"dist": "log_uniform", "min": 32, "max": 256, "levels": 8},
       "temperature": 0.0}


def take(seed, n):
    s = traffic.RequestStream(MIX, 32768, seed)
    return [s.next() for _ in range(n)]


def test_same_seed_same_requests():
    a, b = take(2**31 + 12345, 100), take(2**31 + 12345, 100)
    for x, y in zip(a, b):
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_other_seed_same_sizes_other_order():
    a, b = take(1, 64), take(2, 64)
    sizes = lambda rs: sorted((r["prompt"].size, r["max_new_tokens"]) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(b)
    assert [r["prompt"].size for r in a] != [r["prompt"].size for r in b]
    assert not np.array_equal(a[0]["prompt"][:16], b[0]["prompt"][:16])


def test_grid_is_inside_the_range_and_the_buckets():
    grid = traffic.length_grid(MIX["prompt_len"])
    assert grid == sorted(grid) and 64 <= grid[0] and grid[-1] <= 1024
    assert len(grid) == 8
    assert traffic.length_grid({"dist": "fixed", "value": 7}) == [7]


def test_train_batches_differ_by_step_and_repeat_by_seed():
    t0, l0 = traffic.train_batch(1000, 4, 16, 5, 0)
    t1, _ = traffic.train_batch(1000, 4, 16, 5, 1)
    again, _ = traffic.train_batch(1000, 4, 16, 5, 0)
    assert np.array_equal(t0, again) and not np.array_equal(t0, t1)
    assert np.array_equal(t0[:, 1:], l0[:, :-1])
    assert len({row.tobytes() for row in t0}) == 4
