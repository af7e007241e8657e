"""The traffic generator: exact shares per block, skewed scrambled
keys, the same stream for the same seed, warm-up apart from the window."""

import collections
import itertools

import numpy as np

from perfbench import generator, harness


def _ops(cell):
    return cell.ops


def test_lookup_mix_shares_are_exact_in_every_block():
    cell = harness.load_cell("sf1_lookup")
    s = generator.stream(cell.traffic, cell.ops, 1_500_000, 2**31 + 99, "window")
    ops = list(itertools.islice(s, 100))
    for b in range(10):
        block = collections.Counter(name for name, _ in ops[10 * b:10 * b + 10])
        assert block == {"point_lookup": 9, "short_scan": 1}
    assert all(0 <= p["key"] < 1_500_000 for n, p in ops if n == "point_lookup")
    assert all(p["span"] == 3000 and 0 <= p["lo"] <= 1_500_000 - 3000
               for n, p in ops if n == "short_scan")


def test_stream_repeats_per_seed_and_warmup_differs():
    cell = harness.load_cell("sf1_lookup")

    def first(seed, which):
        return list(itertools.islice(
            generator.stream(cell.traffic, cell.ops, 10_000, seed, which), 30))

    assert first(5, "window") == first(5, "window")
    assert first(5, "window") != first(6, "window")
    assert first(5, "window") != first(5, "warmup")


def test_scrambled_zipfian_is_skewed_and_in_range():
    z = generator.ScrambledZipfian(100_000, 0.99)
    keys = z.keys(np.random.default_rng(1), 200_000)
    assert keys.min() >= 0 and keys.max() < 100_000
    counts = np.sort(np.bincount(keys, minlength=100_000))[::-1]
    # theta = 0.99: the hottest key takes about 1/zeta(n) of the draws
    assert abs(counts[0] / len(keys) - 1 / z.zetan) < 0.01
    # scrambled: the hottest keys are not the lowest ones
    top = np.argsort(np.bincount(keys, minlength=100_000))[::-1][:10]
    assert top.max() > 1000


def test_fnv1a64_matches_a_byte_loop():
    def slow(v):
        h = 0xCBF29CE484222325
        for i in range(8):
            h ^= (v >> (8 * i)) & 0xFF
            h = (h * 1099511628211) % 2**64
        return h

    vals = np.array([0, 1, 255, 2**40 + 3], dtype=np.int64)
    assert [int(x) for x in generator.fnv1a64(vals)] == [slow(int(v)) for v in vals]
