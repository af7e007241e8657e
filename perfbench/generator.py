"""The one traffic generator: reads a mix from ``traffic/<name>.json``.

A mix is a list of operations with counts (one block). Blocks are
drawn one after another, each shuffled by the seed, and a window runs
whole blocks, so the shares of the mix are exact in every window and
each operation class keeps its place in the latency distribution. Each
operation draws its own parameters (``ops/<op>.py``'s ``draw``) from the
block's random stream and, where it takes keys, from the mix's key
distribution.
"""

from __future__ import annotations

import numpy as np

# Independent random streams of one seed: set-up warm-up and the window
# never share a draw.
STREAMS = {"window": 1, "warmup": 2}

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """FNV-1a over the eight little-endian bytes of each int64, as
    YCSB's ``Utils.fnvhash64`` scrambles its Zipfian draws."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= _FNV_PRIME
        v >>= np.uint64(8)
    return h


class ScrambledZipfian:
    """YCSB's scrambled Zipfian over ``[0, n)`` (core workload C).

    Ranks follow Gray et al.'s Zipfian generator ("Quickly generating
    billion-record synthetic databases", SIGMOD 1994) with constant
    ``theta``; each rank is then hashed (FNV-1a 64) into the key space,
    so the hot keys are spread over it rather than clustered at 0."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = int(n), float(theta)
        ranks = np.arange(1, self.n + 1, dtype=np.float64)
        self.zetan = float(np.sum(ranks ** -self.theta))
        zeta2 = 1.0 + 0.5 ** self.theta
        self.alpha = 1.0 / (1.0 - self.theta)
        self.eta = (1.0 - (2.0 / self.n) ** (1.0 - self.theta)) / (1.0 - zeta2 / self.zetan)
        self._one = 1.0 + 0.5 ** self.theta

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * self.zetan
        r = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha).astype(np.int64)
        r = np.where(uz < self._one, 1, r)
        r = np.where(uz < 1.0, 0, r)
        return np.minimum(r, self.n - 1)

    def keys(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return (fnv1a64(self.ranks(rng, size)) % np.uint64(self.n)).astype(np.int64)


def key_sampler(spec: dict | None, domain: int):
    if spec is None:
        return None
    if spec["distribution"] != "scrambled_zipfian":
        raise ValueError(f"unknown key distribution {spec['distribution']!r}")
    return ScrambledZipfian(domain, spec["theta"])


def blocks(traffic: dict, ops: dict, domain: int, seed: int, which: str):
    """Endless blocks of the mix for one seed and stream, each a list of
    (op name, params) in shuffled order.

    `ops` maps each op name of the mix to its module; `domain` is the
    number of keys (orders) the data holds."""
    rng = np.random.default_rng([int(seed), STREAMS[which]])
    keys = key_sampler(traffic.get("keys"), domain)
    entries = [e for e in traffic["block"] for _ in range(e["count"])]
    while True:
        yield [
            (entries[i]["op"], ops[entries[i]["op"]].draw(rng, entries[i], keys, domain))
            for i in rng.permutation(len(entries))
        ]


def stream(traffic: dict, ops: dict, domain: int, seed: int, which: str):
    """The blocks' (op name, params) pairs one after another."""
    for block in blocks(traffic, ops, domain, seed, which):
        yield from block
