"""Reference index contents after a full refresh.

A covering index over key column K with B buckets holds every source
row once, projected to its indexed and included columns, in bucket
``h(K) mod B``, and each bucket's rows sorted by K. ``h`` is the
index's documented int64 hash: murmur3's fmix32 of the low word xor
fmix32(high word) * 0x9E3779B1. Bucket b is ``bucket-<b:05d>.parquet``
in the version directory."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pyarrow.parquet as pq


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def bucket_of(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    k = keys.astype(np.int64)
    lo = (k & 0xFFFFFFFF).astype(np.uint32)
    hi = ((k >> 32) & 0xFFFFFFFF).astype(np.uint32)
    h = _fmix32(lo ^ (_fmix32(hi) * np.uint32(0x9E3779B1)))
    return (h % np.uint32(num_buckets)).astype(np.int64)


def index_spec(config: dict, name: str) -> dict:
    return next(i for i in config["indexes"] if i["name"] == name)


def answer(params, data):
    """The index as the reference builds it: {bucket: columns}."""
    spec = index_spec(data.config, params["index"])
    (key,) = spec["indexed"]
    names = [key, *spec["included"]]
    cols = data.columns(spec["table"], names)
    bucket = bucket_of(cols[key], data.config["num_buckets"])
    order = np.lexsort((cols[key], bucket))
    nb = data.config["num_buckets"]
    bounds = np.searchsorted(bucket[order], np.arange(nb + 1))
    return {"num_buckets": nb, "columns": names, "buckets": {
        b: {n: cols[n][order[bounds[b]:bounds[b + 1]]] for n in names}
        for b in range(nb) if bounds[b + 1] > bounds[b]
    }}


def load(version_dir: Path, names) -> dict:
    """The bucket files a build wrote: {bucket: columns}."""
    out = {}
    for f in sorted(Path(version_dir).glob("bucket-*.parquet")):
        t = pq.read_table(f, columns=list(names))
        out[int(f.stem.split("-")[1])] = {n: t.column(n).to_numpy() for n in names}
    return out


def _mix64(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint64(30))
    x = x * np.uint64(0xBF58476D1CE4E5B9)
    x = x ^ (x >> np.uint64(27))
    x = x * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _rows(buckets: dict, names) -> tuple:
    """(fingerprints, columns) of all rows, sorted by a 64-bit fingerprint
    of each row's values. Equal multisets of rows give equal sequences;
    the values themselves are compared after, so a fingerprint collision
    can only make two equal indexes read as different, never the reverse."""
    if not buckets:
        return np.zeros(0, dtype=np.uint64), [np.zeros(0) for _ in names]
    arrs = [np.concatenate([buckets[b][n] for b in sorted(buckets)]) for n in names]
    fp = np.zeros(len(arrs[0]), dtype=np.uint64)
    for a in arrs:
        bits = np.ascontiguousarray(a.astype(np.float64) if a.dtype.kind == "f" else a.astype(np.int64))
        fp = _mix64(fp * np.uint64(31) + bits.view(np.uint64))
    order = np.argsort(fp, kind="stable")
    return fp[order], [a[order] for a in arrs]


def compare(got, want) -> dict:
    """Counts of what the index got wrong; all 0 when it is right.
    `got` is a version directory, or the control's answer."""
    names, nb = want["columns"], want["num_buckets"]
    got = got["buckets"] if isinstance(got, dict) else load(got, names)
    if "rows" not in want:
        want["rows"] = _rows(want["buckets"], names)
    key = names[0]
    misbucketed = sum(int(np.sum(bucket_of(cols[key], nb) != b)) for b, cols in got.items())
    unsorted = sum(
        int(len(cols[key]) > 1 and not bool(np.all(cols[key][1:] >= cols[key][:-1])))
        for cols in got.values()
    )
    g, w = _rows(got, names), want["rows"]
    if len(g[1]) == len(w[1]) and np.array_equal(g[0], w[0]):
        differ = np.zeros(len(g[1][0]), dtype=bool)
        for a, b in zip(g[1], w[1]):
            differ |= a != b
        wrong = int(np.sum(differ))
    else:  # rows present on one side and not the other, with multiplicity
        ug, cg = np.unique(g[0], return_counts=True)
        uw, cw = np.unique(w[0], return_counts=True)
        u = np.union1d(ug, uw)
        count_g = np.zeros(len(u), dtype=np.int64)
        count_w = np.zeros(len(u), dtype=np.int64)
        count_g[np.searchsorted(u, ug)] = cg
        count_w[np.searchsorted(u, uw)] = cw
        wrong = int(np.sum(np.abs(count_g - count_w)))
    return {
        "index_rows_wrong": wrong,
        "rows_misbucketed": misbucketed,
        "buckets_unsorted": unsorted,
    }


def compare_sequence(answers) -> dict:
    """Each refresh has to leave a newer version than the one before."""
    versions = [int(Path(a).name.split("=")[1]) for a in answers]
    return {"refreshes_without_new_version": sum(
        1 for a, b in zip(versions, versions[1:]) if b <= a
    )}
