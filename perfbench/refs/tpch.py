"""What the TPC-H report references share: dates as day numbers, group
sums in the data's own type, and the tolerance comparison.

Group keys, counts and row membership compare exactly. A sum or a mean
compares within a relative ``TOL`` of the sum of its group's absolute
terms. Float64 summed in another order over at most 6M terms errs by
about log2(n) x 2^-53 of that sum in the worst case, and by far less in
practice; the chip's float64 is two float32 values, which errs by about
2^-48 a step. So 1e-9 holds the program to float64 with a margin of
three orders of magnitude, while the control (float32 columns, sums
accumulated in float32) errs by about 1e-7 or more and fails it.
"""

from __future__ import annotations

import datetime

import numpy as np

TOL = 1e-9
_EPOCH = datetime.date(1970, 1, 1)


def day(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def days(arr) -> np.ndarray:
    """A date column (datetime64 or day numbers) as int64 day numbers."""
    a = np.asarray(arr)
    if a.dtype.kind == "M":
        return a.astype("datetime64[D]").astype(np.int64)
    return a.astype(np.int64)


def strings(arr) -> np.ndarray:
    """A string column (decoded or dictionary) as a numpy array of str."""
    return np.asarray(arr).astype(str)


def group_sums(vals: np.ndarray, order: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """Per-group sums, accumulated in the values' own type (float64;
    float32 in the control): `order` sorts the rows by group and
    `starts` marks each group's first row in that order."""
    if not len(starts):
        return np.zeros(0, vals.dtype)
    return np.add.reduceat(vals[order], starts, dtype=vals.dtype)


def groups_of(keys: list) -> tuple:
    """(unique key tuples as columns, inverse [n], order, starts) of
    the rows' key columns."""
    n = len(keys[0])
    if n == 0:
        return [k[:0] for k in keys], np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.int64)
    codes = []
    uniq_cols = []
    for k in keys:
        u, inv = np.unique(k, return_inverse=True)
        codes.append(inv.reshape(-1))
        uniq_cols.append(u)
    combined = np.zeros(n, np.int64)
    for c, u in zip(codes, uniq_cols):
        combined = combined * len(u) + c
    uniq, inv = np.unique(combined, return_inverse=True)
    inv = inv.reshape(-1)
    order = np.argsort(inv, kind="stable")
    counts = np.bincount(inv, minlength=len(uniq))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
    first = order[starts]
    return [k[first] for k in keys], inv, order, starts


def compare_groups(got, want: dict, keys: tuple, exact: tuple, approx: tuple) -> dict:
    """One wrong answer unless `got` holds `want`'s groups: the key
    columns and `exact` columns equal, each `approx` column within
    ``want["tol"][name]`` of `want`'s, row order free."""
    names = (*keys, *exact, *approx)
    if not isinstance(got, dict) or any(n not in got for n in names):
        return {"wrong_answers": 1}
    n = len(want[keys[0]]) if keys else len(want[approx[0]])
    if any(len(got[c]) != n for c in names):
        return {"wrong_answers": 1}
    if n == 0:
        return {"wrong_answers": 0}

    def canon(cols):
        ks = [strings(cols[k]) if np.asarray(cols[k]).dtype.kind in "OUS" else np.asarray(cols[k])
              for k in keys]
        order = np.lexsort(tuple(reversed(ks))) if ks else np.arange(n)
        return ks, order

    gk, go = canon(got)
    wk, wo = canon(want)
    if not all(np.array_equal(a[go], b[wo]) for a, b in zip(gk, wk)):
        return {"wrong_answers": 1}
    for c in exact:
        if not np.array_equal(np.asarray(got[c])[go], np.asarray(want[c])[wo]):
            return {"wrong_answers": 1}
    for c in approx:
        g = np.asarray(got[c], np.float64)[go]
        w = np.asarray(want[c], np.float64)[wo]
        if not bool(np.all(np.abs(g - w) <= want["tol"][c][wo])):
            return {"wrong_answers": 1}
    return {"wrong_answers": 0}
