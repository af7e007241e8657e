"""Row-order-free comparison of two answers given as columns."""

from __future__ import annotations

import numpy as np


def canonical(cols: dict, names) -> list:
    arrs = [np.asarray(cols[n]) for n in names]
    if not arrs or len(arrs[0]) == 0:
        return arrs
    order = np.lexsort(tuple(reversed(arrs)))
    return [a[order] for a in arrs]


def same_rows(got: dict, want: dict) -> bool:
    """The same multiset of rows over `want`'s columns, values equal
    exactly (float64 equality: the data holds exact decimals)."""
    names = list(want)
    if any(n not in got for n in names):
        return False
    g, w = canonical(got, names), canonical(want, names)
    return all(len(a) == len(b) and np.array_equal(a, b) for a, b in zip(g, w))


def wrong_answer(got, want) -> dict:
    return {"wrong_answers": int(not same_rows(got, want))}
