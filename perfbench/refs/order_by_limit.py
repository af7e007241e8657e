"""Reference ORDER BY l_extendedprice DESC, l_orderkey LIMIT k.

The (price, key) sequence is defined; rows tied on both may come in
any order with any of their part keys, so the part key of each answer
row only has to belong to a row with that price and key."""

import numpy as np

COLUMNS = ("l_extendedprice", "l_orderkey", "l_partkey")


def answer(params, data):
    li = data.columns("lineitem", COLUMNS)
    price, key, part = (li[c] for c in COLUMNS)
    k = params["limit"]
    cut = np.partition(price, len(price) - k)[len(price) - k] if len(price) > k else price.min()
    cand = np.flatnonzero(price >= cut)
    top = cand[np.lexsort((key[cand], -price[cand]))[:k]]
    return {
        "l_extendedprice": price[top],
        "l_orderkey": key[top],
        "l_partkey": part[top],
        "rows": {(float(e), int(o), int(p)) for e, o, p in zip(price[cand], key[cand], part[cand])},
    }


def compare(got, want):
    ok = (
        all(c in got for c in COLUMNS)
        and np.array_equal(got["l_extendedprice"], want["l_extendedprice"])
        and np.array_equal(got["l_orderkey"], want["l_orderkey"])
        and all(
            (float(e), int(o), int(p)) in want["rows"]
            for e, o, p in zip(got["l_extendedprice"], got["l_orderkey"], got["l_partkey"])
        )
    )
    return {"wrong_answers": int(not ok)}
