"""TPC-H ``lineitem`` and ``orders`` as parquet, made from a seed.

A copy of the repository's chunked TPC-H generators with two changes.
String columns are dictionary arrays over their small published domains
(flags, priorities, modes, clerks, comment templates), so the schema,
the widths and the distributions stay as they were while generation
runs several times faster. And the number of lines of each order is a
fixed function of its key, so the seed changes every value but not the
sizes: every seed gives the same row counts. The benchmark owns this copy: a change to
the program's generators cannot move the data a cell measures.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

SF1_ORDERS = 1_500_000
ROW_GROUP = 262_144
_EPOCH_1992 = 8035  # days from 1970-01-01 to 1992-01-01
_DATE_SPAN = 2525  # order dates span 1992-01-01 .. 1998-12-01 (TPC-H 4.2.3)

_RETURNFLAGS = pa.array(["A", "N", "R"])
_LINESTATUS = pa.array(["F", "O"])
_SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
_SHIPMODE = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
_ORDERPRIORITY = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_ORDERSTATUS = pa.array(["F", "O", "P"])
# l_comment: "<shipmode> carefully <shipinstruct>", code = mode * 4 + instruct.
_L_COMMENTS = pa.array([f"{m} carefully {i}" for m in _SHIPMODE for i in _SHIPINSTRUCT])
# o_comment: the priority template, or (about 1.2%) Q13's special request.
_O_COMMENTS = pa.array(
    [f"{p} instructions sleep quickly" for p in _ORDERPRIORITY]
    + ["the special packages wake furiously among the requests"]
)
_CLERKS = pa.array([f"Clerk#{i}" for i in range(1, 1001)])


def _dict(codes: np.ndarray, dictionary: pa.Array) -> pa.DictionaryArray:
    return pa.DictionaryArray.from_arrays(pa.array(codes.astype(np.int32)), dictionary)


def lines_per_order(orderkeys: np.ndarray) -> np.ndarray:
    """1-7 lines per order, uniform as in dbgen, but the same for every
    seed: a fixed hash of the key (splitmix64 finalizer) picks the count.
    So every seed gives the same row count and the same rows per index
    bucket, and the programs compiled for those shapes serve every seed."""
    x = orderkeys.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (x % np.uint64(7)).astype(np.int64) + 1


def _lineitem_chunk(o0: int, o1: int, sf: float, rng: np.random.Generator) -> pa.Table:
    keys = np.arange(o0, o1, dtype=np.int64)
    orderkey = np.repeat(keys, lines_per_order(keys))
    m = len(orderkey)
    shipdate = (_EPOCH_1992 + rng.integers(0, _DATE_SPAN, m) + rng.integers(1, 122, m)).astype(np.int32)
    quantity = rng.integers(1, 51, m).astype(np.float64)
    extendedprice = np.round(quantity * (900 + rng.random(m) * 100_000) / 100, 2)
    mode = rng.integers(0, 7, m)
    instruct = rng.integers(0, 4, m)
    return pa.table({
        "l_orderkey": orderkey,
        "l_partkey": rng.integers(0, int(200_000 * max(sf, 0.01)), m).astype(np.int64),
        "l_suppkey": rng.integers(0, int(10_000 * max(sf, 0.01)), m).astype(np.int64),
        "l_linenumber": np.ones(m, dtype=np.int32),
        "l_quantity": quantity,
        "l_extendedprice": extendedprice,
        "l_discount": np.round(rng.integers(0, 11, m) / 100.0, 2),
        "l_tax": np.round(rng.integers(0, 9, m) / 100.0, 2),
        "l_returnflag": _dict(rng.integers(0, 3, m), _RETURNFLAGS),
        "l_linestatus": _dict((shipdate > _EPOCH_1992 + 1260).astype(np.int32), _LINESTATUS),
        "l_shipdate": pa.array(shipdate, type=pa.date32()),
        "l_commitdate": pa.array(shipdate + rng.integers(-30, 31, m).astype(np.int32), type=pa.date32()),
        "l_receiptdate": pa.array(shipdate + rng.integers(1, 31, m).astype(np.int32), type=pa.date32()),
        "l_shipinstruct": _dict(rng.integers(0, 4, m), pa.array(_SHIPINSTRUCT)),
        "l_shipmode": _dict(rng.integers(0, 7, m), pa.array(_SHIPMODE)),
        "l_comment": _dict(mode * 4 + instruct, _L_COMMENTS),
    })


def _orders_chunk(k0: int, k1: int, n: int, rng: np.random.Generator) -> pa.Table:
    m = k1 - k0
    orderdate = (_EPOCH_1992 + rng.integers(0, _DATE_SPAN, m)).astype(np.int32)
    special = rng.random(m) < 0.012
    return pa.table({
        "o_orderkey": np.arange(k0, k1, dtype=np.int64),
        "o_custkey": rng.integers(0, n // 10 + 1, m).astype(np.int64),
        "o_orderstatus": _dict(rng.integers(0, 3, m), _ORDERSTATUS),
        "o_totalprice": np.round(rng.random(m) * 500_000, 2),
        "o_orderdate": pa.array(orderdate, type=pa.date32()),
        "o_orderpriority": _dict(rng.integers(0, 5, m), pa.array(_ORDERPRIORITY)),
        "o_clerk": _dict(rng.integers(0, 1000, m), _CLERKS),
        "o_shippriority": np.zeros(m, dtype=np.int32),
        "o_comment": _dict(np.where(special, 5, rng.integers(0, 5, m)), _O_COMMENTS),
    })


def _write(parts: list, root: Path, threads: int) -> int:
    """Make and write each (maker, args, path) part; returns total rows."""
    root.mkdir(parents=True, exist_ok=True)

    def one(part):
        make, args, path = part
        t = make(*args)
        pq.write_table(t, path, row_group_size=ROW_GROUP)
        return t.num_rows

    with ThreadPoolExecutor(max_workers=threads) as ex:
        return sum(ex.map(one, parts))


def gen_lineitem(root: Path, sf: float, seed: int, threads: int = 4) -> int:
    """lineitem (16 columns, about 6.0M rows a scale factor) in
    max(8, 8*sf) files, each a contiguous order range with its own
    derived seed. Returns the row count."""
    n_orders = int(SF1_ORDERS * sf)
    files = max(8, int(round(8 * sf)))
    per = (n_orders + files - 1) // files
    parts = [
        (_lineitem_chunk, (i * per, min((i + 1) * per, n_orders), sf,
                           np.random.default_rng([seed, 1, i])), root / f"part-{i}.parquet")
        for i in range(files) if i * per < n_orders
    ]
    return _write(parts, root, threads)


def gen_orders(root: Path, sf: float, seed: int, threads: int = 4) -> int:
    """orders (9 columns, 1.5M rows a scale factor) in max(4, 4*sf)
    files. Returns the row count."""
    n = int(SF1_ORDERS * sf)
    files = max(4, int(round(4 * sf)))
    per = (n + files - 1) // files
    parts = [
        (_orders_chunk, (i * per, min((i + 1) * per, n), n,
                         np.random.default_rng([seed, 2, i])), root / f"part-{i}.parquet")
        for i in range(files) if i * per < n
    ]
    return _write(parts, root, threads)


GENERATORS = {"lineitem": gen_lineitem, "orders": gen_orders}
