"""The source tables as numpy columns, at the reference's precision or
at the control's (each dtype one step below the configuration's)."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

# The control's precision: the nearest type below each one the
# configuration states (float64 decimals, int64 keys and sums).
LOWER = {np.dtype(np.float64): np.float32, np.dtype(np.int64): np.int32}


def part_files(root: Path) -> list[Path]:
    """The generator's part files in order (part-2 before part-10)."""
    return sorted(Path(root).glob("part-*.parquet"), key=lambda p: int(p.stem.split("-")[1]))


class Data:
    """Columns of the generated tables, loaded on first use."""

    def __init__(self, roots: dict, config: dict, control: bool = False):
        self.roots = {k: Path(v) for k, v in roots.items()}
        self.config = config
        self.control = control
        self._cols: dict = {}
        self._sorted: dict = {}

    def column(self, table: str, name: str) -> np.ndarray:
        key = (table, name)
        if key not in self._cols:
            t = pa.concat_tables(
                pq.read_table(f, columns=[name]) for f in part_files(self.roots[table])
            )
            arr = t.column(name).to_numpy()
            if self.control:
                arr = arr.astype(LOWER.get(arr.dtype, arr.dtype))
            self._cols[key] = arr
        return self._cols[key]

    def columns(self, table: str, names) -> dict:
        return {n: self.column(table, n) for n in names}

    def order(self, table: str, key: str) -> np.ndarray | None:
        """Row order sorting `table` by `key` (None: already sorted)."""
        if (table, key) not in self._sorted:
            k = self.column(table, key)
            self._sorted[(table, key)] = (
                None if len(k) < 2 or bool(np.all(k[1:] >= k[:-1]))
                else np.argsort(k, kind="stable")
            )
        return self._sorted[(table, key)]

    def by_key(self, table: str, key: str, names) -> dict:
        """`names` of `table` with its rows sorted by `key`."""
        order = self.order(table, key)
        cols = self.columns(table, set(names) | {key})
        return {n: (c if order is None else c[order]) for n, c in cols.items()}
