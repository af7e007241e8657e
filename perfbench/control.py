#!/usr/bin/env python3
"""The control of a cell's check: the reference put in the program's
place, computed one precision step below what the configuration states
(float32 for float64 columns, int32 for int64 columns and sums).

    python perfbench/control.py --workload <name> --seeds 11 12 13 --ops 600

For each seed it generates the cell's tables, draws the first ``--ops``
operations of the window's stream, takes the control's answer to each
(for index builds, the index the reference builds, two of them as a run
checks), and compares them with the reference exactly, as a run's check
does. It prints one JSON line per seed with each number compared; the
control has to come out not correct. It needs no chip: the program is
not involved.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_counts(cell, roots: dict, seed: int, n_ops: int) -> dict:
    from perfbench import generator
    from perfbench.refs.data import Data

    domain = int(cell.config["key_domain"]["rows_per_scale_factor"] * cell.config["scale_factor"])
    stream = generator.stream(cell.traffic, cell.ops, domain, seed, "window")
    ops = [next(stream) for _ in range(n_ops)]
    builds = [i for i, (name, _) in enumerate(ops) if cell.ops[name].KIND == "build"]
    keep = set(i for i, (name, _) in enumerate(ops) if cell.ops[name].KIND == "query")
    keep |= set(builds[-2:])
    ref, ctl = Data(roots, cell.config), Data(roots, cell.config, control=True)
    counts: dict = {}
    memo: dict = {}
    for i in sorted(keep):
        name, params = ops[i]
        key = (name, json.dumps(params, sort_keys=True))
        if key not in memo:
            r = cell.refs[name]
            memo[key] = r.compare(r.answer(params, ctl), r.answer(params, ref))
        for k, v in memo[key].items():
            counts[k] = counts.get(k, 0) + v
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--ops", type=int, required=True, help="operations a run completes")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import harness

    cell = harness.load_cell(args.workload)
    work = harness.WORK / f"control-{cell.name}"
    tables = {t for m in cell.ops.values() for t in m.TABLES}
    for seed in args.seeds:
        shutil.rmtree(work, ignore_errors=True)
        t0 = time.perf_counter()
        roots, _ = harness.generate(cell.config, tables, work, seed)
        counts = control_counts(cell, roots, seed, args.ops)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "ops": args.ops,
            "correct": all(v == 0 for v in counts.values()),
            "checks": {k: {"value": v, "limit": 0} for k, v in counts.items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
