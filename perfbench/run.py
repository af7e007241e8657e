#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it finds.

    python perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Earlier
lines of standard output are one JSON object per phase; the last line
is the result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones),
``device`` and, last, ``checks``: each number compared with the plain
reference beside its limit. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearsal-sf", type=float, default=None,
        help="CPU rehearsal at this scale factor (< 0.1); no chip run passes it",
    )
    args = ap.parse_args(argv)
    if args.rehearsal_sf is not None and not 0 < args.rehearsal_sf < 0.1:
        ap.error("--rehearsal-sf must lie in (0, 0.1): the cells' sizes are for the chip only")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    # libtpu would log under /tmp/tpu_logs, a path both sides of a
    # comparison share.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    import hyperspace_tpu

    if ROOT not in Path(hyperspace_tpu.__file__).resolve().parents:
        raise SystemExit(f"perfbench: hyperspace_tpu comes from {hyperspace_tpu.__file__}, not this checkout")
    from perfbench import harness

    rehearse = args.rehearsal_sf is not None
    return harness.main(args, allow_cpu=rehearse, scale_factor=args.rehearsal_sf)


if __name__ == "__main__":
    sys.exit(main())
