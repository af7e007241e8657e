"""Drive one harness run in this process at a rehearsal size."""

from __future__ import annotations

import argparse
import contextlib
import io
import json

from perfbench import harness

REHEARSAL_SF = 0.01


def run_cell(tmp_path, workload: str, seed: int = 2**31 + 11, seconds: float = 2.0,
             trace: int = 0) -> dict:
    """The result line of one CPU rehearsal run of `workload`."""
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(args, allow_cpu=True, scale_factor=REHEARSAL_SF,
                          work=tmp_path / "work", cache_dir=tmp_path / "jax_cache")
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
