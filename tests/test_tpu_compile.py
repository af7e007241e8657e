"""Compile the main path's device programs for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with libtpu compiles for
a topology that is only described (on-chip-measurement guide, section 2).
A kernel that passes every interpret-mode test can still be refused
here: a block shape not aligned to the (8, 128) tiling, a 64-bit
element type inside a Mosaic kernel, an index map that returns int64
under x64. Each test compiles one program at the shapes and dtypes the
SF1 main path gives it; a refusal raises, so the test fails.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load libtpu, and
pytest-xdist workers all import this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read
    # back without a chip: keep these compiles out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_run_bounds_compiles_for_every_bucket_row(one_chip):
    from hyperspace_tpu.ops.sortkeys import _RB_MAX_SECONDARY, _RB_TILE, _make_run_bounds_kernel
    from hyperspace_tpu.parallel.x64 import run_x64

    b, ls = 200, _RB_MAX_SECONDARY
    run = _make_run_bounds_kernel(_RB_TILE, ls, False)
    compiled = run_x64(
        lambda: run.lower(
            _shape(one_chip, (b, 8192), jnp.int32), _shape(one_chip, (b, ls), jnp.int32)
        ).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_join_agg_dense_reduce_compiles_at_sf1_shape(one_chip):
    """The fused join-aggregate with its dense float64 group reduction, as
    the SF1 report runs it: 200 buckets of 31,104 padded lineitem rows
    against 7,552 orders rows, the count and two sums of each side, 64
    padded groups."""
    from hyperspace_tpu.ops.join_agg import _DENSE_MAX_SEGMENTS, _fused_join_agg_bounds
    from hyperspace_tpu.parallel.x64 import run_x64

    b, lp, ls, k = 200, 31104, 7552, 64
    assert k <= _DENSE_MAX_SEGMENTS
    channels = (("star",), ("s", 0), ("s", 1), ("p", 0), ("p", 1))
    i32 = [_shape(one_chip, s, jnp.int32) for s in ((b, lp), (b, ls), (b, lp), (b, lp))]
    compiled = run_x64(
        lambda: _fused_join_agg_bounds.lower(
            *i32,
            _shape(one_chip, (2, b, lp), jnp.float64),
            _shape(one_chip, (2, b, ls), jnp.float64),
            _shape(one_chip, (b, lp), jnp.int32),
            num_segments=k,
            channels=channels,
            reduce="dense",
        ).compile()
    )
    assert "scatter" not in compiled.as_text()
    # No [K, rows] mask or masked-weight buffer is held in HBM.
    assert compiled.memory_analysis().temp_size_in_bytes < k * b * lp


def test_join_agg_bucket_reduce_compiles_at_sf1_q3_shape(one_chip):
    """TPC-H Q3's join-aggregate at SF1: about 0.7M orders grouped by
    order key, 200 buckets of 4,096 padded orders rows against 32,768
    lineitem rows, each bucket's groups reduced densely into 4,096 local
    segments. The [B, K] accumulators stay far below one [B, 2^20]
    float64 scatter accumulator of the global path (1.68 GB)."""
    from hyperspace_tpu.ops.join_agg import _DENSE_MAX_SEGMENTS, _fused_join_agg_bounds
    from hyperspace_tpu.parallel.x64 import run_x64

    b, lp, ls, k = 200, 4096, 32768, 4096
    assert k <= _DENSE_MAX_SEGMENTS
    channels = (("star",), ("s", 0), ("s", 1))
    i32 = [_shape(one_chip, s, jnp.int32) for s in ((b, lp), (b, ls), (b, lp), (b, lp))]
    compiled = run_x64(
        lambda: _fused_join_agg_bounds.lower(
            *i32,
            _shape(one_chip, (0, b, lp), jnp.float64),
            _shape(one_chip, (2, b, ls), jnp.float64),
            _shape(one_chip, (b, lp), jnp.int32),
            num_segments=k,
            channels=channels,
            reduce="bucket_dense",
        ).compile()
    )
    # No scatter instruction (source names in the metadata may say it).
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < b * (1 << 20) * 8


def test_aggregate_dense_reduce_compiles_at_sf1_q1_shape(one_chip):
    """TPC-H Q1's grouped aggregate at SF1: its four float64 lineitem
    columns over 8,388,608 padded rows, the six channels the program
    builds from them (four sums, the discount, the shared row count)
    into 8 segments by the dense reduction: no scatter, and temporaries
    under twice the six channels, where a [K, rows] float64 buffer per
    channel would take 3.2 GB."""
    import pyarrow as pa

    from hyperspace_tpu import AggSpec, col, lit
    from hyperspace_tpu.execution.table import ColumnTable
    from hyperspace_tpu.ops.aggregate import _channel_reduce, _plan_channels
    from hyperspace_tpu.parallel.x64 import run_x64

    names = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    table = ColumnTable.from_arrow(pa.table({c: np.ones(4) for c in names}))
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    aggs = [
        AggSpec.of("sum", "l_quantity"), AggSpec.of("sum", "l_extendedprice"),
        AggSpec.of("sum", disc_price), AggSpec.of("sum", disc_price * (lit(1.0) + col("l_tax"))),
        AggSpec.of("mean", "l_quantity"), AggSpec.of("mean", "l_extendedprice"),
        AggSpec.of("mean", "l_discount"), AggSpec.of("count", None),
    ]
    arrays, masks, channels, _slots, _n_host = _plan_channels(table, aggs)
    assert (len(arrays), len(masks), len(channels)) == (4, 0, 6)
    n, k = 1 << 23, 8
    compiled = run_x64(
        lambda: _channel_reduce.lower(
            tuple(_shape(one_chip, (n,), jnp.float64) for _ in arrays),
            (),
            _shape(one_chip, (n,), jnp.int32),
            num_segments=k,
            channels=channels,
            reduce="dense",
        ).compile()
    )
    assert " scatter(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * len(channels) * n * 8


def test_topk_tile_kernel_compiles(one_chip):
    from hyperspace_tpu.ops.topk import _QBLOCK, _TILE, _make_tile_kernel

    run, _ = _make_tile_kernel(10, _TILE, False)
    compiled = run.lower(_shape(one_chip, (_QBLOCK, 1 << 20), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_build_batch_sort_compiles_at_sf1_bucket_size(one_chip):
    """The streaming build's per-batch device sort: is_pad, the int64
    key's validity/hi/lo lanes and the row iota, `_SORT_BATCH` buckets
    of an SF1 lineitem bucket's padded length (~30k rows at 200)."""
    from hyperspace_tpu.ops.sortkeys import _SORT_BATCH, _make_batch_sort

    shape = (_SORT_BATCH, 1 << 15)
    dtypes = (np.int32, np.int32, np.int32, np.uint32, np.int32)
    fn = _make_batch_sort(len(dtypes), len(dtypes) - 1)
    compiled = fn.lower(*[_shape(one_chip, shape, d) for d in dtypes]).compile()
    assert compiled.memory_analysis() is not None
