"""Device→host transfer probe and the venue choice it drives.

Operators whose OUTPUT must land on host (a materialized join's match
pairs, a sort permutation) pick their execution venue by the measured
device→host link: below the configured floor, computing on host beats
shipping results off the device. The link speed is a property of the
deployment (PCIe generation, a shared or remote attachment), so it is
measured, once per process, with a 4 MB readback.
"""

from __future__ import annotations

import functools
import time


def pick_venue(
    requested: str,
    floor_mbps: float,
    prefer_device: bool,
    what: str,
    needs_native: bool = True,
) -> str:
    """Shared auto/device/host venue selection (join merge, build sort,
    aggregation reduce).

    `requested` other than auto forces the venue — forcing "host" without
    the native library (when the host path needs it) is an error, not a
    silent device fallback. `prefer_device` wins the auto case (e.g. a
    real multi-device mesh, where the distributed kernel is the point).
    `needs_native=False` marks host paths implemented in pure numpy.

    The HYPERSPACE_VENUE env var overrides every auto decision at once
    (explicit per-operator conf still wins) — the testing/ops escape
    hatch for exercising one venue across a whole run."""
    import os

    from hyperspace_tpu import native
    from hyperspace_tpu.exceptions import HyperspaceError

    forced_by_env = False
    if requested == "auto":
        env = os.environ.get("HYPERSPACE_VENUE", "")
        if env:
            if env not in ("device", "host"):
                raise HyperspaceError(
                    f"unknown HYPERSPACE_VENUE={env!r} (device|host)"
                )
            requested = env
            forced_by_env = True

    if requested == "host":
        if needs_native and not native.available():
            origin = "HYPERSPACE_VENUE" if forced_by_env else what
            raise HyperspaceError(
                f"{origin}=host requires the native library (g++ build failed "
                "or unavailable); use auto or device"
            )
        return "host"
    if requested == "device":
        return "device"
    if requested != "auto":
        raise HyperspaceError(f"unknown {what}={requested!r} (auto|device|host)")
    if prefer_device or (needs_native and not native.available()):
        return "device"
    return "host" if d2h_mb_per_s() < floor_mbps else "device"


@functools.lru_cache(maxsize=1)
def d2h_mb_per_s() -> float:
    """Measured device→host bandwidth (MB/s), probed once per process
    with a 4 MB readback."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.arange(1 << 20, dtype=jnp.uint32)  # 4 MB
    x.block_until_ready()
    t0 = time.perf_counter()
    np.asarray(jax.device_get(x))
    dt = time.perf_counter() - t0
    return 4.0 / max(dt, 1e-9)
