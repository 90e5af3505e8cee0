"""The (worker, zero, model) training mesh over the local devices."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def host_training_mesh(n_workers: int, model: int = 1) -> Mesh:
    """(worker, zero, model) mesh over the *local* devices.

    The single code path for every mesh-consuming trainer feature
    (``zero_sharded``, ``device_parallel_local``; also the device-parallel
    tests under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    The worker axis matches ``n_workers`` when the device grid allows; a
    single-device host degrades to worker=1 (so the same code runs on one
    CPU device), and any other mismatch is an error — silently replicating
    workers on a multi-device grid would defeat the sharding it names.
    """
    devices = np.array(jax.devices())
    n = (len(devices) // model) * model
    if n < 1:
        raise ValueError(
            f"host_training_mesh needs at least model={model} devices, "
            f"have {len(devices)}"
        )
    rows = n // model
    if rows % n_workers == 0:
        worker = n_workers
    elif rows == 1:
        worker = 1  # single-device degenerate mesh
    else:
        raise ValueError(
            f"n_workers={n_workers} does not divide the host device grid "
            f"({len(devices)} devices / model={model} -> {rows} rows); pick "
            f"a worker count from the divisors of {rows}"
        )
    zero = rows // worker
    grid = devices[: worker * zero * model].reshape(worker, zero, model)
    return Mesh(grid, ("worker", "zero", "model"))

