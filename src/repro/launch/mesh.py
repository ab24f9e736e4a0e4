"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before calling it; smoke tests see the default single device and use
``make_host_mesh``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes.

    ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` refuses; the logical-rule ``shard()`` calls
    need Auto axes so GSPMD propagates the annotated layouts.
    """

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Mesh over whatever devices exist (CPU smoke/test runs).

    ``model`` requests a model-parallel axis; it is shrunk to the largest
    divisor of the device count that is <= the request (e.g. asking for
    ``model=4`` on 6 devices yields a (3, 2) mesh, on 7 devices (7, 1)) so
    any device count factors into a valid (data, model) rectangle instead
    of crashing ``jax.make_mesh``.
    """

    n = len(jax.devices())
    m = max(1, min(model, n))
    while n % m:
        m -= 1
    return _auto_mesh((n // m, m), ("data", "model"))


def make_test_mesh(*, data: int, model: int = 1, devices: Optional[Sequence] = None):
    """Exact-shape mesh for forced-host-device tests; validates the count.

    Raises with an actionable message when the forced device count does not
    match ``data * model`` — the usual cause is a missing
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the test env.
    """

    devs = list(devices) if devices is not None else jax.devices()
    if data * model != len(devs):
        raise ValueError(
            f"make_test_mesh(data={data}, model={model}) needs "
            f"{data * model} devices but found {len(devs)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model} "
            f"before importing jax"
        )
    import numpy as np

    from jax.sharding import Mesh

    return Mesh(np.asarray(devs).reshape(data, model), ("data", "model"))


def split_device_groups(*, prefill: int = 1) -> Tuple[List, List]:
    """(prefill_devices, decode_devices) split for disaggregated serving.

    The *last* ``prefill`` devices are dedicated to long-prompt prefill so
    the decode group keeps the default device (uncommitted arrays land on
    ``jax.devices()[0]``; giving that device to prefill would silently put
    both roles back on one chip).  Degenerates gracefully: with a single
    device both groups are that device (prefill still pipelines through a
    separate dispatch, just without physical isolation).
    """

    devs = jax.devices()
    if len(devs) <= prefill:
        return list(devs), list(devs)
    return list(devs[-prefill:]), list(devs[:-prefill])
