"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler is installed with JAX, so these compile for a ``v5e:2x2``
topology that is described, not attached: what Mosaic would refuse on the
chip (an unaligned block, too much VMEM, a kernel GSPMD cannot partition)
fails here at no chip time.  Nothing runs, so nothing here says anything
about results or speed.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, so a test worker that imports this file
must not touch it unless it runs these tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_sharded,
)

PAGE = 16
# (heads, kv heads, head dim, window, pages per sequence, rows on one chip)
WIDTHS = {
    "phi-3-vision": (32, 32, 96, 0, 8, 8),
    # a sequence that spans the 4096-token sliding window, plus a page
    "h2o-danube3": (32, 8, 120, 4096, 4096 // PAGE + 1, 8),
    # the benchmark's danube3 cell: 16 rows of 70 tokens, global attention
    "h2o-danube3-cell": (32, 8, 120, 0, 5, 16),
    # the benchmark's phi3v16 cell: 96 rows of 70 tokens over a 480-page
    # pool, too large to read in place, so the pages are streamed
    "phi-3-vision-cell": (32, 32, 96, 0, 5, 96),
}
# the pattern bench/programs.json reads the kernel's device time by
KERNEL_NAME = "paged_decode_attention"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


def _args(widths, batch, sharding_of):
    h, kv, d, _, maxp, _ = widths
    pool = 4 * -(-batch * maxp // 4)  # splits evenly over 4 chips
    shapes = [
        ((batch, h, d), jnp.bfloat16, "rows"),
        ((pool, PAGE, kv, d), jnp.bfloat16, "pool"),
        ((pool, PAGE, kv, d), jnp.bfloat16, "pool"),
        ((batch, maxp), jnp.int32, "rows"),
        ((batch,), jnp.int32, "rows"),
    ]
    return [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding_of(kind))
        for s, dt, kind in shapes
    ]


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_compiles_for_v5e(topo, arch):
    one_chip = SingleDeviceSharding(topo.devices[0])
    window, rows = WIDTHS[arch][3], WIDTHS[arch][5]
    compiled = paged_decode_attention.lower(
        *_args(WIDTHS[arch], rows, lambda kind: one_chip), window=window
    ).compile()
    calls = [l for l in compiled.as_text().splitlines() if "tpu_custom_call" in l]
    assert calls and all(KERNEL_NAME in l.split(" = ")[0] for l in calls)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_sharded_compiles_for_4_chips(topo, arch):
    mesh = Mesh(
        np.asarray(topo.devices).reshape(4, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    # 6 rows arrive replicated; the wrapper pads them to 8 empty-padded
    # rows and splits those over the 4 chips
    spec = {"rows": P(), "pool": P("data")}
    args = _args(WIDTHS[arch], 6, lambda kind: NamedSharding(mesh, spec[kind]))
    fn = jax.jit(
        lambda *a: paged_decode_attention_sharded(
            *a, mesh=mesh, window=WIDTHS[arch][3]
        )
    )
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text  # the page-sharded pool is gathered per chip
