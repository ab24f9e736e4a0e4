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
import re

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


# the benchmark's two cells: (arch, layers, rows, pool pages before the trash
# page).  Each stack is deeper than ``Model.STEP_UNROLL_MAX``, so its layer
# scan stays rolled and each step slices one layer's weights from the stack
WINDOW_CELLS = {
    "phi3v-16l": ("phi-3-vision-4.2b", 16, 96, 480),
    "danube3-4b": ("h2o-danube-3-4b", 24, 16, 80),
}


def _materialized(hlo: str):
    """``(name, result type, opcode, operands)`` of every instruction outside
    a fused computation: what runs as an op of its own on the chip."""

    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    ops, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*?)\)", line)
        if inst and comp not in fused:
            ops.append(inst.groups())
    return ops


@pytest.mark.parametrize("cell", sorted(WINDOW_CELLS))
def test_decode_window_streams_qkv_weights_in_place(topo, cell):
    """The scheduler's decode window (4 rounds of 7 steps) at a cell's widths
    slices each layer's q/k/v weights inside the dots' own fusions: no op
    of its own copies a layer's slice at every step.  At phi3v-16l's widths
    no stacked ``wq``/``wk``/``wv`` is relaid out at entry either."""

    from repro.configs import get_config
    from repro.models.model import Model
    from repro.runtime.kv_cache import PagedSpec
    from repro.runtime.scheduler import decode_window

    arch, layers, rows, pages = WINDOW_CELLS[cell]
    cfg = get_config(arch).replace(num_layers=layers, sliding_window=0)
    model = Model(cfg)
    assert model.repeats > Model.STEP_UNROLL_MAX
    one_chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    params = placed(model.abstract_params())
    spec = PagedSpec(num_pages=pages, page_size=PAGE, max_pages_per_seq=5)
    pcache = placed(jax.eval_shape(lambda: model.init_paged_cache(rows, spec)))
    logits = jax.eval_shape(
        lambda p: model.prefill(p, {"tokens": jnp.zeros((1, 14), jnp.int32)}, extra=0)[0],
        params,
    )
    logits = jax.ShapeDtypeStruct((rows, logits.shape[-1]), logits.dtype, sharding=one_chip)
    window = decode_window(model, 7, 4, 100)
    hlo = jax.jit(window, donate_argnums=(1, 2)).lower(params, logits, pcache).compile().as_text()
    ops = _materialized(hlo)

    d, hd = cfg.d_model, cfg.resolved_head_dim
    one_layer = {f"bf16[1,{d},{n * hd}]" for n in (cfg.num_heads, cfg.num_kv_heads)}
    assert [(name, op) for name, ty, op, _ in ops if ty.split("{")[0] in one_layer] == []

    # at danube3's widths two entry copies of the stacked wk/wv remain
    # (their device layout keeps the 3840 dim minor), once per window
    if cell == "phi3v-16l":
        qkv = set(re.findall(
            r"%([\w.\-]+) = \S+ parameter\(\d+\).*attn\\?'\]\[\\?'w[qkv]\\?'\]", hlo
        ))
        assert len(qkv) == 3
        assert [
            (name, op) for name, _, op, args in ops
            if op != "tuple" and qkv & set(re.findall(r"%([\w.\-]+)", args))
        ] == []
