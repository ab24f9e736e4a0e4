"""Logical-axis sharding rules (MaxText-style) with a divisibility guard.

Model code annotates activations with *logical* axis names via ``shard(x,
"batch", "seq", "embed")``.  Outside a mesh context this is the identity, so
the same model code runs on CPU smoke tests and under the production mesh.

``logical_to_pspec`` maps logical names to mesh axes and **drops any mapping
whose dimension is not divisible by the mesh-axis product** (e.g.
starcoder2's 24 heads over a 16-way model axis), so every assigned
architecture lowers without uneven-sharding hazards.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

# Default logical->mesh rules for the production mesh.  Multi-pod meshes add
# the "pod" axis to the batch mapping.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("data",),
    "seq": (),            # sequence replicated by default (overridable)
    "embed": (),          # d_model replicated on activations
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "qkv_features": ("model",),   # flattened heads*head_dim on weights
    "mlp": ("model",),
    # expert parallelism rides the data axis (tokens all-to-all to their
    # experts), leaving "model" free to shard each expert's FFN hidden —
    # otherwise the capacity-dispatch [E, C, F] hidden is F-unsharded
    # (§Perf iteration C3: 16 GB/expert/device at 32k prefill)
    "expert": ("data",),
    "vocab": ("model",),
    "kv_seq": (),         # kv-cache sequence dim (sharded for long-context)
    # global page-pool dim of the paged KV cache: page ids are global, each
    # data shard owns a contiguous [P+1]/ndata block (trash page lives on
    # the last shard) and the host allocator steers new sequences to the
    # least-loaded shard's id range
    "pages": ("data",),
    "state": ("model",),  # ssm/xlstm inner feature dim
    "conv": (),
}

MULTIPOD_BATCH = ("pod", "data")


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[Dict[str, Tuple[str, ...]]] = None


_CTX = _Ctx()


def make_rules(mesh: Mesh, overrides: Optional[Dict[str, Tuple[str, ...]]] = None):
    rules = dict(DEFAULT_RULES)
    if "pod" in mesh.axis_names:
        rules["batch"] = MULTIPOD_BATCH
    if overrides:
        rules.update(overrides)
    return rules


@contextlib.contextmanager
def sharding_rules(mesh: Mesh, overrides: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Activate logical sharding for model-internal ``shard()`` calls."""

    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, make_rules(mesh, overrides)
    try:
        yield _CTX.rules
    finally:
        _CTX.mesh, _CTX.rules = prev


@contextlib.contextmanager
def no_sharding():
    """Suspend any active mesh context (``shard()`` becomes the identity).

    Disaggregated serving uses this to trace the prefill-device entry point
    single-device while the surrounding scheduler step runs under the
    decode mesh.
    """

    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = None, None
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def active_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``sharding_rules`` context, if any."""

    return _CTX.mesh


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def logical_to_pspec(
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
) -> P:
    """Build a PartitionSpec for ``shape`` from logical axis names.

    A logical axis maps to its mesh axes only if the dim is divisible by the
    mesh-axis product; otherwise that dim is left unsharded.  A mesh axis is
    used at most once per spec (first dim that claims it wins).
    """

    rules = rules or make_rules(mesh)
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    used: set = set()
    spec = []
    for dim, name in zip(shape, logical_axes):
        entry: MeshAxes = None
        if name is not None:
            axes = tuple(a for a in rules.get(name, ()) if a not in used)
            if axes and dim % _axis_size(mesh, axes) == 0:
                entry = axes if len(axes) > 1 else axes[0]
                used.update(axes)
        spec.append(entry)
    return P(*spec)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply a with_sharding_constraint if a mesh context is active."""

    if _CTX.mesh is None:
        return x
    spec = logical_to_pspec(x.shape, logical_axes, _CTX.mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def named_sharding(
    mesh: Mesh,
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(shape, logical_axes, mesh, rules))


def pspec_tree(shapes_tree, logical_tree, mesh: Mesh, rules=None):
    """Map ``logical_to_pspec`` over parallel pytrees of shapes and logical axes."""

    # a leaf is a flat tuple of dims (shapes tree) or axis names (logical
    # tree) — tree.map applies is_leaf to the first tree, so both spellings
    # must match or shape tuples get recursed into element-wise
    def _leaf(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (int, str, type(None))) for e in x
        )

    return jax.tree.map(
        lambda sh, ax: logical_to_pspec(sh, ax, mesh, rules),
        shapes_tree,
        logical_tree,
        is_leaf=_leaf,
    )
