"""Logical-axis sharding: MaxText-style rules mapping logical tensor axes
to mesh axes.  Models annotate every parameter/activation with logical
axis names; a rules table (swappable — this is the hillclimbing surface)
maps them to PartitionSpecs.  Divisibility is checked per-dim: a rule that
does not divide the dimension is dropped rather than erroring, so one
rules table serves all 10 architectures.
"""

from __future__ import annotations

import math
from collections.abc import Mapping, Sequence
from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Mesh axis sets for supported rule values
AxisVal = tuple[str, ...] | str | None


def _as_tuple(v: AxisVal) -> tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


@dataclass(frozen=True)
class Rules:
    """logical axis name -> mesh axes (in sharding order)."""

    table: Mapping[str, AxisVal]
    name: str = "rules"

    def lookup(self, logical: str) -> tuple[str, ...]:
        return _as_tuple(self.table.get(logical))


# ---------------------------------------------------------------------------
# Baseline rule tables
# ---------------------------------------------------------------------------

# Single-pod baseline: DP over `data` + FSDP over `data` for weights,
# TP over `model` for heads / mlp / vocab / experts.
BASELINE = Rules(
    name="baseline",
    table={
        "batch": ("pod", "data"),
        "embed": ("data",),  # FSDP: shard d_model dim of weights
        "embed_act": (),  # activations keep d_model replicated
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "expert_mlp": ("model",),  # fallback TP dim inside experts
        "kv_seq": ("data",),  # long-context KV cache sequence dim
        "seq": (),
        "head_dim": (),
        "state": (),
        "layers": (),
        "conv": (),
        "frontend": (),
        # MoE dispatch internals
        "expert_cap": ("data",),
        "expert_group": ("data",),
        "flat_tokens": ("pod", "data"),
        # SSM / xLSTM inner dims
        "ssm_inner": ("model",),
        "ssm_heads": ("model",),
        "ssm_state": (),
        "mlstm_inner": ("model",),
        "mlstm_qk": ("model",),
        "mlstm_p": (),
        "slstm_p": (),
    },
)

# GridLocal: identical to baseline but the batch does NOT shard over `pod`
# (each pod is an independent "site"); parameters gain a leading `grid`
# logical axis sharded over `pod`.
GRIDLOCAL = Rules(
    name="gridlocal",
    table={**BASELINE.table, "batch": ("data",), "grid": ("pod",)},
)


def mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return math.prod(mesh.shape[a] for a in axes if a in mesh.shape)


def logical_to_pspec(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    rules: Rules,
    mesh: Mesh,
) -> P:
    """Build a PartitionSpec for a tensor with the given logical axes.

    Per-dim: drop mesh axes that are absent from the mesh, already used by
    an earlier dim, or whose product does not divide the dim size.
    """
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set[str] = set()
    parts = []
    for ax, dim in zip(logical_axes, shape):
        cand = [a for a in (rules.lookup(ax) if ax else ()) if a in mesh.shape and a not in used]
        # greedily keep the longest divisible prefix
        keep: list[str] = []
        prod = 1
        for a in cand:
            if dim % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
        used.update(keep)
        if not keep:
            parts.append(None)
        elif len(keep) == 1:
            parts.append(keep[0])
        else:
            parts.append(tuple(keep))
    # trim trailing Nones (cosmetic)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_pspecs(axes_tree, shape_tree, rules: Rules, mesh: Mesh):
    """Map logical_to_pspec over parallel pytrees of axes-tuples and shapes."""
    return jax.tree.map(
        lambda ax, shp: logical_to_pspec(ax, shp, rules, mesh),
        axes_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree, shape_tree, rules: Rules, mesh: Mesh):
    specs = tree_pspecs(axes_tree, shape_tree, rules, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P))


@dataclass
class ShapeAxes:
    """A (shape, dtype, logical_axes) leaf used to describe parameters and
    inputs without materialising them."""

    shape: tuple[int, ...]
    dtype: str
    axes: tuple[str | None, ...] = field(default=())

    def __post_init__(self):
        if not self.axes:
            object.__setattr__(self, "axes", (None,) * len(self.shape))
        assert len(self.axes) == len(self.shape), (self.shape, self.axes)

    def struct(self, rules: Rules | None = None, mesh: Mesh | None = None) -> jax.ShapeDtypeStruct:
        if rules is None or mesh is None:
            return jax.ShapeDtypeStruct(self.shape, self.dtype)
        sh = NamedSharding(mesh, logical_to_pspec(self.axes, self.shape, rules, mesh))
        return jax.ShapeDtypeStruct(self.shape, self.dtype, sharding=sh)


def is_shape_axes(x) -> bool:
    return isinstance(x, ShapeAxes)


def specs_to_structs(tree, rules: Rules | None = None, mesh: Mesh | None = None):
    return jax.tree.map(lambda s: s.struct(rules, mesh), tree, is_leaf=is_shape_axes)


def specs_to_shardings(tree, rules: Rules, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, logical_to_pspec(s.axes, s.shape, rules, mesh)),
        tree,
        is_leaf=is_shape_axes,
    )


# ---------------------------------------------------------------------------
# Activation-constraint context (used inside model code; identity when no
# mesh is active, e.g. in CPU smoke tests)
# ---------------------------------------------------------------------------

import contextlib

_ACTIVE: list[tuple[Mesh, Rules]] = []


@contextlib.contextmanager
def activate(mesh: Mesh, rules: Rules):
    """Make (mesh, rules) available to ``constrain`` during tracing.  Wrap
    the ``jit(...).lower(...)`` call (constraints bake in at trace time)."""
    _ACTIVE.append((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.pop()


def constrain(x: jax.Array, logical_axes: Sequence[str | None]) -> jax.Array:
    """with_sharding_constraint by logical axes; identity outside activate().

    Inside shard_map bodies (e.g. the GridLocal per-pod step) the context
    mesh marks the manual axes — constraints must be expressed on that
    abstract mesh with manual axes stripped from the spec."""
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    pspec = logical_to_pspec(logical_axes, x.shape, rules, mesh)
    am = jax.sharding.get_abstract_mesh()
    if am.shape:
        manual = {
            name
            for name, ty in zip(am.axis_names, am.axis_types)
            if "manual" in str(ty).lower()
        }
        if manual:
            def strip(entry):
                if entry is None:
                    return None
                if isinstance(entry, str):
                    return None if entry in manual else entry
                kept = tuple(a for a in entry if a not in manual)
                return kept if kept else None
            pspec = P(*[strip(e) for e in pspec])
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, pspec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))
