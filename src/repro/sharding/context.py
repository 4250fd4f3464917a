"""Logical-axis sharding context.

Models annotate activations with *logical* axis names via :func:`constrain`
and parameters with logical-spec tuples; the launcher installs a
:class:`Rules` object mapping logical names to mesh axes for the current
(mesh, input-shape) combination.  Outside any rules context every helper is
a no-op, so the same model code runs on a laptop CPU and on a 512-chip mesh.

Divisibility guard: a logical axis only shards a dimension if the dimension
is divisible by the product of mesh-axis sizes; otherwise it silently falls
back to replication (e.g. 4 kv heads cannot shard over model=16; batch=1 in
``long_500k`` cannot shard over data).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


_STATE = threading.local()


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    table: Dict[str, MeshAxes]

    def axis_size(self, axes: MeshAxes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        return size

    def resolve(self, logical: Optional[str], dim: Optional[int]) -> MeshAxes:
        if logical is None:
            return None
        axes = self.table.get(logical)
        if axes is None:
            return None
        if dim is not None and dim % self.axis_size(axes):
            return None  # divisibility fallback -> replicate
        return axes

    def spec_for(self, logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> P:
        dims = list(shape) if shape is not None else [None] * len(logical_axes)
        used: set = set()
        parts = []
        for logical, dim in zip(logical_axes, dims):
            axes = self.resolve(logical, dim)
            # a mesh axis may appear at most once in a PartitionSpec
            if axes is not None:
                flat = (axes,) if isinstance(axes, str) else tuple(axes)
                if any(a in used for a in flat):
                    axes = None
                else:
                    used.update(flat)
            parts.append(axes)
        return P(*parts)

    def sharding_for(self, logical_axes: Sequence[Optional[str]],
                     shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))


def set_rules(rules: Optional[Rules]) -> None:
    _STATE.rules = rules


def get_rules() -> Optional[Rules]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = get_rules()
    set_rules(rules)
    try:
        yield rules
    finally:
        set_rules(prev)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axis shardings (no-op without
    rules)."""
    rules = get_rules()
    if rules is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} array")
    sh = rules.sharding_for(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, sh)


def is_spec(s) -> bool:
    """True for a logical-spec tuple: elements are None, axis names, or
    tuples of axis names (a logical axis may resolve to multiple mesh
    axes, e.g. batch -> ('pod', 'data'))."""
    def ok(a):
        return (a is None or isinstance(a, str)
                or (isinstance(a, tuple) and all(isinstance(x, str)
                                                 for x in a)))
    return isinstance(s, tuple) and all(ok(a) for a in s)


def param_sharding(specs_tree, params_tree, rules: Rules):
    """Resolve a logical-spec pytree against actual param shapes.

    ``params_tree`` may hold arrays or ShapeDtypeStructs.  A spec longer
    than the array rank (e.g. scalar placeholders for int leaves in
    optimizer state) resolves to full replication.
    """
    def resolve(spec, p):
        if len(spec) != len(p.shape):
            return NamedSharding(rules.mesh, P())
        return rules.sharding_for(spec, p.shape)

    return jax.tree.map(resolve, specs_tree, params_tree, is_leaf=is_spec)
