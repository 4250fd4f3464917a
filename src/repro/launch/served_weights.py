"""The weights a served program reads: cast to the compute dtype once.

A configuration may store its parameters in float32 and compute in
bfloat16; the model then reads each matmul weight through
``.astype(ct)``.  Inside a jitted step that cast is work like any other:
XLA hoists the cast of each stacked ``(n_units, ...)`` weight out of the
layer scan, so every decode step and every prefill chunk reads the whole
float32 tree, writes its bfloat16 copy and reads that again.

:func:`served_params` does that rounding once, on the device, and hands
back the tree the programs should be given.  Which leaves it casts is
read off the staged programs themselves (:func:`cast_only_inputs`): a
floating input is cast when every use of it, followed through layout
ops (slices, reshapes, gathers) and into the bodies of ``jit``, ``scan``
and ``remat``, is a ``convert_element_type`` to the compute dtype.  A
leaf read at its own precision anywhere (a router scored in float32, a
norm scale) is served as stored.  The served copy is the same rounding
the step did, so the step's results are unchanged to the last bit.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax._src import core as jax_core

#: Ops that move or select elements without arithmetic on them: a cast
#: after one of these equals the same op after the cast.  Each is
#: followed only where the leaf is its first operand (the one read).
_LAYOUT = frozenset({"reshape", "squeeze", "expand_dims", "broadcast_in_dim",
                     "transpose", "slice", "dynamic_slice", "gather", "rev",
                     "copy"})


# Like ``repro.analysis.jaxpr_walk``'s helpers; that package imports
# Pallas, which would add seconds to every serving process's set-up.
def _as_jaxpr(obj):
    return obj.jaxpr if isinstance(obj, jax_core.ClosedJaxpr) else obj


def _sub_jaxprs(eqn) -> List:
    out = []
    for val in eqn.params.values():
        for it in val if isinstance(val, (tuple, list)) else (val,):
            if isinstance(it, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                out.append(_as_jaxpr(it))
    return out


def _join(a: Optional[bool], b: Optional[bool]) -> Optional[bool]:
    """Uses combine: any other read wins (False), then a cast (True);
    None is no use at all."""
    if a is False or b is False:
        return False
    return a or b


class _Uses:
    """Where each variable of a jaxpr (and of the jaxprs inside it) is
    read, indexed once per jaxpr."""

    def __init__(self, dtype):
        self.dtype = np.dtype(dtype)
        self._index: Dict[int, Dict] = {}

    def _uses(self, jaxpr):
        idx = self._index.get(id(jaxpr))
        if idx is None:
            idx = {}
            for eqn in jaxpr.eqns:
                for i, v in enumerate(eqn.invars):
                    if isinstance(v, jax_core.Var):
                        idx.setdefault(v, []).append((eqn, i))
            self._index[id(jaxpr)] = idx
        return idx

    def reads(self, jaxpr, var, exits=None) -> Optional[bool]:
        """True when every use of ``var`` in ``jaxpr`` is a cast to the
        dtype, False when any use reads it otherwise, None when nothing
        uses it.  ``exits(j)`` answers for the value leaving as the
        jaxpr's output ``j``; with none, a value that leaves is read."""
        seen = None
        for j, o in enumerate(jaxpr.outvars):
            if o is var:
                seen = _join(seen, exits(j) if exits else False)
        by_eqn: Dict[int, Tuple] = {}
        for eqn, i in self._uses(jaxpr).get(var, ()):
            by_eqn.setdefault(id(eqn), (eqn, []))[1].append(i)
        for eqn, at in by_eqn.values():
            if seen is False:
                break
            seen = _join(seen, self._eqn_reads(jaxpr, eqn, at, exits))
        return seen

    def _eqn_reads(self, jaxpr, eqn, at: List[int], exits) -> Optional[bool]:
        name = eqn.primitive.name
        if name == "convert_element_type":
            return np.dtype(eqn.params["new_dtype"]) == self.dtype
        if name in _LAYOUT:
            return at == [0] and self.reads(jaxpr, eqn.outvars[0], exits)
        subs = _sub_jaxprs(eqn)
        # jit, scan, remat, custom_jvp/vjp: the body's inputs are the
        # operands, one to one (a scanned operand arrives as its slice);
        # what a call returns is followed on in this jaxpr, what a scan
        # returns (a carry, a stacked output) counts as read
        if (name == "pallas_call" or len(subs) != 1
                or len(subs[0].invars) != len(eqn.invars)):
            return False
        body = subs[0]
        out = None
        if name != "scan" and len(body.outvars) == len(eqn.outvars):
            out = lambda j: self.reads(jaxpr, eqn.outvars[j], exits)
        seen = None
        for i in at:
            seen = _join(seen, self.reads(body, body.invars[i], out))
        return seen


def cast_only_inputs(jaxpr, n: int, dtype) -> List[bool]:
    """For each of the first ``n`` inputs of ``jaxpr``: is it used, and
    only ever through a cast to ``dtype``?"""
    jaxpr = _as_jaxpr(jaxpr)
    uses = _Uses(dtype)
    return [uses.reads(jaxpr, v) is True for v in jaxpr.invars[:n]]


def served_params(params, programs: Sequence, dtype):
    """The tree to hand the served programs, and what the cast covers.

    ``programs`` are the staged jaxprs of every program that will be
    given the tree, each taking the tree's leaves as its first inputs.
    A floating leaf wider than ``dtype`` is cast (once, on the device)
    when every program reads it only through a cast to ``dtype``.
    Returns ``(served, cast_bytes, master_bytes)``: the bytes of the
    copies and of the float leaves they were cast from."""
    dtype = np.dtype(dtype)
    leaves, tree = jax.tree.flatten(params)
    cast = [np.issubdtype(leaf.dtype, np.floating)
            and leaf.dtype.itemsize > dtype.itemsize for leaf in leaves]
    for jaxpr in programs:
        if not any(cast):
            break
        cast = [c and r for c, r in
                zip(cast, cast_only_inputs(jaxpr, len(leaves), dtype))]
    picked = [leaf for leaf, c in zip(leaves, cast) if c]
    if not picked:
        return params, 0, 0
    copies = iter(jax.jit(lambda ws: [w.astype(dtype) for w in ws])(picked))
    served = [next(copies) if c else leaf for leaf, c in zip(leaves, cast)]
    cast_bytes = sum(w.size * dtype.itemsize for w in picked)
    master_bytes = sum(w.size * w.dtype.itemsize for w in picked)
    return jax.tree.unflatten(tree, served), cast_bytes, master_bytes
