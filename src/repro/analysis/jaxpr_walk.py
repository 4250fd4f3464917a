"""Recursive jaxpr traversal with name-stack paths and taint propagation.

JAX stages nested computations (``pjit``, ``scan``, ``while``, ``cond``,
``custom_vjp``/``custom_jvp``, ``remat``, ``pallas_call``) as jaxpr-valued
equation params.  The walker here flattens that hierarchy:

* :func:`iter_eqns` yields every equation with its accumulated name-stack
  path (``b0_attn/ffn_down/cs_topk/select``), so rules can attribute a
  primitive to the layer that staged it.  Scan/while bodies are visited
  once — matching the "per traced superblock" accounting of the model's
  ``lax.scan`` layer stack.
* :func:`propagate_taint` runs a forward may-analysis over the same
  hierarchy: variables produced by *source* primitives are tainted, taint
  flows through every equation except designated *sinks*, and each
  (tainted-input, flagged-primitive) hit is reported.  Used by the
  dense-fallback rule: sources = ``top_k`` (the Select), sink =
  ``pallas_call`` (the sanctioned sparse consumer), flagged =
  ``dot_general``.

Sub-jaxpr inputs/outputs are aligned to the outer equation's operands by
suffix: every jaxpr-carrying primitive in JAX (pjit, scan, while, cond,
custom_* calls, remat) passes its operands as the *trailing* invars of the
inner jaxpr (leading positions are consts / carry prefixes that are also
operands), so suffix alignment is exact for pjit/scan/remat/custom and a
safe over-approximation for while/cond.
"""

from __future__ import annotations

from typing import (Callable, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np
from jax._src import core as jax_core
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Jaxpr = jax_core.Jaxpr
ClosedJaxpr = jax_core.ClosedJaxpr
Var = jax_core.Var


def _as_jaxpr(obj) -> Jaxpr:
    return obj.jaxpr if isinstance(obj, ClosedJaxpr) else obj


def sub_jaxprs(eqn) -> List[Jaxpr]:
    """All jaxpr-valued params of an equation (flattening tuples/lists)."""
    out: List[Jaxpr] = []
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for it in items:
            if isinstance(it, (Jaxpr, ClosedJaxpr)):
                out.append(_as_jaxpr(it))
    return out


def _join(prefix: str, name_stack: str) -> str:
    if prefix and name_stack:
        return f"{prefix}/{name_stack}"
    return prefix or name_stack


def eqn_path(eqn, prefix: str = "") -> str:
    """Accumulated name-stack path of one equation."""
    try:
        ns = str(eqn.source_info.name_stack)
    except AttributeError:           # pragma: no cover - very old jax
        ns = ""
    return _join(prefix, ns)


class EqnAt(NamedTuple):
    eqn: jax_core.JaxprEqn
    path: str
    depth: int


def iter_eqns(jaxpr, prefix: str = "", depth: int = 0,
              into_pallas: bool = True) -> Iterator[EqnAt]:
    """Yield every equation (recursively) with its name-stack path.

    ``into_pallas=False`` stops at ``pallas_call`` boundaries (the kernel
    body is a different machine model; rules that only make sense at the
    XLA level skip it)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        path = eqn_path(eqn, prefix)
        yield EqnAt(eqn, path, depth)
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, path, depth + 1, into_pallas)


class BlockView(NamedTuple):
    """What the static rules read off one ``pallas_call`` block mapping."""

    block_shape: Tuple[Optional[int], ...]   # None = squeezed axis
    array_shape: Tuple[int, ...]
    dtype: np.dtype
    in_smem: bool                            # scalar memory, not VMEM


def _block_dim(b) -> Optional[int]:
    return None if isinstance(b, pl.Squeezed) else int(b.block_size)


def block_view(bm) -> BlockView:
    """The one reader of Pallas' ``GridMapping.block_mappings`` entries.

    Block dims arrive as ``pl.Blocked``/``pl.Squeezed`` objects and the
    array as ``array_aval``; every rule reads them through here, so a
    change of those private fields is repaired in one place."""
    aval = bm.array_aval
    space = getattr(bm.transformed_block_aval, "memory_space", None)
    return BlockView(tuple(_block_dim(b) for b in bm.block_shape),
                     tuple(int(d) for d in aval.shape), np.dtype(aval.dtype),
                     space == pltpu.SMEM)


def kernel_name(eqn) -> str:
    """Name of the kernel body function a ``pallas_call`` eqn stages."""
    info = getattr(eqn.params.get("jaxpr"), "debug_info", None)
    src = getattr(info, "func_src_info", None)
    return src.split(" ")[0] if src else "pallas_call"


class TaintHit(NamedTuple):
    eqn: jax_core.JaxprEqn
    path: str


def propagate_taint(jaxpr,
                    source_prims: Sequence[str],
                    sink_prims: Sequence[str],
                    flag_prims: Sequence[str],
                    prefix: str = "",
                    in_taint: Sequence[bool] = ()) -> Tuple[List[bool],
                                                            List[TaintHit]]:
    """Forward taint propagation; returns (outvar taint, flagged hits).

    * outputs of any ``source_prims`` equation are tainted;
    * ``sink_prims`` consume taint (their outputs are clean, and their
      sub-jaxprs are not entered);
    * a ``flag_prims`` equation with any tainted input is reported;
    * every other equation propagates any-input-tainted -> all outputs.
    """
    jaxpr = _as_jaxpr(jaxpr)
    taint = {}
    invals = list(in_taint) + [False] * (len(jaxpr.invars) - len(in_taint))
    for v, t in zip(jaxpr.invars, invals):
        taint[v] = t
    for v in jaxpr.constvars:
        taint[v] = False
    hits: List[TaintHit] = []

    def var_taint(v) -> bool:
        return isinstance(v, Var) and taint.get(v, False)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        path = eqn_path(eqn, prefix)
        in_ts = [var_taint(v) for v in eqn.invars]
        any_in = any(in_ts)
        if name in flag_prims and any_in:
            hits.append(TaintHit(eqn, path))
        if name in source_prims:
            out_ts = [True] * len(eqn.outvars)
        elif name in sink_prims:
            out_ts = [False] * len(eqn.outvars)
        else:
            subs = sub_jaxprs(eqn)
            if subs:
                out_ts = [False] * len(eqn.outvars)
                for sub in subs:
                    # suffix-align outer operands to inner invars
                    n_in = len(_as_jaxpr(sub).invars)
                    inner_in = in_ts[len(in_ts) - n_in:] if n_in else []
                    if n_in > len(in_ts):
                        inner_in = [False] * (n_in - len(in_ts)) + in_ts
                    sub_out, sub_hits = propagate_taint(
                        sub, source_prims, sink_prims, flag_prims,
                        prefix=path, in_taint=inner_in)
                    hits.extend(sub_hits)
                    # suffix-align inner outvars to outer outvars
                    n_out = min(len(sub_out), len(eqn.outvars))
                    for i in range(n_out):
                        if sub_out[len(sub_out) - n_out + i]:
                            out_ts[len(eqn.outvars) - n_out + i] = True
            else:
                out_ts = [any_in] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out_ts):
            if isinstance(v, Var):
                taint[v] = taint.get(v, False) or t
    return [var_taint(v) for v in jaxpr.outvars], hits
