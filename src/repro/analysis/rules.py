"""Jaxpr-level lint rules for the sparsity invariants.

Each rule is a pure function ``(closed_jaxpr, ctx...) -> List[Finding]``
over a traced entrypoint.  Layer attribution relies on the
``jax.named_scope`` annotations the model code stages (``b{i}_{kind}``
block scopes, ``ffn_up``/``ffn_gate``/``ffn_kwta``/``ffn_down`` and
``o_proj`` family scopes, ``cs_{path}`` execution-path scopes,
``select`` around every counted ``lax.top_k``).

Rules
-----
``select-count``     one Select (top_k) per sparse layer (paper Fig. 8a)
``dense-fallback``   the k-sparse support must reach the Pallas kernel,
                     never a ``dot_general`` (sparse-sparse stays sparse)
``dtype-promotion``  no float64 staging; no implicit widening inside
                     Pallas kernel bodies
``pallas-resource``  every ``pallas_call`` BlockSpec divides its array,
                     fits the grid, and the per-step blocks fit VMEM
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.kernels.block_validation import (check_block_shape,
                                            estimate_vmem_bytes, vmem_budget)

from .findings import Finding
from .jaxpr_walk import (block_view, iter_eqns, kernel_name,
                         propagate_taint, sub_jaxprs)

#: Primitives that implement a Select (top-k winner choice).  ``sort`` is
#: counted too: a sort-based k-WTA is a Select with a worse lowering.
SELECT_PRIMS = ("top_k", "approx_top_k", "sort")

#: Family markers staged by models/ffn.py and models/attention.py.
_FAMILY_OF_SEG = {"o_proj": "o_proj"}
_BLOCK_SEG = re.compile(r"^b\d+_")


def layer_key(path: str) -> str:
    """Collapse a name-stack path to its sparse-layer key.

    ``b0_attn/ffn_down/cs_topk/select`` -> ``b0_attn/ffn``;
    ``b1_attn/o_proj/...`` -> ``b1_attn/o_proj``; paths outside any
    family scope collapse to their block prefix (or "")."""
    blocks: List[str] = []
    for seg in path.split("/"):
        if _BLOCK_SEG.match(seg):
            blocks.append(seg)
            continue
        fam = _FAMILY_OF_SEG.get(seg)
        if fam is None and seg.startswith("ffn_"):
            fam = "ffn"
        if fam is not None:
            return "/".join(blocks + [fam])
    return "/".join(blocks)


# ---------------------------------------------------------------------------
# Rule: select-count
# ---------------------------------------------------------------------------

def rule_select_count(closed_jaxpr, expected: Optional[Dict[str, int]],
                      entry: str = "") -> List[Finding]:
    """One Select per sparse layer (paper Fig. 8a).

    ``expected`` maps layer keys (see :func:`layer_key`) to the number of
    Select primitives the configuration should stage — computed by
    ``repro.analysis.lint.expected_selects`` from the same dispatch rules
    the layers use.  ``None`` skips the rule (un-modeled config, e.g. MoE
    routers)."""
    if expected is None:
        return []
    actual: Dict[str, int] = {}
    where: Dict[str, str] = {}
    for eqn, path, _ in iter_eqns(closed_jaxpr, into_pallas=False):
        if eqn.primitive.name not in SELECT_PRIMS:
            continue
        key = layer_key(path)
        actual[key] = actual.get(key, 0) + 1
        where.setdefault(key, path)
    out: List[Finding] = []
    for key, exp in sorted(expected.items()):
        got = actual.get(key, 0)
        if got > exp:
            out.append(Finding(
                rule="select-count", entry=entry, scope=key,
                primitive="top_k",
                message=f"layer {key or '<entry>'} stages {got} Select "
                        f"primitives, expected {exp} (one Select per sparse "
                        f"layer; first at {where.get(key, key)!r})"))
        elif got < exp:
            out.append(Finding(
                rule="select-count", entry=entry, scope=key,
                primitive="top_k", severity="warning",
                message=f"layer {key or '<entry>'} stages {got} Select "
                        f"primitives, model expected {exp} — the Select "
                        f"model in analysis/lint.py is out of date"))
    for key, got in sorted(actual.items()):
        if key in expected or not key:
            continue
        fam = key.rsplit("/", 1)[-1]
        if fam in ("ffn", "o_proj"):
            out.append(Finding(
                rule="select-count", entry=entry, scope=key,
                primitive="top_k",
                message=f"unmodeled sparse layer {key} stages {got} Select "
                        f"primitives (first at {where[key]!r})"))
    return out


# ---------------------------------------------------------------------------
# Rule: dense-fallback
# ---------------------------------------------------------------------------

def rule_dense_fallback(closed_jaxpr, entry: str = "") -> List[Finding]:
    """The k-sparse support must be consumed by a Pallas kernel.

    Taint flows from every ``top_k`` output (the Select's ``(vals, idx)``
    support); ``pallas_call`` is the sanctioned sink.  A ``dot_general``
    (or conv) touching tainted data means the sparse-sparse contraction
    fell back to dense math — the paper's FLOP savings silently vanish.

    Only meaningful when the entrypoint is configured for the Pallas
    topk path (``use_pallas`` on and the regime dispatch picks ``topk``);
    the caller gates on that."""
    _, hits = propagate_taint(
        closed_jaxpr,
        source_prims=("top_k", "approx_top_k"),
        sink_prims=("pallas_call",),
        flag_prims=("dot_general", "conv_general_dilated"))
    out = []
    for eqn, path in hits:
        key = layer_key(path)
        out.append(Finding(
            rule="dense-fallback", entry=entry, scope=path,
            primitive=eqn.primitive.name,
            message=f"{eqn.primitive.name} consumes the k-sparse Select "
                    f"support in layer {key or '<entry>'} — expected the "
                    f"Pallas sparse-sparse kernel (use_pallas is on); the "
                    f"contraction fell back to dense math"))
    return out


# ---------------------------------------------------------------------------
# Rule: dtype-promotion
# ---------------------------------------------------------------------------

#: Widening through these is sanctioned (explicit casts; f32 accumulation).
_PROMOTION_EXEMPT = frozenset({
    "convert_element_type", "dot_general", "conv_general_dilated",
    "pallas_call", "iota", "reduce_sum", "reduce_max", "reduce_min",
    "cumsum", "integer_pow",
})

_WIDE_DTYPES = ("float64", "complex128")


def _float_width(dtype) -> Optional[int]:
    dt = np.dtype(dtype)
    return dt.itemsize if dt.kind == "f" else None


def _iter_kernel_jaxprs(closed_jaxpr):
    for eqn, path, _ in iter_eqns(closed_jaxpr, into_pallas=False):
        if eqn.primitive.name == "pallas_call":
            for sub in sub_jaxprs(eqn):
                yield sub, path


def rule_dtype_promotion(closed_jaxpr, entry: str = "") -> List[Finding]:
    """No float64 staging anywhere; no implicit widening in kernel bodies.

    f64 (usually a weak-typed Python scalar under ``enable_x64``) doubles
    kernel VMEM traffic and falls off the TPU fast path entirely.  Inside
    Pallas kernel bodies we additionally flag *implicit* float widening by
    elementwise ops — accumulating in f32 is fine when explicit
    (``convert_element_type`` / ``preferred_element_type``), invisible
    promotion is not."""
    out: List[Finding] = []
    for eqn, path, _ in iter_eqns(closed_jaxpr, into_pallas=True):
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in _WIDE_DTYPES:
                out.append(Finding(
                    rule="dtype-promotion", entry=entry, scope=path,
                    primitive=eqn.primitive.name,
                    message=f"{eqn.primitive.name} stages a {dt} value in "
                            f"{layer_key(path) or '<entry>'} — 64-bit types "
                            f"must never reach the sparse kernels"))
                break
    for kernel, kpath in _iter_kernel_jaxprs(closed_jaxpr):
        for eqn, path, _ in iter_eqns(kernel, prefix=kpath):
            if eqn.primitive.name in _PROMOTION_EXEMPT:
                continue
            in_w = [_float_width(v.aval.dtype) for v in eqn.invars
                    if getattr(v, "aval", None) is not None
                    and hasattr(v.aval, "dtype")]
            in_w = [w for w in in_w if w]
            out_w = [_float_width(v.aval.dtype) for v in eqn.outvars
                     if hasattr(getattr(v, "aval", None), "dtype")]
            out_w = [w for w in out_w if w]
            if in_w and out_w and max(out_w) > max(in_w):
                out.append(Finding(
                    rule="dtype-promotion", entry=entry, scope=path,
                    primitive=eqn.primitive.name, severity="warning",
                    message=f"implicit float widening ({8 * max(in_w)}->"
                            f"{8 * max(out_w)} bit) by {eqn.primitive.name} "
                            f"inside a Pallas kernel body"))
    return out


# ---------------------------------------------------------------------------
# Rule: pallas-resource
# ---------------------------------------------------------------------------

def _block_shape_ints(block_shape) -> tuple:
    return tuple(int(b) if isinstance(b, (int, np.integer)) else 1
                 for b in block_shape)


def rule_pallas_resource(closed_jaxpr, entry: str = "",
                         backend: str = "tpu") -> List[Finding]:
    """Static resource check of every staged ``pallas_call``.

    Re-validates what :mod:`repro.kernels.block_validation` enforced at
    call time — but on the *staged* program, so a kernel wrapper that
    skipped validation (or a grid computed from bad shapes) is still
    caught: every BlockSpec must divide its array shape, and the sum of
    per-grid-step blocks must fit the VMEM lint budget."""
    out: List[Finding] = []
    budget = vmem_budget(backend)
    for eqn, path, _ in iter_eqns(closed_jaxpr, into_pallas=False):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        if gm is None:                      # pragma: no cover - API drift
            out.append(Finding(
                rule="pallas-resource", entry=entry, scope=path,
                primitive="pallas_call", severity="warning",
                message="pallas_call without grid_mapping param; cannot "
                        "check BlockSpecs (jax API drift?)"))
            continue
        name = kernel_name(eqn)
        blocks = []
        for bv in map(block_view, gm.block_mappings):
            for problem in check_block_shape(bv.block_shape, bv.array_shape):
                out.append(Finding(
                    rule="pallas-resource", entry=entry, scope=path,
                    primitive=name,
                    message=f"kernel {name}: BlockSpec "
                            f"{_block_shape_ints(bv.block_shape)} vs array "
                            f"{bv.array_shape}: {problem}"))
            if not bv.in_smem:
                blocks.append((bv.block_shape, bv.dtype))
        vmem = estimate_vmem_bytes(blocks)
        if vmem > budget:
            out.append(Finding(
                rule="pallas-resource", entry=entry, scope=path,
                primitive=name,
                message=f"kernel {name}: per-grid-step blocks need "
                        f"{vmem} bytes of VMEM, over the {backend} lint "
                        f"budget of {budget} bytes"))
        grid = tuple(getattr(gm, "grid", ()) or ())
        for axis, extent in enumerate(grid):
            if isinstance(extent, (int, np.integer)) and extent < 1:
                out.append(Finding(
                    rule="pallas-resource", entry=entry, scope=path,
                    primitive=name,
                    message=f"kernel {name}: grid axis {axis} has extent "
                            f"{int(extent)}"))
    return out
