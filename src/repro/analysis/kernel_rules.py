"""Kernel-body verifier: symbolic bounds, race and masking proofs for the
Pallas sparse-sparse kernels.

PR 6's jaxpr/HLO linter stops at the ``pallas_call`` boundary; the rules
here step *inside* it.  Each staged kernel body is re-interpreted over the
interval/affine domain of :mod:`repro.analysis.intervals`, with
``pl.program_id`` values bound to symbols ranging over the grid and loop
counters recovered by induction analysis (a ``fori_loop`` stages as a
static-length ``scan``; its counter carry is recognized as ``init +
iter·stride`` with ``iter ∈ [0, length)``).  Four rule families come out
of one abstract pass:

``oob-access``
    Every Ref load/store index interval must fit the Ref's block shape —
    including ``pl.ds`` slices whose start is a traced value.  Data-
    dependent gathers (the ``p_idx`` rows of ``topk_gather``) are bounded
    by *provenance*: the kernel registry declares the value range of each
    index-carrying operand (``p_idx`` from ``top_k`` over ``P``
    partitions ⇒ ``[0, P)``), and the verifier proves every derived
    access stays inside the block.  An index the analysis cannot bound is
    a finding, not a pass — these are proofs, not heuristics.

``grid-race``
    An output Ref whose BlockSpec index map ignores a grid axis is
    revisited across that axis's steps.  Writes to it must follow the
    init-then-accumulate discipline: one full-block store guarded by
    ``pl.when(program_id(axis) == 0)`` dominating every read-modify-write.
    A missing init (RMW of uninitialized VMEM on the first visit) or an
    unguarded plain overwrite (last-writer-wins across steps) is flagged.

``unmasked-pad``
    When an array dim is not divisible by its block, the trailing block
    is padded; loads from such a Ref carry a pad taint that only a
    ``select_n`` (``jnp.where``) with a pad-clean predicate launders.
    Pad-tainted data reaching an output Ref is flagged.

``scratch-overflow``
    ``scratch_shapes`` buffers are folded into the per-grid-step VMEM
    working set (on top of the BlockSpec buffers that ``pallas-resource``
    already accounts) and checked against the lint budget.

Soundness notes: value-range provenance is declared, not derived — the
registry entry documents *why* each range holds (see
``repro.kernels.registry``); Ref-mediated dataflow through scratch
buffers preserves read/pad taint via the Ref's accumulated store taint;
``while`` loops (traced-bound ``fori_loop``) widen carries to ``±inf``,
which can only add findings, never hide one.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax import tree_util

from repro.kernels.block_validation import (block_bytes, estimate_vmem_bytes,
                                            vmem_budget)

from .findings import Finding
from .intervals import TOP, AbsVal, Interval, Sym
from .jaxpr_walk import block_view, iter_eqns, kernel_name, sub_jaxprs

# ---------------------------------------------------------------------------
# Ref bookkeeping
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class RefInfo:
    """One kernel operand Ref: block geometry + declared value range."""

    idx: int                      # body invar position
    kind: str                     # "index" | "in" | "out" | "scratch"
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]  # == block_shape for scratch
    dtype: object
    value_range: Optional[Interval] = None   # declared element range
    padded_axes: Tuple[int, ...] = ()        # axes with a partial block
    # taint accumulated by stores, returned by subsequent loads (sound
    # Ref-mediated dataflow through scratch/output buffers)
    stored_reads: frozenset = frozenset()
    stored_pad: frozenset = frozenset()

    @property
    def label(self) -> str:
        shape = "x".join(str(d) for d in self.block_shape)
        return f"{self.kind}[{self.idx}] {np.dtype(self.dtype).name}[{shape}]"


@dataclasses.dataclass(frozen=True)
class Access:
    ref: RefInfo
    kind: str          # "read" | "write" | "accum"
    order: int
    guards: Tuple[tuple, ...]
    full_block: bool


def _is_init_guard(guards: Tuple[tuple, ...], revisited: Sequence[int]) -> bool:
    return any(g and g[0] == "pid_eq0" and g[1] in revisited for g in guards)


# ---------------------------------------------------------------------------
# Value-range provenance registry
# ---------------------------------------------------------------------------

#: kernel body function name -> fn(refs: List[RefInfo]) -> {operand: Interval}
_VALUE_RANGES: Dict[str, Callable[[List[RefInfo]], Dict[int, Interval]]] = {}


def register_value_ranges(kernel_name: str,
                          fn: Callable[[List[RefInfo]],
                                       Dict[int, Interval]]) -> None:
    """Declare the element ranges of a kernel's index-carrying operands.

    ``kernel_name`` is the staged kernel body's name, as
    :func:`~repro.analysis.jaxpr_walk.kernel_name` reads it off the
    ``pallas_call`` eqn.  ``fn`` receives the operand :class:`RefInfo`
    list and returns a mapping
    from operand position to the :class:`Interval` its *values* are
    guaranteed to lie in.  The declaration is the verifier's trust root:
    register it next to the wrapper that constructs those operands, with
    a comment saying why the range holds.
    """
    _VALUE_RANGES[kernel_name] = fn


def _apply_provenance(kernel_name: str, refs: List[RefInfo]) -> None:
    # The shipped kernels' declarations live in repro.kernels.registry;
    # registration is lazy (first verification) to avoid the circular
    # import between the registry and this module.
    try:
        from repro.kernels import registry
        registry.ensure_provenance()
    except ImportError:      # pragma: no cover - circular-import guard
        pass
    fn = _VALUE_RANGES.get(kernel_name)
    if fn is None:
        return
    for pos, rng in fn(refs).items():
        if 0 <= pos < len(refs):
            refs[pos].value_range = rng


# ---------------------------------------------------------------------------
# The abstract interpreter
# ---------------------------------------------------------------------------


class _Ctx:
    """Per-pallas_call verification context: findings + access log."""

    def __init__(self, kernel: str, entry: str, scope: str):
        self.kernel = kernel
        self.entry = entry
        self.scope = scope
        self.findings: List[Finding] = []
        self.accesses: List[Access] = []
        self.order = 0
        self.suppress = 0       # >0 during the symbolic scan pre-pass

    def tick(self) -> int:
        self.order += 1
        return self.order

    def find(self, rule: str, message: str, severity: str = "error") -> None:
        if self.suppress:
            return
        self.findings.append(Finding(
            rule=rule, entry=self.entry, scope=self.scope,
            primitive=self.kernel, severity=severity,
            message=f"kernel {self.kernel}: {message}"))

    def access(self, ref: RefInfo, kind: str, guards, full: bool) -> None:
        if self.suppress:
            return
        self.accesses.append(Access(ref, kind, self.tick(), tuple(guards),
                                    full))


def _const_absval(val) -> AbsVal:
    arr = np.asarray(val)
    if arr.size == 0:
        return AbsVal.top()
    if arr.dtype.kind in "biu":
        return AbsVal.interval(float(arr.min()), float(arr.max()))
    if arr.dtype.kind == "f" and arr.size == 1 and np.isfinite(arr).all():
        return AbsVal.const(float(arr))
    return AbsVal.top()


class _Interp:
    def __init__(self, ctx: _Ctx, pid_syms: List[Sym]):
        self.ctx = ctx
        self.pid_syms = pid_syms

    # -- environment --------------------------------------------------------

    def _lookup(self, env: dict, atom):
        if hasattr(atom, "val"):                      # Literal
            return _const_absval(atom.val)
        return env.get(atom, AbsVal.top())

    def _abs(self, env: dict, atom) -> AbsVal:
        v = self._lookup(env, atom)
        return v if isinstance(v, AbsVal) else AbsVal.top()

    # -- indexers -----------------------------------------------------------

    def _index_entries(self, eqn, env, n_lead: int):
        """Yield (axis, kind, parts) per indexed dim of a get/swap eqn.

        kind is "int" (parts = AbsVal) or "slice"
        (parts = (start AbsVal, size, stride))."""
        tree = eqn.params.get("tree")
        idx_atoms = list(eqn.invars[n_lead:])
        if tree is None:
            return []
        indexers = tree_util.tree_unflatten(tree, idx_atoms)
        out = []
        axis = 0
        for indexer in (indexers if isinstance(indexers, tuple)
                        else (indexers,)):
            indices = getattr(indexer, "indices", None)
            if indices is None:                     # bare int/slice indexer
                indices = (indexer,)
            for ind in indices:
                if hasattr(ind, "start") and hasattr(ind, "size"):
                    start = (AbsVal.const(ind.start)
                             if isinstance(ind.start, (int, np.integer))
                             else self._abs(env, ind.start))
                    size = (int(ind.size)
                            if isinstance(ind.size, (int, np.integer))
                            else None)
                    stride = getattr(ind, "stride", 1)
                    stride = (int(stride)
                              if isinstance(stride, (int, np.integer)) else 1)
                    out.append((axis, "slice", (start, size, stride)))
                elif isinstance(ind, (int, np.integer)):
                    out.append((axis, "int", AbsVal.const(int(ind))))
                else:
                    out.append((axis, "int", self._abs(env, ind)))
                axis += 1
        return out

    def _check_access(self, eqn, env, ref: RefInfo, n_lead: int,
                      what: str) -> bool:
        """oob-access proof of one get/swap/addupdate; returns full-block."""
        entries = self._index_entries(eqn, env, n_lead)
        dims = ref.block_shape
        if not entries:           # x_ref[...] with no indexer tree: full
            return True
        full = len(entries) == len(dims)
        for axis, kind, parts in entries:
            if axis >= len(dims):
                break
            dim = int(dims[axis])
            if kind == "slice":
                start, size, stride = parts
                siv = start.iv()
                if size is None:          # dynamic size: require full proof
                    lo, hi = siv.lo, float("inf")
                else:
                    lo = siv.lo
                    hi = siv.hi + (size - 1) * stride
                full = full and start.is_const and siv.lo == 0 \
                    and size == dim and stride == 1
                if lo < 0 or hi > dim - 1:
                    rng = Interval(lo, hi)
                    self.ctx.find(
                        "oob-access",
                        f"{what} {ref.label} axis {axis}: slice "
                        f"[start + 0..{(size or 0) - 1}] spans "
                        f"{rng.render()}, outside the block's "
                        f"[0, {dim - 1}] (start range {siv.render()})")
            else:
                iv = parts.iv()
                full = full and dim == 1 and parts.is_const and iv.lo == 0
                if iv.lo < 0 or iv.hi > dim - 1:
                    self.ctx.find(
                        "oob-access",
                        f"{what} {ref.label} axis {axis}: index range "
                        f"{iv.render()} outside the block's [0, {dim - 1}]")
        return full

    # -- primitive handlers -------------------------------------------------

    def run(self, jaxpr, env: dict, guards: Tuple[tuple, ...] = ()):
        """Interpret a (closed) jaxpr body; returns abstract outvars."""
        consts = getattr(jaxpr, "consts", None)
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        if consts is not None:
            for cv, c in zip(inner.constvars, consts):
                env[cv] = _const_absval(c)
        else:
            for cv in inner.constvars:
                env.setdefault(cv, AbsVal.top())
        for eqn in inner.eqns:
            self.eqn(eqn, env, guards)
        return [self._lookup(env, v) for v in inner.outvars]

    def _bind(self, jaxpr, vals) -> dict:
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        return dict(zip(inner.invars, vals))

    def eqn(self, eqn, env: dict, guards) -> None:
        name = eqn.primitive.name
        handler = getattr(self, f"_p_{name}", None)
        if handler is not None:
            outs = handler(eqn, env, guards)
        elif name in _IDENTITY_PRIMS:
            outs = [self._abs(env, eqn.invars[0])]
        elif name in _JOIN_PRIMS:
            vals = [self._abs(env, v) for v in eqn.invars]
            out = vals[0]
            for v in vals[1:]:
                out = out.join(v)
            outs = [out]
        elif sub_jaxprs(eqn):
            outs = self._generic_call(eqn, env, guards)
        else:
            vals = [self._lookup(env, v) for v in eqn.invars]
            avs = [v for v in vals if isinstance(v, AbsVal)]
            meta = avs[0].meta(*avs[1:]) if avs else {}
            outs = [AbsVal.top(**meta)] * len(eqn.outvars)
        if outs is None:
            outs = []
        for v, out in zip(eqn.outvars, outs):
            env[v] = out

    # arithmetic ----------------------------------------------------------

    def _p_add(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        return [a.add(b)]

    def _p_sub(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        return [a.sub(b)]

    def _p_mul(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        return [a.mul(b)]

    def _p_neg(self, eqn, env, guards):
        return [self._abs(env, eqn.invars[0]).neg()]

    def _p_max(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        ia, ib = a.iv(), b.iv()
        return [AbsVal.interval(max(ia.lo, ib.lo), max(ia.hi, ib.hi),
                                **a.meta(b))]

    def _p_min(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        ia, ib = a.iv(), b.iv()
        return [AbsVal.interval(min(ia.lo, ib.lo), min(ia.hi, ib.hi),
                                **a.meta(b))]

    def _p_div(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        dt = getattr(getattr(eqn.outvars[0], "aval", None), "dtype", None)
        if dt is not None and np.dtype(dt).kind in "iu" and b.is_const \
                and b.iv().lo > 0 and a.iv().lo >= 0:
            return [AbsVal(base=a.iv().floordiv(b.iv().lo), **a.meta(b))]
        return [AbsVal.top(**a.meta(b))]

    def _p_rem(self, eqn, env, guards):
        a, b = (self._abs(env, v) for v in eqn.invars)
        if b.is_const and b.iv().lo > 0:
            n = b.iv().lo
            lo = 0.0 if a.iv().lo >= 0 else -(n - 1)
            return [AbsVal.interval(lo, n - 1, **a.meta(b))]
        return [AbsVal.top(**a.meta(b))]

    def _p_iota(self, eqn, env, guards):
        shape = eqn.params.get("shape", ())
        dim = eqn.params.get("dimension", 0)
        hi = (int(shape[dim]) - 1) if shape else 0
        return [AbsVal.interval(0, max(hi, 0))]

    # comparisons ---------------------------------------------------------

    def _cmp(self, eqn, env, decide):
        a, b = (self._abs(env, v) for v in eqn.invars)
        d = a.sub(b).iv()
        tri = decide(d)            # True / False / None
        if tri is None:
            out = AbsVal.interval(0, 1, **a.meta(b))
        else:
            out = AbsVal.const(1 if tri else 0).with_meta(**a.meta(b))
        return out, a, b

    def _p_lt(self, eqn, env, guards):
        out, _, _ = self._cmp(eqn, env, lambda d: True if d.hi < 0 else
                              (False if d.lo >= 0 else None))
        return [out]

    def _p_le(self, eqn, env, guards):
        out, _, _ = self._cmp(eqn, env, lambda d: True if d.hi <= 0 else
                              (False if d.lo > 0 else None))
        return [out]

    def _p_gt(self, eqn, env, guards):
        out, _, _ = self._cmp(eqn, env, lambda d: True if d.lo > 0 else
                              (False if d.hi <= 0 else None))
        return [out]

    def _p_ge(self, eqn, env, guards):
        out, _, _ = self._cmp(eqn, env, lambda d: True if d.lo >= 0 else
                              (False if d.hi < 0 else None))
        return [out]

    def _p_eq(self, eqn, env, guards):
        out, a, b = self._cmp(eqn, env, lambda d: True if (d.is_point and
                              d.lo == 0) else (False if (d.lo > 0 or
                                                         d.hi < 0) else None))
        pred = _pid_eq0_pred(a, b) or _pid_eq0_pred(b, a)
        if pred is not None:
            out = dataclasses.replace(out, pred=pred)
        return [out]

    def _p_ne(self, eqn, env, guards):
        out, _, _ = self._cmp(eqn, env, lambda d: False if (d.is_point and
                              d.lo == 0) else (True if (d.lo > 0 or
                                                        d.hi < 0) else None))
        return [out]

    def _p_select_n(self, eqn, env, guards):
        pred = self._abs(env, eqn.invars[0])
        cases = [self._abs(env, v) for v in eqn.invars[1:]]
        piv = pred.iv()
        if piv.is_point and 0 <= int(piv.lo) < len(cases):
            out = cases[int(piv.lo)]
        else:
            out = cases[0]
            for c in cases[1:]:
                out = out.join(c)
        meta = out.meta(pred)
        # a where() with a pad-clean predicate is THE sanctioned mask:
        # it launders the pad taint of its data operands.
        meta["pad"] = meta["pad"] if pred.pad else pred.pad
        return [dataclasses.replace(out, pred=None, **meta)]

    def _p_convert_element_type(self, eqn, env, guards):
        return [self._lookup(env, eqn.invars[0])
                if isinstance(self._lookup(env, eqn.invars[0]), AbsVal)
                else AbsVal.top()]

    def _p_reduce_sum(self, eqn, env, guards):
        a = self._abs(env, eqn.invars[0])
        n_in = int(np.prod(eqn.invars[0].aval.shape, dtype=np.int64)) \
            if getattr(eqn.invars[0], "aval", None) is not None else 1
        n_out = int(np.prod(eqn.outvars[0].aval.shape, dtype=np.int64)) \
            if getattr(eqn.outvars[0], "aval", None) is not None else 1
        factor = max(n_in // max(n_out, 1), 1)
        return [AbsVal(base=a.iv() * Interval(0, factor), **a.meta())
                if a.iv().lo >= 0 else
                AbsVal(base=a.iv().scale(factor), **a.meta())]

    def _p_argmax(self, eqn, env, guards):
        return self._arg_reduce(eqn, env)

    def _p_argmin(self, eqn, env, guards):
        return self._arg_reduce(eqn, env)

    def _arg_reduce(self, eqn, env):
        a = self._abs(env, eqn.invars[0])
        axes = eqn.params.get("axes", ())
        shape = getattr(getattr(eqn.invars[0], "aval", None), "shape", ())
        hi = 0
        for ax in axes:
            if ax < len(shape):
                hi = max(hi, int(shape[ax]) - 1)
        return [AbsVal.interval(0, hi, **a.meta())]

    # refs ----------------------------------------------------------------

    def _p_program_id(self, eqn, env, guards):
        axis = eqn.params.get("axis", 0)
        if axis >= len(self.pid_syms):
            return [AbsVal.interval(0, float("inf"))]
        return [AbsVal.of_sym(self.pid_syms[axis])]

    def _p_num_programs(self, eqn, env, guards):
        axis = eqn.params.get("axis", 0)
        if axis < len(self.pid_syms):
            rng = self.pid_syms[axis].range
            if rng.hi != float("inf"):
                return [AbsVal.const(rng.hi + 1)]
        return [AbsVal.interval(1, float("inf"))]

    def _p_get(self, eqn, env, guards):
        ref = self._lookup(env, eqn.invars[0])
        if not isinstance(ref, RefInfo):
            return [AbsVal.top()]
        full = self._check_access(eqn, env, ref, 1, "load from")
        self.ctx.access(ref, "read", guards, full)
        return [self._load_val(ref)]

    def _load_val(self, ref: RefInfo) -> AbsVal:
        base = ref.value_range if ref.value_range is not None else TOP
        pad = frozenset([ref.idx]) if ref.padded_axes else frozenset()
        return AbsVal(base=base, reads=frozenset([ref.idx]) | ref.stored_reads,
                      pad=pad | ref.stored_pad)

    def _p_swap(self, eqn, env, guards):
        ref = self._lookup(env, eqn.invars[0])
        if not isinstance(ref, RefInfo):
            return [AbsVal.top()]
        val = self._abs(env, eqn.invars[1])
        full = self._check_access(eqn, env, ref, 2, "store to")
        self._store(ref, val, guards, full)
        return [self._load_val(ref)]      # swap returns the old contents

    def _p_addupdate(self, eqn, env, guards):
        ref = self._lookup(env, eqn.invars[0])
        if not isinstance(ref, RefInfo):
            return []
        val = self._abs(env, eqn.invars[1])
        self._check_access(eqn, env, ref, 2, "accumulate into")
        # addupdate IS a read-modify-write by construction
        val = dataclasses.replace(val, reads=val.reads | {ref.idx})
        self._store(ref, val, guards, full=False)
        return []

    def _store(self, ref: RefInfo, val: AbsVal, guards, full: bool) -> None:
        is_accum = ref.idx in val.reads
        if val.pad and ref.kind == "out" and not self.ctx.suppress:
            srcs = ", ".join(f"operand {i}" for i in sorted(val.pad))
            self.ctx.find(
                "unmasked-pad",
                f"store to {ref.label} consumes data loaded from a "
                f"partial trailing block ({srcs}) without passing through "
                f"a where()/mask — padded lanes reach the output")
        self.ctx.access(ref, "accum" if is_accum else "write", guards, full)
        if not self.ctx.suppress:
            ref.stored_reads = ref.stored_reads | val.reads
            ref.stored_pad = ref.stored_pad | val.pad

    # control flow --------------------------------------------------------

    def _p_cond(self, eqn, env, guards):
        pred = self._abs(env, eqn.invars[0])
        branches = eqn.params.get("branches", ())
        operands = [self._lookup(env, v) for v in eqn.invars[1:]]
        piv = pred.iv()
        chosen = None
        if piv.is_point:
            i = min(max(int(piv.lo), 0), len(branches) - 1)
            chosen = [(i, guards)]
        else:
            chosen = []
            for i in range(len(branches)):
                if len(branches) == 2 and pred.pred is not None:
                    g = pred.pred if i == 1 else ("not",) + pred.pred
                else:
                    g = ("branch", i)
                chosen.append((i, guards + (g,)))
        outs = None
        for i, g in chosen:
            sub = branches[i]
            sub_env = self._bind(sub, operands)
            res = self.run(sub, sub_env, g)
            if outs is None:
                outs = res
            else:
                outs = [a.join(b) if isinstance(a, AbsVal) and
                        isinstance(b, AbsVal) else a
                        for a, b in zip(outs, res)]
        return outs or []

    def _p_scan(self, eqn, env, guards):
        p = eqn.params
        body = p["jaxpr"]
        nc, ncar = p["num_consts"], p["num_carry"]
        length = int(p.get("length", 0) or 0)
        consts = [self._lookup(env, v) for v in eqn.invars[:nc]]
        inits = [self._abs(env, v) for v in eqn.invars[nc:nc + ncar]]
        xs = [self._abs(env, v) for v in eqn.invars[nc + ncar:]]

        # pass 1 (symbolic, no findings): carries as fresh symbols, to
        # recognize induction carries (out = carry + loop-invariant stride)
        syms = [Sym.fresh(f"carry{i}", TOP, "carry") for i in range(ncar)]
        self.ctx.suppress += 1
        try:
            outs1 = self.run(body, self._bind(
                body, consts + [AbsVal.of_sym(s) for s in syms] + xs), guards)
        finally:
            self.ctx.suppress -= 1
        carry_outs = [o if isinstance(o, AbsVal) else AbsVal.top()
                      for o in outs1[:ncar]]

        iter_sym = Sym.fresh("iter", Interval(0, max(length - 1, 0)), "iter")
        in_loop: List[AbsVal] = []
        sym_set = set(syms)
        for s, init, out in zip(syms, inits, carry_outs):
            tm = out.term_map()
            coeff = tm.pop(s, 0.0)
            if coeff == 1.0 and not (sym_set & set(tm)):
                stride = AbsVal(base=out.base, terms=tuple(tm.items()),
                                reads=out.reads, pad=out.pad)
                in_loop.append(init.add(
                    stride.mul(AbsVal.of_sym(iter_sym))))
            else:
                # non-affine carry: widen (out was computed from TOP syms)
                in_loop.append(AbsVal(base=init.iv().join(out.iv()),
                                      **init.meta(out)))

        # pass 2 (real): findings + access log with the proven carry ranges
        outs2 = self.run(body, self._bind(body, consts + in_loop + xs),
                         guards)
        finals = []
        for init, out in zip(inits, outs2[:ncar]):
            o = out if isinstance(out, AbsVal) else AbsVal.top()
            finals.append(init.join(o) if length else init)
        ys = [o if isinstance(o, AbsVal) else AbsVal.top()
              for o in outs2[ncar:]]
        return finals + ys

    def _p_while(self, eqn, env, guards):
        p = eqn.params
        cn, bn = p.get("cond_nconsts", 0), p.get("body_nconsts", 0)
        body = p["body_jaxpr"]
        consts = [self._lookup(env, v) for v in eqn.invars[cn:cn + bn]]
        inits = [self._abs(env, v) for v in eqn.invars[cn + bn:]]
        # widen every carry to TOP (keeping taint): sound, may over-flag —
        # a traced-bound loop the analysis can't bound is worth a look
        carries = [AbsVal.top(**v.meta()) for v in inits]
        outs = self.run(body, self._bind(body, consts + carries), guards)
        return [i.join(o) if isinstance(o, AbsVal) else AbsVal.top()
                for i, o in zip(inits, outs)]

    def _generic_call(self, eqn, env, guards):
        subs = sub_jaxprs(eqn)
        vals = [self._lookup(env, v) for v in eqn.invars]
        outs = None
        for sub in subs:
            n_in = len(sub.invars)
            inner = vals[len(vals) - n_in:] if n_in <= len(vals) else \
                [AbsVal.top()] * (n_in - len(vals)) + vals
            res = self.run(sub, dict(zip(sub.invars, inner)), guards)
            n_out = min(len(res), len(eqn.outvars))
            if outs is None:
                outs = [AbsVal.top()] * len(eqn.outvars)
            for i in range(n_out):
                r = res[len(res) - n_out + i]
                if isinstance(r, AbsVal):
                    j = len(eqn.outvars) - n_out + i
                    outs[j] = r if outs[j].base.is_top else outs[j].join(r)
        return outs or [AbsVal.top()] * len(eqn.outvars)


#: element-range-preserving prims (result values ⊆ input values)
_IDENTITY_PRIMS = frozenset({
    "copy", "reshape", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "transpose", "rev", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "stop_gradient", "abs_after", "dynamic_slice",
})

#: joins of all inputs
_JOIN_PRIMS = frozenset({"concatenate", "dynamic_update_slice", "pad",
                         "gather", "clamp"})


def _pid_eq0_pred(a: AbsVal, b: AbsVal):
    if (len(a.terms) == 1 and a.terms[0][1] == 1.0
            and a.terms[0][0].kind == "pid"
            and a.base.is_point and a.base.lo == 0
            and b.is_const and b.iv().lo == 0):
        return ("pid_eq0", a.terms[0][0].axis)
    return None


# ---------------------------------------------------------------------------
# Per-pallas_call verification
# ---------------------------------------------------------------------------


def _index_map_used_axes(index_map_jaxpr, n_axes: int) -> set:
    """Grid axes the block index map actually depends on (backward slice)."""
    jaxpr = getattr(index_map_jaxpr, "jaxpr", index_map_jaxpr)
    needed = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    for eqn in reversed(jaxpr.eqns):
        if any(v in needed for v in eqn.outvars):
            needed.update(v for v in eqn.invars if not hasattr(v, "val"))
    return {i for i, v in enumerate(jaxpr.invars[:n_axes]) if v in needed}


def _build_refs(body, gm) -> List[RefInfo]:
    n_idx = getattr(gm, "num_index_operands", 0)
    nin = gm.num_inputs
    nout = gm.num_outputs
    bms = list(gm.block_mappings)
    refs: List[RefInfo] = []
    for i, invar in enumerate(body.invars):
        aval = getattr(invar, "aval", None)
        shape = tuple(int(d) for d in getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", np.float32)
        if i < n_idx:
            kind, arr_shape, padded = "index", shape, ()
        elif i < n_idx + nin + nout:
            kind = "in" if i < n_idx + nin else "out"
            bv = block_view(bms[i - n_idx]) if i - n_idx < len(bms) \
                else None
            arr_shape = bv.array_shape if bv is not None else shape
            padded = tuple(
                ax for ax, (b, d) in enumerate(zip(bv.block_shape, arr_shape))
                if b and d % b) if bv is not None else ()
        else:
            kind, arr_shape, padded = "scratch", shape, ()
        refs.append(RefInfo(idx=i, kind=kind, block_shape=shape,
                            array_shape=arr_shape, dtype=dtype,
                            padded_axes=padded))
    return refs


def _race_findings(ctx: _Ctx, refs: List[RefInfo], gm) -> None:
    grid = tuple(getattr(gm, "grid", ()) or ())
    n_idx = getattr(gm, "num_index_operands", 0)
    bms = list(gm.block_mappings)
    for ref in refs:
        if ref.kind != "out":
            continue
        bm = bms[ref.idx - n_idx] if ref.idx - n_idx < len(bms) else None
        if bm is None:
            continue
        used = _index_map_used_axes(bm.index_map_jaxpr, len(grid))
        revisited = [ax for ax, extent in enumerate(grid)
                     if ax not in used
                     and (not isinstance(extent, (int, np.integer))
                          or int(extent) > 1)]
        if not revisited:
            continue
        accs = [a for a in ctx.accesses if a.ref is ref]
        accums = [a for a in accs if a.kind == "accum"]
        inits = [a for a in accs if a.kind == "write" and a.full_block
                 and _is_init_guard(a.guards, revisited)]
        plains = [a for a in accs if a.kind == "write"
                  and not _is_init_guard(a.guards, revisited)]
        reads = [a for a in accs if a.kind == "read"]
        axes = ",".join(str(a) for a in revisited)
        if accums and not inits:
            ctx.find(
                "grid-race",
                f"output {ref.label} is accumulated across grid steps "
                f"(axis {axes} revisited by the index map) with no "
                f"pl.when(program_id == 0) full-block init store — the "
                f"read-modify-write reads uninitialized VMEM on the first "
                f"visit")
        elif accums and inits and reads and \
                min(i.order for i in inits) > min(r.order for r in reads):
            ctx.find(
                "grid-race",
                f"output {ref.label}: the pl.when init store does not "
                f"dominate the first read-modify-write (init is staged "
                f"after the accumulating read)")
        if plains:
            ctx.find(
                "grid-race",
                f"output {ref.label} is overwritten from multiple grid "
                f"steps (axis {axes} revisited) by a store outside the "
                f"pl.when(program_id == 0) init — cross-step race, the "
                f"last visiting step wins")


def _scratch_findings(ctx: _Ctx, refs: List[RefInfo], gm,
                      backend: str) -> None:
    scratch = [r for r in refs if r.kind == "scratch"]
    if not scratch:
        return
    scratch_bytes = sum(block_bytes(r.block_shape, r.dtype) for r in scratch)
    blocks = [(bv.block_shape, bv.dtype)
              for bv in map(block_view, gm.block_mappings) if not bv.in_smem]
    total = estimate_vmem_bytes(blocks) + scratch_bytes
    budget = vmem_budget(backend)
    if total > budget:
        ctx.find(
            "scratch-overflow",
            f"scratch buffers add {scratch_bytes} bytes; the per-grid-step "
            f"working set is {total} bytes, over the {backend} lint budget "
            f"of {budget} bytes")


def verify_pallas_eqn(eqn, scope: str = "", entry: str = "",
                      backend: str = "tpu") -> List[Finding]:
    """Run the kernel-body rule families over one staged ``pallas_call``."""
    gm = eqn.params.get("grid_mapping")
    body = eqn.params.get("jaxpr")
    kernel = kernel_name(eqn)
    ctx = _Ctx(kernel, entry, scope)
    if gm is None or body is None:    # pragma: no cover - jax API drift
        ctx.findings.append(Finding(
            rule="oob-access", entry=entry, scope=scope, primitive=kernel,
            severity="warning",
            message=f"kernel {kernel}: pallas_call without grid_mapping/"
                    f"jaxpr params; cannot verify the body (jax API drift?)"))
        return ctx.findings
    body = getattr(body, "jaxpr", body)
    grid = tuple(getattr(gm, "grid", ()) or ())
    refs = _build_refs(body, gm)
    _apply_provenance(kernel, refs)
    pid_syms = [
        Sym.fresh(f"pid{ax}",
                  Interval(0, int(extent) - 1)
                  if isinstance(extent, (int, np.integer)) else TOP,
                  "pid", axis=ax)
        for ax, extent in enumerate(grid)]
    interp = _Interp(ctx, pid_syms)
    env = dict(zip(body.invars, refs))
    try:
        interp.run(body, env)
    except Exception as e:            # pragma: no cover - keep CI diagnosable
        ctx.findings.append(Finding(
            rule="oob-access", entry=entry, scope=scope, primitive=kernel,
            severity="warning",
            message=f"kernel {kernel}: body interpretation failed "
                    f"({type(e).__name__}: {e}); bounds not proven"))
        return ctx.findings
    _race_findings(ctx, refs, gm)
    _scratch_findings(ctx, refs, gm, backend)
    return ctx.findings


def rule_kernel_body(closed_jaxpr, entry: str = "",
                     backend: str = "tpu") -> List[Finding]:
    """Verify every ``pallas_call`` staged by a traced entrypoint.

    The kernel-body companion to ``pallas-resource``: where that rule
    checks the call's BlockSpecs from outside, this one proves the body's
    Ref accesses in-bounds, its cross-grid-step writes race-free, its
    padded loads masked, and its scratch within the VMEM budget."""
    out: List[Finding] = []
    for eqn, path, _ in iter_eqns(closed_jaxpr, into_pallas=False):
        if eqn.primitive.name != "pallas_call":
            continue
        out.extend(verify_pallas_eqn(eqn, scope=path, entry=entry,
                                     backend=backend))
    return out
