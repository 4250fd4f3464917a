"""Sparsity-invariant linting of traced entrypoints.

Library API
-----------
* :func:`lint_fn` — trace any callable with :func:`jax.make_jaxpr` and
  run the jaxpr rule pack over it.
* :func:`lint_config` — lint the named architecture's real entrypoints
  (decode step, paged decode step, fused prefill, the kwta→packed-
  projection kernel pipeline, forward training loss) abstractly: params
  and caches are
  :func:`jax.eval_shape` pytrees, so even the full-scale configs lint on
  a CPU without allocating a single weight.  The decode step is
  additionally AOT-compiled and its HLO text checked (host transfers,
  unexpected collectives).
* :func:`expected_selects` — the Select-count model: mirrors the exact
  dispatch logic of :func:`repro.core.layers.apply_kwta` /
  :func:`repro.core.layers.packed_linear_apply` to predict how many
  ``top_k`` primitives each sparse layer should stage (paper Fig. 8a:
  at most one per layer).
* :func:`lint_kernels` — sweep the Pallas kernel registry
  (:mod:`repro.kernels.registry`) and run the kernel-body verifier
  (:mod:`repro.analysis.kernel_rules`) plus the resource rule over every
  shipped kernel at every declared shape configuration (the CLI
  ``--kernels`` path).
* :func:`seeded_regressions` — deliberately broken pipelines (a doubled
  Select; an f64 kernel input; an off-by-one ``pl.ds`` gather; a missing
  ``pl.when`` accumulation init) used by the CLI ``--self-test`` and the
  test suite to prove the linter catches what it claims to.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import SparsityConfig, choose_executor, choose_path
from repro.core.masks import pad_to_multiple

from .findings import Finding, Report
from .hlo_rules import rule_hlo_collectives, rule_hlo_host_transfer
from .rules import (rule_dense_fallback, rule_dtype_promotion,
                    rule_pallas_resource, rule_select_count)

ENTRIES = ("decode", "decode_paged", "prefill", "kernel", "train")


# ---------------------------------------------------------------------------
# The Select-count model
# ---------------------------------------------------------------------------

def family_path(sp: SparsityConfig, n_tokens: int, d_in: int,
                d_out: int) -> Optional[str]:
    """Execution path the packed projection consuming the k-WTA output
    will take, or None when the projection isn't CS-packed."""
    if not (sp.weight_sparse and d_in % sp.n == 0 and d_out % sp.n == 0):
        return None
    d_in_p = pad_to_multiple(d_in, sp.n)
    return choose_path(sp, n_tokens, d_in_p, x_is_sparse=sp.activation_sparse)


def family_selects(sp: SparsityConfig, n_tokens: int, d_in: int,
                   d_out: int) -> int:
    """Selects staged by one kwta→packed-projection pipeline.

    Mirrors ``apply_kwta`` + ``packed_linear_apply``: the k-WTA stages a
    ``top_k`` only for the exact global impl (``kwta_support``, whose
    ``(vals, idx)`` handoff it is); histogram, bisection and local k-WTA
    (the sort-free :func:`repro.core.kwta.kwta` per partition) stage none.
    The downstream projection re-derives the support (one more ``top_k``)
    only on the topk path when no handoff exists."""
    if not sp.activation_sparse:
        return 0
    k = sp.k_for(d_in)
    if k >= d_in:
        return 0
    has_support = (sp.kwta_impl not in ("hist", "bisect")
                   and sp.kwta_partitions <= 1)
    n_sel = 1 if has_support else 0
    if family_path(sp, n_tokens, d_in, d_out) == "topk" and not has_support:
        n_sel += 1
    return n_sel


def expected_selects(cfg, n_tokens: int) -> Optional[Dict[str, int]]:
    """Per-layer-key Select expectation for a model config, or None when
    the config is un-modeled (MoE routers run their own top-k)."""
    if cfg.is_moe:
        return None
    exp: Dict[str, int] = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind not in ("attn", "shared_attn"):
            continue
        if cfg.d_ff > 0:
            exp[f"b{i}_{kind}/ffn"] = family_selects(
                cfg.ffn_sparsity, n_tokens, cfg.d_ff, cfg.d_model)
        if cfg.proj_sparsity.activation_sparse:
            exp[f"b{i}_{kind}/o_proj"] = family_selects(
                cfg.proj_sparsity, n_tokens,
                cfg.padded_heads * cfg.head_dim, cfg.d_model)
    return exp


def _wants_dense_fallback_rule(cfg, n_tokens: int) -> bool:
    """The dense-fallback rule only means something when a sparse family
    is configured to hit the Pallas topk path: in the Hadamard/dense
    regimes a ``dot_general`` on the k-sparse activation IS the
    sanctioned algorithm."""
    if cfg.is_moe:
        # The MoE router's own top-k legitimately feeds dense expert
        # combines; taint can't tell it from the sparse-sparse support.
        return False
    fams = [(cfg.ffn_sparsity, cfg.d_ff, cfg.d_model),
            (cfg.proj_sparsity, cfg.padded_heads * cfg.head_dim,
             cfg.d_model)]
    for sp, d_in, d_out in fams:
        if not (sp.activation_sparse and d_in):
            continue
        if not choose_executor(sp).use_pallas:
            continue
        if family_path(sp, n_tokens, d_in, d_out) == "topk":
            return True
    return False


# ---------------------------------------------------------------------------
# lint_fn: the library core
# ---------------------------------------------------------------------------

def lint_fn(fn: Callable, *example_args,
            entry: str = "fn",
            expected: Optional[Dict[str, int]] = None,
            check_select: bool = True,
            check_dense_fallback: bool = False,
            check_dtype: bool = True,
            check_pallas: bool = True,
            check_kernel_body: bool = True,
            backend: str = "tpu",
            waivers: Sequence[str] = (),
            **example_kwargs) -> Report:
    """Trace ``fn`` on abstract arguments and lint the jaxpr.

    ``example_args`` may be concrete arrays or ``ShapeDtypeStruct``
    pytrees (e.g. from :func:`jax.eval_shape`) — tracing never executes
    the function.  Returns a :class:`Report`; ``report.ok`` is the
    one-line "zero findings" assertion."""
    closed = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(
        *example_args, **example_kwargs)
    report = Report(entries=[entry])
    if check_select:
        report.add(rule_select_count(closed, expected, entry), waivers)
    if check_dense_fallback:
        report.add(rule_dense_fallback(closed, entry), waivers)
    if check_dtype:
        report.add(rule_dtype_promotion(closed, entry), waivers)
    if check_pallas:
        report.add(rule_pallas_resource(closed, entry, backend), waivers)
    if check_kernel_body:
        from .kernel_rules import rule_kernel_body
        report.add(rule_kernel_body(closed, entry=entry, backend=backend),
                   waivers)
    return report


def lint_hlo(hlo_text: str, entry: str = "decode",
             allowed_collectives: Sequence[str] = (),
             waivers: Sequence[str] = ()) -> Report:
    """Run the HLO rule pack over compiled module text."""
    report = Report(entries=[f"{entry}:hlo"])
    report.add(rule_hlo_host_transfer(hlo_text, entry), waivers)
    report.add(rule_hlo_collectives(hlo_text, entry, allowed_collectives),
               waivers)
    return report


# ---------------------------------------------------------------------------
# lint_config: lint a named architecture's real entrypoints
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _decode_batch(cfg, slots: int):
    if cfg.frontend == "embed":
        return {"embeds": _sds((slots, 1, cfg.d_model), jnp.float32)}
    return {"tokens": _sds((slots, 1), jnp.int32)}


def _seq_batch(cfg, batch: int, seq: int, labels: bool):
    out = {}
    if cfg.frontend == "embed":
        out["embeds"] = _sds((batch, seq, cfg.d_model), jnp.float32)
    else:
        out["tokens"] = _sds((batch, seq), jnp.int32)
    if cfg.frontend == "vision_prefix":
        out["patch_embeds"] = _sds((batch, cfg.n_prefix, cfg.d_model),
                                   jnp.float32)
    if labels:
        out["labels"] = _sds((batch, seq), jnp.int32)
    return out


def _with_pallas_mode(cfg, mode: Optional[str]):
    if mode is None:
        return cfg
    return dataclasses.replace(
        cfg,
        ffn_sparsity=dataclasses.replace(cfg.ffn_sparsity, use_pallas=mode),
        proj_sparsity=dataclasses.replace(cfg.proj_sparsity,
                                          use_pallas=mode))


def lint_config(arch, entries: Sequence[str] = ENTRIES,
                use_pallas: Optional[str] = "force",
                slots: int = 4, seq: int = 8, max_seq: int = 64,
                reduced: bool = False,
                check_hlo: bool = True,
                backend: str = "tpu",
                waivers: Sequence[str] = ()) -> Report:
    """Lint the named (or given) model config's entrypoints abstractly.

    ``arch`` is a config name (``smollm_360m``) or a ``ModelConfig``.
    ``use_pallas`` overrides both sparsity families' backend flag
    (default ``"force"``: lint the Pallas kernel path even on CPU, which
    is exactly what the CI job wants); ``None`` keeps the config's own.
    ``check_hlo`` AOT-compiles the decode step and runs the HLO rules
    (single-process: the rules prove no collectives/host transfers leak
    into an unsharded decode)."""
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config(arch) if isinstance(arch, str) else arch
    if reduced:
        cfg = cfg.reduced()
    cfg = _with_pallas_mode(cfg, use_pallas)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_model(key, cfg)[0])
    report = Report()

    if "decode" in entries:
        cache = jax.eval_shape(lambda: T.init_cache(cfg, slots, max_seq)[0])
        batch = _decode_batch(cfg, slots)
        pos = _sds((slots,), jnp.int32)
        fn = lambda p, c, b, q: T.serve_step(p, c, b, q, cfg)
        exp = expected_selects(cfg, n_tokens=slots)
        report.extend(lint_fn(
            fn, params, cache, batch, pos, entry="decode", expected=exp,
            check_dense_fallback=_wants_dense_fallback_rule(cfg, slots),
            backend=backend, waivers=waivers))
        if check_hlo:
            hlo = jax.jit(fn).lower(params, cache, batch, pos)\
                .compile().as_text()
            report.extend(lint_hlo(hlo, entry="decode", waivers=waivers))

    if "decode_paged" in entries and all(
            k in ("attn", "shared_attn") for k in cfg.block_pattern):
        # Same decode step through the paged KV pools: the gather/scatter
        # indirection must not stage extra Selects, promote dtypes, or
        # (HLO) introduce host transfers — the page tables stay on device.
        from repro.runtime.kvcache import PagedKV
        geo = PagedKV.build(max_seq, slots, page_size=16)
        cache = jax.eval_shape(lambda: T.init_paged_cache(
            cfg, geo.n_pages, geo.page_size)[0])
        batch = _decode_batch(cfg, slots)
        pos = _sds((slots,), jnp.int32)
        pages = _sds((slots, geo.blocks_per_slot), jnp.int32)
        fn = lambda p, c, b, q, pg: T.serve_step(p, c, b, q, cfg, pages=pg)
        exp = expected_selects(cfg, n_tokens=slots)
        report.extend(lint_fn(
            fn, params, cache, batch, pos, pages, entry="decode_paged",
            expected=exp,
            check_dense_fallback=_wants_dense_fallback_rule(cfg, slots),
            backend=backend, waivers=waivers))
        if check_hlo:
            hlo = jax.jit(fn).lower(params, cache, batch, pos, pages)\
                .compile().as_text()
            report.extend(lint_hlo(hlo, entry="decode_paged",
                                   waivers=waivers))

    if "prefill" in entries and T.supports_fused_prefill(cfg):
        batch = _seq_batch(cfg, 1, seq, labels=False)
        fn = lambda p, b: T.prefill(p, b, cfg, max_seq)
        exp = expected_selects(cfg, n_tokens=seq)
        report.extend(lint_fn(
            fn, params, batch, entry="prefill", expected=exp,
            check_dense_fallback=_wants_dense_fallback_rule(cfg, seq),
            backend=backend, waivers=waivers))

    if "kernel" in entries and cfg.d_ff > 0:
        report.extend(lint_kernel_pipeline(
            cfg.ffn_sparsity, slots, cfg.d_ff, cfg.d_model,
            backend=backend, waivers=waivers))

    if "train" in entries:
        batch = _seq_batch(cfg, 2, seq, labels=True)
        fn = lambda p, b: T.loss_fn(p, b, cfg)[0]
        exp = expected_selects(cfg, n_tokens=2 * seq)
        report.extend(lint_fn(
            fn, params, batch, entry="train", expected=exp,
            check_dense_fallback=False,   # backward re-plays are not linted
            backend=backend, waivers=waivers))
    return report


def lint_kernel_pipeline(sp: SparsityConfig, n_tokens: int, d_in: int,
                         d_out: int, backend: str = "tpu",
                         waivers: Sequence[str] = ()) -> Report:
    """Lint the bare kwta→packed-projection pipeline (the
    ``cs_topk_matmul`` entrypoint) at the given shapes."""
    from repro.core.layers import (apply_kwta, packed_linear_apply,
                                   packed_linear_init)
    if not (sp.weight_sparse and d_in % sp.n == 0 and d_out % sp.n == 0):
        return Report(entries=["kernel:skipped"])
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: packed_linear_init(
        key, d_in, d_out, sp, bias=False)[0])
    x = _sds((n_tokens, d_in), jnp.float32)

    def fn(p, x):
        with jax.named_scope("ffn_kwta"):
            h, support = apply_kwta(x, sp, return_support=True)
        with jax.named_scope("ffn_down"):
            return packed_linear_apply(p, h, sp,
                                       x_is_sparse=sp.activation_sparse,
                                       support=support)

    expected = {"ffn": family_selects(sp, n_tokens, d_in, d_out)}
    on_topk = (sp.activation_sparse and choose_executor(sp).use_pallas
               and family_path(sp, n_tokens, d_in, d_out) == "topk")
    return lint_fn(fn, params, x, entry="kernel", expected=expected,
                   check_dense_fallback=on_topk, backend=backend,
                   waivers=waivers)


# ---------------------------------------------------------------------------
# lint_kernels: sweep the Pallas kernel registry
# ---------------------------------------------------------------------------

def lint_kernels(backend: str = "tpu",
                 waivers: Sequence[str] = ()) -> Report:
    """Verify every registered Pallas kernel at every declared shape.

    Stages each :func:`repro.kernels.registry.kernel_cases` entry
    abstractly and runs the kernel-body rule families (``oob-access``,
    ``grid-race``, ``unmasked-pad``, ``scratch-overflow``) plus the
    outer ``pallas-resource`` rule over it — the CLI ``--kernels`` /
    CI sweep."""
    from repro.kernels.registry import kernel_cases

    from .kernel_rules import rule_kernel_body

    report = Report()
    for case in kernel_cases():
        entry = f"kernels:{case.label}"
        closed = case.trace()
        report.entries.append(entry)
        report.add(rule_kernel_body(closed, entry=entry, backend=backend),
                   waivers)
        report.add(rule_pallas_resource(closed, entry, backend), waivers)
    return report


# ---------------------------------------------------------------------------
# Seeded regressions (CLI --self-test; tests/test_analysis.py)
# ---------------------------------------------------------------------------

def _regression_double_topk() -> Report:
    """A layer that ignores the k-WTA support handoff and re-derives it:
    two Selects where the paper's pipeline (Fig. 8a) stages one."""
    from repro.core.layers import (apply_kwta, packed_linear_apply,
                                   packed_linear_init)
    sp = SparsityConfig(n=4, k_frac=0.125, route_share=0, kwta_impl="topk")
    d_in, d_out, tokens = 128, 64, 2
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: packed_linear_init(
        key, d_in, d_out, sp, bias=False)[0])
    x = _sds((tokens, d_in), jnp.float32)

    def bad(p, x):
        with jax.named_scope("b0_attn"):
            with jax.named_scope("ffn_kwta"):
                h, support = apply_kwta(x, sp, return_support=True)
            with jax.named_scope("ffn_down"):
                # BUG under test: drop the handoff; the projection
                # re-runs lax.top_k on the already k-sparse activation.
                return packed_linear_apply(p, h, sp, x_is_sparse=True,
                                           support=None)

    expected = {"b0_attn/ffn": family_selects(sp, tokens, d_in, d_out)}
    return lint_fn(bad, params, x, entry="decode", expected=expected,
                   check_pallas=False)


def _regression_f64_kernel() -> Report:
    """An f64 constant leaking into the sparse contraction: every value
    it touches promotes to float64 (only stageable under x64)."""
    from repro.core.functional import cs_topk_from_support, topk_support_flat

    with jax.enable_x64(True):
        packed = _sds((16, 8, 4), jnp.float32)
        route = _sds((16, 8, 4), jnp.int32)
        x = _sds((2, 32), jnp.float32)

        def bad(x, packed, route):
            with jax.named_scope("b0_attn"):
                with jax.named_scope("ffn_down"):
                    with jax.named_scope("cs_topk"):
                        vals, sel = topk_support_flat(x, 4)
                        # BUG under test: a float64 scale drags the whole
                        # kernel input up to 64-bit.
                        vals = vals * jnp.asarray(1.0, jnp.float64)
                        return cs_topk_from_support(
                            vals, sel // 4, sel % 4, packed, route)

        return lint_fn(bad, x, packed, route, entry="kernel",
                       check_select=False, check_pallas=False)


def _regression_oob_gather() -> Report:
    """The off-by-one ``pl.ds`` gather: the ``fori_loop`` body fetches
    packed row ``p + 1`` — one past the declared ``[0, P)`` provenance
    range of ``p_idx``, so the last partition reads out of bounds."""
    import functools

    from jax import lax
    from jax.experimental import pallas as pl

    from .intervals import Interval
    from .kernel_rules import register_value_ranges

    b, k, p, g, n = 2, 8, 16, 4, 4

    def _oob_gather_kernel(vals_ref, pidx_ref, packed_ref, o_ref, *, k_nnz):
        vals, pidx = vals_ref[0], pidx_ref[0]
        bg, nn = packed_ref.shape[1], packed_ref.shape[2]

        def body(j, acc):
            # BUG under test: rows are fetched at p + 1, sailing one past
            # the end of the packed partition dim when p == P - 1.
            w = packed_ref[pl.ds(pidx[j] + 1, 1), :, :][0]
            return acc + w * vals[j]

        acc = lax.fori_loop(0, k_nnz, body, jnp.zeros((bg, nn), jnp.float32))
        o_ref[0] = acc.reshape(bg * nn)

    # same provenance the real topk_gather kernel declares: p_idx ∈ [0, P)
    register_value_ranges(
        "_oob_gather_kernel",
        lambda refs: {1: Interval(0, refs[2].block_shape[0] - 1)})

    def bad(vals, pidx, packed):
        return pl.pallas_call(
            functools.partial(_oob_gather_kernel, k_nnz=k),
            grid=(1, b),
            in_specs=[pl.BlockSpec((1, k), lambda ig, ib: (ib, 0)),
                      pl.BlockSpec((1, k), lambda ig, ib: (ib, 0)),
                      pl.BlockSpec((p, g, n), lambda ig, ib: (0, 0, 0))],
            out_specs=pl.BlockSpec((1, g * n), lambda ig, ib: (ib, 0)),
            out_shape=jax.ShapeDtypeStruct((b, g * n), jnp.float32),
        )(vals, pidx, packed)

    return lint_fn(bad, _sds((b, k), jnp.float32), _sds((b, k), jnp.int32),
                   _sds((p, g, n), jnp.float32), entry="kernel",
                   check_select=False)


def _regression_missing_init() -> Report:
    """A grouped accumulation kernel whose ``pl.when(k == 0)`` zero-store
    was dropped: the ``+=`` reads uninitialized VMEM on the first visit
    of every revisited output block."""
    from jax.experimental import pallas as pl

    def _missing_init_kernel(x_ref, w_ref, o_ref):
        # BUG under test: no @pl.when(pl.program_id(3) == 0) init before
        # the read-modify-write on the k-revisited output block.
        o_ref[0] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    def bad(xg, packed):
        return pl.pallas_call(
            _missing_init_kernel,
            grid=(2, 1, 1, 2),
            in_specs=[
                pl.BlockSpec((1, 8, 8), lambda s, ib, ig, ik: (s, ib, ik)),
                pl.BlockSpec((1, 8, 8), lambda s, ib, ig, ik: (s, ik, ig)),
            ],
            out_specs=pl.BlockSpec((1, 8, 8),
                                   lambda s, ib, ig, ik: (s, ib, ig)),
            out_shape=jax.ShapeDtypeStruct((2, 8, 8), jnp.float32),
        )(xg, packed)

    return lint_fn(bad, _sds((2, 8, 16), jnp.float32),
                   _sds((2, 16, 8), jnp.float32), entry="kernel",
                   check_select=False)


def seeded_regressions() -> Dict[str, Callable[[], Report]]:
    """Named deliberately-broken pipelines the linter must flag."""
    return {"double-topk": _regression_double_topk,
            "f64-kernel": _regression_f64_kernel,
            "oob-gather": _regression_oob_gather,
            "missing-init": _regression_missing_init}


def self_test() -> List[str]:
    """Run every seeded regression; return failure descriptions (empty
    when the linter caught all of them — the CI negative test)."""
    expect_rule = {"double-topk": "select-count",
                   "f64-kernel": "dtype-promotion",
                   "oob-gather": "oob-access",
                   "missing-init": "grid-race"}
    # kernel-body findings must name the kernel AND the offending Ref
    expect_text = {"oob-gather": ("_oob_gather_kernel", "in[2]"),
                   "missing-init": ("_missing_init_kernel", "out[2]")}
    failures = []
    for name, run in seeded_regressions().items():
        report = run()
        rule = expect_rule[name]
        hits = report.by_rule(rule)
        if not hits:
            failures.append(
                f"seeded regression {name!r} was NOT caught (expected a "
                f"{rule} finding; got: {report.render()})")
            continue
        for needle in expect_text.get(name, ()):
            if not any(needle in f.message for f in hits):
                failures.append(
                    f"seeded regression {name!r}: the {rule} finding does "
                    f"not name {needle!r} (got: {hits[0].message})")
    return failures
