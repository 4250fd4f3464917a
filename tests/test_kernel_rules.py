"""Kernel-body verifier tests: the interval/affine domain, the four rule
families (oob-access, grid-race, unmasked-pad, scratch-overflow), the
kernel registry sweep, and the CLI ``--kernels`` path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl

from repro.analysis import lint_kernels, rule_kernel_body, self_test
from repro.analysis.__main__ import main as cli_main
from repro.analysis.intervals import AbsVal, Interval, Sym
from repro.analysis.kernel_rules import register_value_ranges
from repro.kernels import kernel_cases


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _findings(fn, *args, **kw):
    closed = jax.make_jaxpr(fn)(*args)
    return rule_kernel_body(closed, entry="test", **kw)


# ---------------------------------------------------------------------------
# The abstract domain
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    a, b = Interval(0, 7), Interval(-2, 3)
    assert (a + b) == Interval(-2, 10)
    assert (a - b) == Interval(-3, 9)
    assert (a * b) == Interval(-14, 21)
    assert a.join(b) == Interval(-2, 7)
    assert Interval(1, 9).floordiv(2) == Interval(0, 4)
    assert Interval.top().scale(0) == Interval(0, 0)


def test_absval_affine_cancellation():
    # (pid + 3) - pid must concretize to exactly [3, 3], not via ranges
    pid = Sym.fresh("pid", Interval(0, 99), "pid", axis=0)
    v = AbsVal.of_sym(pid).add(AbsVal.const(3)).sub(AbsVal.of_sym(pid))
    assert v.iv() == Interval(3, 3)
    assert v.is_const


def test_absval_scalar_mul_keeps_affine():
    it = Sym.fresh("iter", Interval(0, 9), "iter")
    v = AbsVal.of_sym(it).mul(AbsVal.const(4))
    assert v.iv() == Interval(0, 36)
    assert len(v.terms) == 1        # still affine, not widened


def test_absval_taint_union():
    a = AbsVal.interval(0, 1, reads=frozenset({1}))
    b = AbsVal.interval(2, 3, pad=frozenset({2}))
    c = a.add(b)
    assert c.reads == frozenset({1}) and c.pad == frozenset({2})


# ---------------------------------------------------------------------------
# oob-access
# ---------------------------------------------------------------------------

def _gather_call(kernel, b, k, p, g, n):
    def f(vals, pidx, packed):
        return pl.pallas_call(
            functools.partial(kernel, k_nnz=k),
            grid=(1, b),
            in_specs=[pl.BlockSpec((1, k), lambda ig, ib: (ib, 0)),
                      pl.BlockSpec((1, k), lambda ig, ib: (ib, 0)),
                      pl.BlockSpec((p, g, n), lambda ig, ib: (0, 0, 0))],
            out_specs=pl.BlockSpec((1, g * n), lambda ig, ib: (ib, 0)),
            out_shape=jax.ShapeDtypeStruct((b, g * n), jnp.float32),
        )(vals, pidx, packed)
    return f, (_sds((b, k), jnp.float32), _sds((b, k), jnp.int32),
               _sds((p, g, n), jnp.float32))


def _gather_kernel(off):
    def kern(vals_ref, pidx_ref, packed_ref, o_ref, *, k_nnz):
        vals, pidx = vals_ref[0], pidx_ref[0]
        bg, n = packed_ref.shape[1], packed_ref.shape[2]

        def body(j, acc):
            w = packed_ref[pl.ds(pidx[j] + off, 1), :, :][0]
            return acc + w * vals[j]

        acc = lax.fori_loop(0, k_nnz, body, jnp.zeros((bg, n), jnp.float32))
        o_ref[0] = acc.reshape(bg * n)
    return kern


def test_oob_provenance_gather_in_bounds_is_clean():
    kern = _gather_kernel(0)
    kern.__name__ = "_prov_ok_kernel"
    register_value_ranges(
        "_prov_ok_kernel",
        lambda refs: {1: Interval(0, refs[2].block_shape[0] - 1)})
    f, args = _gather_call(kern, 2, 8, 16, 4, 4)
    assert _findings(f, *args) == []


def test_oob_off_by_one_gather_names_kernel_and_ref():
    kern = _gather_kernel(1)
    kern.__name__ = "_prov_off1_kernel"
    register_value_ranges(
        "_prov_off1_kernel",
        lambda refs: {1: Interval(0, refs[2].block_shape[0] - 1)})
    f, args = _gather_call(kern, 2, 8, 16, 4, 4)
    fs = [x for x in _findings(f, *args) if x.rule == "oob-access"]
    assert fs, "off-by-one gather not caught"
    assert "_prov_off1_kernel" in fs[0].message
    assert "in[2]" in fs[0].message and "axis 0" in fs[0].message


def test_oob_unbounded_index_is_a_finding_not_a_pass():
    # No provenance declared: the traced gather index is unbounded, and
    # the verifier's contract is proof, not optimism.
    kern = _gather_kernel(0)
    kern.__name__ = "_prov_missing_kernel"
    f, args = _gather_call(kern, 2, 8, 16, 4, 4)
    fs = [x for x in _findings(f, *args) if x.rule == "oob-access"]
    assert fs and "in[2]" in fs[0].message


def test_oob_fori_loop_induction_bounds_are_exact():
    # x_ref row j for j in [0, 8): in bounds exactly; j+1 overflows.
    def ok(x_ref, o_ref):
        def body(j, acc):
            return acc + x_ref[pl.ds(j, 1), :][0]
        o_ref[...] = lax.fori_loop(0, 8, body, jnp.zeros((4,), jnp.float32))

    def bad(x_ref, o_ref):
        def body(j, acc):
            return acc + x_ref[pl.ds(j + 1, 1), :][0]
        o_ref[...] = lax.fori_loop(0, 8, body, jnp.zeros((4,), jnp.float32))

    def call(kernel):
        def f(x):
            return pl.pallas_call(
                kernel, grid=(1,),
                in_specs=[pl.BlockSpec((8, 4), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((4,), lambda i: (0,)),
                out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            )(x)
        return f

    assert _findings(call(ok), _sds((8, 4), jnp.float32)) == []
    fs = _findings(call(bad), _sds((8, 4), jnp.float32))
    assert any(x.rule == "oob-access" for x in fs)


# ---------------------------------------------------------------------------
# grid-race
# ---------------------------------------------------------------------------

def _accum_call(kernel, nk=2):
    def f(x, w):
        return pl.pallas_call(
            kernel, grid=(2, 1, 1, nk),
            in_specs=[
                pl.BlockSpec((1, 8, 8), lambda s, ib, ig, ik: (s, ib, ik)),
                pl.BlockSpec((1, 8, 8), lambda s, ib, ig, ik: (s, ik, ig)),
            ],
            out_specs=pl.BlockSpec((1, 8, 8),
                                   lambda s, ib, ig, ik: (s, ib, ig)),
            out_shape=jax.ShapeDtypeStruct((2, 8, 8), jnp.float32),
        )(x, w)
    return f, (_sds((2, 8, 16), jnp.float32), _sds((2, 16, 8), jnp.float32))


def test_grid_race_init_then_accumulate_is_clean():
    def kern(x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(3) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[0] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    f, args = _accum_call(kern)
    assert _findings(f, *args) == []


def test_grid_race_missing_init_is_flagged():
    def kern(x_ref, w_ref, o_ref):
        o_ref[0] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    f, args = _accum_call(kern)
    fs = [x for x in _findings(f, *args) if x.rule == "grid-race"]
    assert fs and "out[2]" in fs[0].message
    assert "uninitialized" in fs[0].message


def test_grid_race_unguarded_overwrite_is_flagged():
    def kern(x_ref, w_ref, o_ref):
        # plain overwrite on a k-revisited block: last writer wins
        o_ref[0] = jnp.dot(x_ref[0], w_ref[0],
                           preferred_element_type=jnp.float32)

    f, args = _accum_call(kern)
    fs = [x for x in _findings(f, *args) if x.rule == "grid-race"]
    assert fs and "race" in fs[0].message


def test_grid_race_single_visit_needs_no_init():
    # nk == 1: the k axis has extent 1, so the output is never revisited
    def kern(x_ref, w_ref, o_ref):
        o_ref[0] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    f, args = _accum_call(kern, nk=1)
    assert [x for x in _findings(f, *args) if x.rule == "grid-race"] == []


# ---------------------------------------------------------------------------
# unmasked-pad
# ---------------------------------------------------------------------------

def _pad_call(kernel, rows=6, block=4):
    def f(x):
        return pl.pallas_call(
            kernel, grid=(-(-rows // block),),
            in_specs=[pl.BlockSpec((block, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, 8), jnp.float32),
        )(x)
    return f, (_sds((rows, 8), jnp.float32),)


def test_unmasked_pad_flagged_on_partial_block():
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    f, args = _pad_call(kernel=kern)
    fs = [x for x in _findings(f, *args) if x.rule == "unmasked-pad"]
    assert fs and "operand 0" in fs[0].message


def test_unmasked_pad_where_mask_launders():
    def kern(x_ref, o_ref):
        i = pl.program_id(0)
        r = lax.broadcasted_iota(jnp.int32, (4, 8), 0) + i * 4
        o_ref[...] = jnp.where(r < 6, x_ref[...] * 2.0, 0.0)

    f, args = _pad_call(kernel=kern)
    assert [x for x in _findings(f, *args) if x.rule == "unmasked-pad"] == []


def test_unmasked_pad_divisible_blocks_are_clean():
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    f, args = _pad_call(kernel=kern, rows=8, block=4)
    assert _findings(f, *args) == []


# ---------------------------------------------------------------------------
# scratch-overflow
# ---------------------------------------------------------------------------

def _scratch_call(scratch_shape):
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, o_ref, s_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            kern, grid=(1,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
            scratch_shapes=[pltpu.VMEM(scratch_shape, jnp.float32)],
        )(x)
    return f, (_sds((8, 8), jnp.float32),)


def test_scratch_overflow_flagged_over_budget():
    f, args = _scratch_call((4096, 1024))       # 16 MiB > 8 MiB budget
    fs = [x for x in _findings(f, *args) if x.rule == "scratch-overflow"]
    assert fs and "budget" in fs[0].message


def test_scratch_within_budget_is_clean():
    f, args = _scratch_call((128, 128))         # 64 KiB
    assert _findings(f, *args) == []


# ---------------------------------------------------------------------------
# The registry sweep + self-test + CLI
# ---------------------------------------------------------------------------

def test_registry_covers_all_four_kernels():
    kinds = {c.kernel for c in kernel_cases()}
    assert kinds == {"topk_gather", "grouped_cs_matmul", "packed_matmul",
                     "kwta_hist", "kwta_exact"}


def test_lint_kernels_sweep_is_clean():
    report = lint_kernels()
    assert report.ok, report.render()
    # the sweep must actually have run over every registered case
    assert len(report.entries) == len(kernel_cases())


def test_self_test_catches_kernel_regressions():
    assert self_test() == []


def test_cli_kernels_exits_zero(capsys):
    rc = cli_main(["--kernels", "--fail-on-findings"])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_seeded_kernel_regressions_exit_one(capsys):
    for name, needle in (("oob-gather", "oob-access"),
                         ("missing-init", "grid-race")):
        rc = cli_main(["--seed-regression", name])
        assert rc == 1
        assert needle in capsys.readouterr().out


def test_cli_no_config_no_kernels_exits_two(capsys):
    assert cli_main([]) == 2
