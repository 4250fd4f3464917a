"""Static-analysis tests (repro.analysis): zero findings on the clean
sparse-sparse paths, seeded regressions caught (doubled Select, f64 in
the kernel input), the Select-count model, taint propagation, the shared
Pallas resource rule, and CLI exit codes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (Finding, Report, expected_selects,
                            family_selects, layer_key, lint_config, lint_fn,
                            propagate_taint, rule_pallas_resource,
                            seeded_regressions, self_test)
from repro.analysis.__main__ import main as cli_main
from repro.configs import get_config
from repro.core.api import SparsityConfig


def _smollm_reduced():
    return get_config("smollm_360m").reduced()


# ---------------------------------------------------------------------------
# Zero findings on the current sparse-sparse paths
# ---------------------------------------------------------------------------

def test_decode_prefill_zero_findings():
    report = lint_config(_smollm_reduced(), entries=("decode", "prefill"),
                         check_hlo=False)
    assert "decode" in report.entries and "prefill" in report.entries
    assert report.ok, report.render()


def test_deepseek_prefill_chunk_zero_findings():
    """The served chunk program of a reduced deepseek-v2-lite — latent
    attention over the paged cache, the held experts and the leading
    dense layer — stages no finding."""
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    assert cfg.use_mla and cfg.is_moe and cfg.n_dense_layers
    report = lint_config(cfg, entries=("prefill",), check_hlo=False)
    assert report.entries == ["prefill"]
    assert report.ok, report.render()


def test_decode_hlo_zero_findings():
    """AOT-compile the reduced decode step; the compiled module must stage
    no host transfers and no collectives (single-process)."""
    report = lint_config(_smollm_reduced(), entries=("decode",),
                         check_hlo=True)
    assert "decode:hlo" in report.entries
    assert report.ok, report.render()


def test_kernel_and_train_zero_findings():
    report = lint_config(_smollm_reduced(), entries=("kernel", "train"),
                         check_hlo=False)
    assert report.ok, report.render()


def test_lint_fn_fixture_one_liner(lint_clean):
    """The conftest fixture asserts zero findings in one line."""
    sp = SparsityConfig(n=4, k_frac=0.125)
    from repro.models.ffn import ffn_apply, ffn_init
    params = jax.eval_shape(
        lambda: ffn_init(jax.random.PRNGKey(0), 64, 256, sp)[0])
    x = jax.ShapeDtypeStruct((2, 1, 64), jnp.float32)
    lint_clean(lambda p, x: ffn_apply(p, x, sp), params, x,
               expected={"ffn": 1})


# ---------------------------------------------------------------------------
# Seeded regressions: the linter must catch what it claims to
# ---------------------------------------------------------------------------

def test_double_topk_regression_caught():
    report = seeded_regressions()["double-topk"]()
    found = report.by_rule("select-count")
    assert found, report.render()
    f = found[0]
    assert f.scope == "b0_attn/ffn"          # names the layer
    assert f.primitive == "top_k"            # names the primitive
    assert "2 Select" in f.message and "expected 1" in f.message


def test_f64_regression_caught():
    report = seeded_regressions()["f64-kernel"]()
    found = report.by_rule("dtype-promotion")
    assert found, report.render()
    assert any("ffn_down" in f.scope for f in found)
    assert any("float64" in f.message for f in found)


def test_self_test_catches_everything():
    assert self_test() == []


# ---------------------------------------------------------------------------
# The Select-count model
# ---------------------------------------------------------------------------

def test_family_selects_mirrors_dispatch():
    base = dict(n=4, k_frac=0.125, route_share=0)
    # bisect k-WTA stages no top_k; the topk-path projection re-derives.
    assert family_selects(SparsityConfig(kwta_impl="bisect", **base),
                          4, 128, 64) == 1
    # large batch leaves the topk regime: no Select at all.
    assert family_selects(SparsityConfig(kwta_impl="bisect", **base),
                          64, 128, 64) == 0
    # exact global top-k: one Select, support handed off (no re-derive).
    assert family_selects(SparsityConfig(kwta_impl="topk", **base),
                          4, 128, 64) == 1
    # local k-WTA is sort-free and has no handoff form: only the
    # re-derivation stages a Select.
    assert family_selects(SparsityConfig(kwta_impl="topk",
                                         kwta_partitions=2, **base),
                          4, 128, 64) == 1
    # dense activations: nothing to Select.
    assert family_selects(SparsityConfig(n=4), 4, 128, 64) == 0


def test_expected_selects_layer_keys_and_moe_skip():
    exp = expected_selects(_smollm_reduced(), n_tokens=4)
    assert exp == {"b0_attn/ffn": 1, "b1_attn/ffn": 1}
    assert expected_selects(get_config("deepseek_v2_lite_16b"), 4) is None


def test_layer_key_collapses_paths():
    assert layer_key("b0_attn/ffn_down/cs_topk/select") == "b0_attn/ffn"
    assert layer_key("b1_attn/o_proj/select") == "b1_attn/o_proj"
    assert layer_key("b1_attn/transpose") == "b1_attn"
    assert layer_key("softmax") == ""


# ---------------------------------------------------------------------------
# Taint propagation (the dense-fallback engine)
# ---------------------------------------------------------------------------

def test_taint_flags_dot_on_select_support():
    def bad(x, w):
        vals, _ = jax.lax.top_k(x, 4)
        return vals @ w

    closed = jax.make_jaxpr(bad)(jnp.zeros((2, 8)), jnp.zeros((4, 3)))
    _, hits = propagate_taint(closed, ("top_k",), ("pallas_call",),
                              ("dot_general",))
    assert len(hits) == 1 and hits[0].eqn.primitive.name == "dot_general"


def test_taint_stops_at_sink_and_clean_inputs_pass():
    def clean(x, w):
        jax.lax.top_k(x, 4)      # support derived but never consumed
        return x @ w

    closed = jax.make_jaxpr(clean)(jnp.zeros((2, 8)), jnp.zeros((8, 3)))
    _, hits = propagate_taint(closed, ("top_k",), ("pallas_call",),
                              ("dot_general",))
    assert hits == []


def test_taint_crosses_scan_boundaries():
    def scanned(x, w):
        vals, _ = jax.lax.top_k(x, 4)

        def body(carry, _):
            return carry @ w, None

        y, _ = jax.lax.scan(body, vals, jnp.arange(3))
        return y

    closed = jax.make_jaxpr(scanned)(jnp.zeros((2, 8)), jnp.zeros((4, 4)))
    _, hits = propagate_taint(closed, ("top_k",), ("pallas_call",),
                              ("dot_general",))
    assert len(hits) >= 1


# ---------------------------------------------------------------------------
# Pallas resource rule (shared validator on staged BlockSpecs)
# ---------------------------------------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def test_pallas_resource_vmem_budget():
    from jax.experimental import pallas as pl

    def big(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    x = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)   # 16 MiB per buffer
    closed = jax.make_jaxpr(big)(x)
    findings = rule_pallas_resource(closed, entry="kernel")
    assert any("VMEM" in f.message for f in findings), findings


def test_pallas_resource_clean_kernel():
    from repro.kernels.ops import topk_gather_support_op

    vals = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    idx = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    packed = jax.ShapeDtypeStruct((16, 16, 4), jnp.float32)
    route = jax.ShapeDtypeStruct((16, 16, 4), jnp.int32)
    closed = jax.make_jaxpr(
        lambda v, i, s, p, r: topk_gather_support_op(v, i, s, p, r, True))(
        vals, idx, idx, packed, route)
    assert rule_pallas_resource(closed, entry="kernel") == []


# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------

def test_waivers_by_rule_and_scope():
    f1 = Finding(rule="select-count", message="m", scope="b0_attn/ffn")
    f2 = Finding(rule="dense-fallback", message="m", scope="b1_attn/ffn")
    r = Report()
    r.add([f1, f2], waivers=("select-count:b0_attn",))
    assert [f.rule for f in r.findings] == ["dense-fallback"]
    assert r.waived == [f1]
    assert not r.ok
    r2 = Report()
    r2.add([f1, f2], waivers=("select-count", "dense-fallback"))
    assert r2.ok and len(r2.waived) == 2


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------

def test_cli_clean_config_exits_zero(capsys):
    rc = cli_main(["--config", "smollm_360m", "--reduced", "--no-hlo",
                   "--fail-on-findings"])
    out = capsys.readouterr().out
    assert rc == 0 and "clean: 0 findings" in out


def test_cli_seeded_regression_exits_nonzero(capsys):
    rc = cli_main(["--seed-regression", "double-topk"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "b0_attn/ffn" in out and "top_k" in out   # layer + primitive


def test_cli_self_test_exits_zero(capsys):
    assert cli_main(["--self-test"]) == 0


def test_cli_usage_error_exits_two(capsys):
    assert cli_main([]) == 2
