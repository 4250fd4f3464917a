"""The DeepSeek-V2-Lite cell: a whole run at a small size on the CPU (the
harness's look for a chip skipped) comes out correct and the control
does not; its count of operations; the scope reduction on a recorded
trace; and its weights' layout at the published widths."""

import dataclasses
import json
import time

import jax
import pytest

from chipbench import counts as CN
from chipbench import counts_mla_moe as MC
from chipbench import harness, scopes, spec

CELL = "deepseek-v2-lite.decode-s16"
SEED = 2 ** 40 + 99
SIZES = dict(n_layers=3, n_dense_layers=1, d_model=64, n_heads=4,
             n_kv_heads=4, d_head=16, d_ff=32, dense_d_ff=96,
             vocab_size=256, n_experts=8, held_experts=2,
             held_expert_start=3, experts_per_token=2, n_shared_experts=2,
             kv_lora_rank=32, rope_head_dim=8)


def small_cell():
    cell = spec.load_cell(CELL)
    return dataclasses.replace(
        cell, config=dict(cell.config, model=dict(cell.config["model"],
                                                  **SIZES)),
        traffic=dict(cell.traffic, n_slots=2, requests_per_batch=4,
                     prompt_len=[8, 40], output_len=[24, 48], max_seq=128,
                     check_requests=4))


def test_sound_run_is_correct_and_control_is_not():
    cell = small_cell()
    r = harness.Run(cell=cell, reference=spec.reference_module(cell.config),
                    seed=SEED, seconds=0.2, devices=jax.devices()[:1],
                    t_start=time.perf_counter(),
                    compiles=harness.CompileClock())
    driver = spec.driver_module(cell.config)
    record = driver.run(r)
    assert record.correct, [(c.name, c.value, c.limit)
                            for c in record.checks]
    assert record.failed == 0
    counters = record.data["counters"]
    assert counters["moe.tokens"] > 0
    # 2 of 8 experts held: about a quarter of the routed pairs
    assert 0.05 < record.data["held_share"] < 0.6
    control = driver.control(r, record)
    limits = cell.traffic["limits"]
    assert any(control[k] > v for k, v in limits.items()), control


def test_smollm_count_unchanged():
    """smollm-360m's cells keep ``chipbench.counts``: 2 layers' worth of
    the GQA and sparse-FFN count, by hand."""
    m = spec.load_cell("smollm-360m.decode-s4").config["model"]
    d, h, hkv, dh, ff = 960, 15, 5, 64, 2560
    fixed = (2 * d * h * dh + 4 * d * hkv * dh + 2 * h * dh * d
             + 2 * (2 * d * ff / 4) + 2 * 320 * d / 4)
    per_ctx = 4 * h * dh
    p, s = 100, 10
    n = p + s - 1
    want = 32 * (n * fixed + per_ctx * n * (n + 1) / 2) + 2 * d * 49152 * s
    assert CN.lm_window_flops(m, [(p, s)]) == pytest.approx(want, rel=1e-12)


def test_mla_moe_hand_count():
    m = spec.load_cell(CELL).config["model"]
    d, h, r, dr, dh = 2048, 16, 512, 64, 128
    proj = 2 * d * h * 192 + 2 * d * 576 + 2 * h * dh * d + 4 * r * h * dh

    def ffn(width, k):
        return 4 * d * width / 4 + 2 * k * d / 4

    expert_layer = (2 * d * 64 + 6 * 16 / 64 * ffn(1408, 176)
                    + ffn(2816, 352))
    per_token = 27 * proj + ffn(10944, 1368) + 26 * expert_layer
    prompt_ctx = 27 * (2 * h * 192 + 2 * h * dh)        # expanded
    decode_ctx = 27 * (2 * h * 576 + 2 * h * r)         # absorbed
    p, s = 3, 3   # prompt contexts 1, 2, 3; decode contexts 4, 5
    want = (5 * per_token + 6 * prompt_ctx + 9 * decode_ctx
            + 3 * 2 * d * 102400)
    assert MC.lm_request_flops(m, p, s) == pytest.approx(want, rel=1e-12)
    assert MC.lm_window_flops(m, [(p, s), (p, s)]) == pytest.approx(2 * want)


def test_scope_time_on_recorded_trace():
    """``testdata/small.xplane.pb`` (one TPU v5 lite): six runs of
    ``small_step``, whose kernel call was traced under the
    ``jit(topk_gather_matmul)`` scope."""
    space = scopes.read_xspace(str(spec.BENCH_DIR / "testdata"
                                   / "small.xplane.pb"))
    ms = scopes.scope_ms(space, "jit_small_step",
                         ["jit(topk_gather_matmul)", "moe.experts"])
    assert 0 < ms["jit(topk_gather_matmul)"] < 1.0
    assert ms["moe.experts"] == 0
    assert scopes.scope_ms(space, "jit_decode_step_paged", ["x"]) == {}


def test_weights_match_the_program_layout():
    """The reference's weights have the tree, shapes and dtypes of the
    program's parameters at the published widths (shapes only)."""
    from repro.models import transformer as T
    cell = spec.load_cell(CELL)
    ref = spec.reference_module(cell.config)
    m = cell.config["model"]
    cfg = spec.driver_module(cell.config).LM.model_config(cell.config)
    got = jax.eval_shape(lambda k: ref.make_weights(k, m),
                         jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda k: T.init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), got)
            == jax.tree.map(lambda a: (a.shape, a.dtype), want))


def test_config_file_holds_the_catalog_numbers():
    """Every number of the published config is in the file under its own
    key, the held experts' count in place of the routed count."""
    c = spec.load_cell(CELL).config
    m = c["model"]
    assert c["n_routed_experts"] == m["held_experts"] == 16
    assert c["published"]["n_routed_experts"] == m["n_experts"] == 64
    assert (c["num_hidden_layers"], c["hidden_size"], c["vocab_size"],
            c["first_k_dense_replace"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"]) == (
        m["n_layers"], m["d_model"], m["vocab_size"], m["n_dense_layers"],
        m["dense_d_ff"], m["d_ff"], m["experts_per_token"])
    rs = c["rope_scaling"]
    assert (rs["factor"], rs["mscale"], rs["mscale_all_dim"]) == (
        m["yarn_factor"], m["yarn_mscale"], m["yarn_mscale_all_dim"])
    json.dumps(c)
