"""A whole run at a small size on the CPU, the harness's look for a chip
skipped: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false; and the control (the plain
reference one step below the configuration's precision, in the
program's place) reads beyond the cell's limits."""

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spec

sys.path.insert(0, str(spec.BENCH_DIR / "drivers"))

SEED = 2 ** 40 + 99
LM_SIZES = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                vocab_size=256, d_head=16)


def small_lm_cell():
    cell = spec.load_cell("smollm-360m.decode-s4")
    return dataclasses.replace(
        cell, config=dict(cell.config, model=dict(cell.config["model"],
                                                  **LM_SIZES)),
        traffic=dict(cell.traffic, n_slots=2, requests_per_batch=4,
                     prompt_len=[8, 40], output_len=[24, 48], max_seq=128,
                     check_requests=4))


def small_gsc_cell():
    cell = spec.load_cell("gsc-cnn.stream-b1024")
    return dataclasses.replace(cell, traffic=dict(
        cell.traffic, batch=16, pool_batches=2, check_calls=2))


def run(cell):
    return harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.mark.parametrize("make_cell", [small_lm_cell, small_gsc_cell])
def test_control_is_not_correct(make_cell):
    cell = make_cell()
    r = harness.Run(cell=cell, reference=spec.reference_module(cell.config),
                    seed=SEED, seconds=0.2, devices=jax.devices()[:1],
                    t_start=time.perf_counter(),
                    compiles=harness.CompileClock())
    driver = spec.driver_module(cell.config)
    record = driver.run(r)
    assert record.correct
    control = driver.control(r, record)
    limits = cell.traffic["limits"]
    assert any(control[k] > v for k, v in limits.items()), control


def test_lm_sound_run_is_correct():
    line = run(small_lm_cell())
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"tok_s", "itl_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"


def test_lm_token_altered_where_produced(monkeypatch):
    import repro.launch.serve as serve
    produced = [0]
    real = serve.sample_token

    def altered(logits, sampling, rng):
        tok = real(logits, sampling, rng)
        produced[0] += 1
        return (tok + 1) % LM_SIZES["vocab_size"] if produced[0] % 5 == 0 \
            else tok

    monkeypatch.setattr(serve, "sample_token", altered)
    assert not run(small_lm_cell())["correct"]


def test_gsc_sound_run_is_correct():
    line = run(small_gsc_cell())
    assert line["correct"]
    assert set(line["metrics"]) == {"words_s", "setup_s"}


def _answer_altered(forward):
    """One answer in eight comes out with its class scores reversed."""
    def broken(params, x, cfg):
        out = forward(params, x, cfg)
        return out.at[::8].set(out[::8, ::-1])
    return broken


def _half_batch_left_out(forward):
    def broken(params, x, cfg):
        half = forward(params, x[: x.shape[0] // 2], cfg)
        return jnp.concatenate([half, half])
    return broken


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out])
def test_gsc_fault_is_caught(monkeypatch, fault):
    from repro.models import gsc_cnn as G
    monkeypatch.setattr(G, "forward", fault(G.forward))
    assert not run(small_gsc_cell())["correct"]
