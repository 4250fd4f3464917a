"""Operation and byte counts against hand counts at small shapes."""

import json

import pytest

from chipbench import counts as CN
from chipbench import spec


def test_topk_gather_hand_count():
    # B=2 rows, K=3 non-zeros of D_in = P·N = 4·2 = 8, G=5 groups of N=2
    w = CN.topk_gather(b=2, k=3, p=4, g=5, n=2, route_groups=1,
                       weight_bytes=4, route_bytes=1, value_bytes=2,
                       index_bytes=4, out_bytes=2)
    assert w.flops == 2 * 2 * 3 * 5
    weights = 3 * 5 * 4             # K rows of G weights, f32
    routes = 2 * 1 * 2 * 1          # ceil(3/2) partitions, N int8 each
    support = 2 * 3 * (2 + 4)       # value + index per non-zero
    out = 2 * 5 * 2 * 2             # B x G·N outputs, bf16
    assert w.bytes == weights + routes + support + out


def test_topk_gather_counts_no_widened_tile():
    m = json.loads((spec.BENCH_DIR / "configs" / "smollm-360m.json")
                   .read_text())["model"]
    n, d_ff, d = 4, m["d_ff"], m["d_model"]
    w = CN.topk_gather(b=4, k=CN.k_for(d_ff, 0.125), p=d_ff // n,
                       g=d // n, n=n, route_groups=1, weight_bytes=4,
                       route_bytes=1, value_bytes=2, index_bytes=4,
                       out_bytes=2)
    # the kernel's widened resident tile (f32 weights + int32 routes,
    # 8·d_ff·d_model/N bytes) is waste, never counted
    assert w.bytes < 8 * d_ff * d / n / 4
    assert CN.k_for(d_ff, 0.125) == 320


def test_lm_token_hand_count():
    cfg = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_head": 4,
           "d_ff": 16, "n_layers": 3, "vocab_size": 10,
           "ffn_sparsity": {"n": 2, "k_frac": 0.25}}
    proj = 2 * 8 * 8 + 2 * 2 * 8 * 4 + 2 * 8 * 8   # q, k+v, o
    ffn = 2 * (2 * 8 * 16 / 2) + 2 * 4 * 8 / 2      # up+gate at 1/N, down K
    assert CN.lm_layer_flops(cfg, 5) == proj + 2 * 2 * 5 * 8 + ffn
    # prompt of 2 and 3 served tokens: positions 0..3 through the layers,
    # the head for each served token
    layers = sum(CN.lm_layer_flops(cfg, c) for c in (1, 2, 3, 4))
    assert CN.lm_request_flops(cfg, 2, 3) == pytest.approx(
        3 * layers + 3 * 2 * 8 * 10)


def test_gsc_utterance_hand_count():
    m = json.loads((spec.BENCH_DIR / "configs" / "gsc-cnn.json")
                   .read_text())["model"]
    macs = CN.gsc_macs(m)
    conv1 = 28 * 28 * 64 * 25 / 5
    conv2 = 10 * 10 * 64 * 25 * 64 / (16 * 8)
    linear = 1600 * 1500 / (16 * 8)
    out = 1500 * 12 / (1504 / 180)
    assert macs["sparse_sparse"] == pytest.approx(conv1 + conv2 + linear +
                                                  out)
    assert CN.gsc_utterance_flops(m) == 2 * macs["sparse_sparse"]
