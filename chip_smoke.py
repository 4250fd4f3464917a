"""Smoke run of the sparse-sparse serving path on one TPU chip.

Drives the main path once, through the entry points a user calls:
``Engine`` with the paged KV cache and grow-on-demand page chains, serving
greedy requests on smollm-360m at its published widths with random
weights made from a seed, and the config's own sparse-sparse FFN with
``use_pallas`` left at ``auto``.  Phases, each of which fails the run:

1. kernel  — ``topk_gather_matmul`` at the FFN down-projection's shape for
   every decode batch on the topk path, against the jnp reference;
2. serve   — 8 requests at 4 slots, prompts of 64-200 tokens (chunked
   prefill), 32 new tokens each; the dispatch events must show the
   down-projection on the Pallas kernel, compiled (not interpreted), and
   the compiled decode step must hold the kernel (``tpu_custom_call``).

``--chips 4`` runs only the tensor-parallel comparison instead: the same
requests on a (1, 4) mesh and on a (1, 1) mesh over the first device.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed on a TPU.  Without a TPU the script exits
non-zero; ``--reduced`` then runs the small same-family config as a CPU
rehearsal, which still never prints a result.

Usage:
  python chip_smoke.py                 # one chip
  python chip_smoke.py --chips 4       # four chips, mesh comparison only
  JAX_PLATFORMS=cpu python chip_smoke.py --reduced      # CPU rehearsal
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import kwta  # noqa: E402
from repro.core.api import choose_executor, observe_dispatch  # noqa: E402
from repro.core.layers import packed_linear_init  # noqa: E402
from repro.kernels import ref as R  # noqa: E402
from repro.kernels import (to_partition_major, topk_gather_matmul,  # noqa
                           topk_support)
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.hlo import collective_stats  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import Engine  # noqa: E402
from repro.runtime.scheduler import Request  # noqa: E402

ARCH = "smollm-360m"
#: smollm-360m's published widths (HuggingFaceTB/SmolLM-360M config).
PUBLISHED = dict(n_layers=32, d_model=960, n_heads=15, n_kv_heads=5,
                 d_ff=2560, vocab_size=49152)
SEED = 0
N_SLOTS = 4
N_REQUESTS = 8
PROMPT_LENS = (64, 200)
MAX_NEW = 32
MAX_SEQ = 256
#: Bound on max |kernel - reference| / max(1, max |reference|).  Both sum
#: the same f32 products, in different orders: the gap is f32 rounding.
KERNEL_ERR_BOUND = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")
    log(f"ok: {what}")


def make_requests(cfg):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    lens[0] = PROMPT_LENS[1]          # at least one multi-chunk prompt
    return [Request(uid=i, max_new_tokens=MAX_NEW,
                    prompt=rng.integers(0, cfg.vocab_size, int(n)).tolist())
            for i, n in enumerate(lens)]


def down_proj_shape(cfg):
    """(P, G, N, K) of the FFN down-projection: d_ff in, d_model out."""
    sp = cfg.ffn_sparsity
    return cfg.d_ff // sp.n, cfg.d_model // sp.n, sp.n, sp.k_for(cfg.d_ff)


def phase_kernel(cfg) -> None:
    """topk_gather_matmul vs ref_topk_gather at the served layout: bf16
    weights and int8 routes from the layer's own init, supports from
    k-WTA'd activations, every decode batch B with B*K < d_ff."""
    sp = cfg.ffn_sparsity
    p, g, n, k = down_proj_shape(cfg)
    ex = choose_executor(dataclasses.replace(sp, use_pallas="force"))
    log(f"kernel: topk_gather_matmul P={p} G={g} N={n} K={k} "
        f"interpret={ex.interpret}")
    key_w, key_x = jax.random.split(jax.random.PRNGKey(SEED))
    params, _ = packed_linear_init(key_w, cfg.d_ff, cfg.d_model, sp,
                                   bias=False)
    pr, rr = to_partition_major(params["packed"].astype(jnp.bfloat16),
                                params["route"])
    batches = [b for b in range(1, 9) if b * k < p * n]
    worst = 0.0
    for b in batches:
        h = kwta(jax.random.normal(jax.random.fold_in(key_x, b),
                                   (b, cfg.d_ff)), k)
        vals, p_idx, s_off = topk_support(h, k, n)
        y = topk_gather_matmul(vals, p_idx, s_off, pr, rr,
                               interpret=ex.interpret)
        with jax.default_matmul_precision("highest"):
            y_ref = R.ref_topk_gather(vals, p_idx, s_off, pr, rr)
        y, y_ref = np.asarray(y), np.asarray(y_ref)
        check(y.shape == (b, g * n) and np.isfinite(y).all(),
              f"kernel B={b}: finite output of shape {y.shape}")
        err = float(np.abs(y - y_ref).max()) / max(1.0,
                                                   float(np.abs(y_ref).max()))
        log(f"kernel B={b}: max|y - ref| / max(1, max|ref|) = {err:.3e} "
            f"(max|ref| = {float(np.abs(y_ref).max()):.4f})")
        worst = max(worst, err)
    check(worst <= KERNEL_ERR_BOUND,
          f"kernel error {worst:.3e} <= bound {KERNEL_ERR_BOUND:.0e} for "
          f"B in {batches}")


def serve(cfg, mesh, reqs, events):
    """Serve ``reqs`` greedily on ``mesh``; returns (engine, outputs,
    stats, compiled decode step text)."""
    engine = Engine(cfg, mesh, max_seq=MAX_SEQ, n_slots=N_SLOTS,
                    kv_layout="paged", kv_policy="grow")
    with observe_dispatch(events.append):
        t0 = time.perf_counter()
        out, stats = engine.serve(reqs)
        wall = time.perf_counter() - t0
        text = engine.lower_decode_step().compile().as_text()
    log(f"serve: {len(out)} requests served, {stats['decode_steps']} decode "
        f"steps, {stats['prefill_calls']} prefill calls, "
        f"{stats['prefill_chunks']} prefill chunks, "
        f"{wall:.1f} s wall (compilation included)")
    return engine, out, stats, text


def check_outputs(cfg, reqs, out, stats) -> None:
    check(sorted(out) == [r.uid for r in reqs]
          and all(len(out[r.uid]) == r.max_new_tokens for r in reqs),
          f"every request served with {MAX_NEW} tokens")
    check(all(0 <= t < cfg.vocab_size for v in out.values() for t in v),
          "every token inside the vocabulary")
    check(stats["prefill_calls"] == len(reqs)
          and stats["prefill_chunks"] > len(reqs),
          "one prefill per request, long prompts chunk-prefilled")


def phase_serve(cfg, devices, on_tpu: bool) -> None:
    reqs = make_requests(cfg)
    events: list = []
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    _, out, stats, text = serve(cfg, mesh, reqs, events)
    check_outputs(cfg, reqs, out, stats)
    p, g, n, _ = down_proj_shape(cfg)
    down = [e for e in events if e["path"] == "topk"
            and e["d_in"] == p * n and e["d_out"] == g * n]
    log(f"dispatch: FFN down-projection events {down[:1]} "
        f"(x{len(down)})")
    check(bool(down) and all(e["batch"] == N_SLOTS for e in down),
          f"decode down-projection took the topk path at batch {N_SLOTS}")
    check(all(e["pallas"] == on_tpu and not e["interpret"] for e in down),
          f"down-projection dispatched pallas={on_tpu}, interpret=False")
    has_kernel = "tpu_custom_call" in text
    log(f"compiled decode step holds tpu_custom_call: {has_kernel}")
    if on_tpu:
        check(has_kernel, "tpu_custom_call in the compiled decode step")


def device_bytes(tree):
    """Bytes of ``tree``'s shards held by each device id."""
    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return held


def phase_four_chips(cfg, devices, on_tpu: bool) -> None:
    """Tensor parallelism over a (1, 4) mesh vs one device.  float32
    compute, so that the order in which shards' partial sums are reduced
    cannot flip a greedy token."""
    check(len(devices) >= 4, f"4 devices visible ({len(devices)})")
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    reqs = make_requests(cfg)
    mesh4 = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    ev4: list = []
    eng4, out4, stats4, text4 = serve(cfg, mesh4, reqs, ev4)
    _, out1, stats1, text1 = serve(cfg, mesh1, reqs, [])
    same = sum(a == b for r in reqs for a, b in zip(out4[r.uid], out1[r.uid]))
    log(f"tokens: {same} of {len(reqs) * MAX_NEW} equal across the meshes")
    held = device_bytes(eng4.params)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(eng4.params))
    log(f"params: {total} bytes in all; held per device {held}")
    c4, c1 = collective_stats(text4), collective_stats(text1)
    log(f"collectives (1, 4) step: {c4}")
    log(f"collectives (1, 1) step: {c1}")
    down = [e for e in ev4 if e["path"] == "topk"]
    has_kernel = "tpu_custom_call" in text4
    log(f"compiled (1, 4) decode step holds tpu_custom_call: {has_kernel}")
    check_outputs(cfg, reqs, out4, stats4)
    check(out4 == out1, "greedy tokens on the (1, 4) mesh equal the (1, 1) "
          "mesh's")
    check(len(held) == 4 and max(held.values()) < total,
          "parameters sharded over four devices, not all on the first")
    check(bool(c4) and not c1,
          "the sharded decode step communicates; the one-device step "
          "does not")
    check(bool(down) and all(e["pallas"] == on_tpu and not e["interpret"]
                             for e in down),
          f"(1, 4) down-projection dispatched pallas={on_tpu}, "
          "interpret=False")
    if on_tpu:
        check(has_kernel, "tpu_custom_call in the (1, 4) decode step")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (1, 4)-mesh comparison")
    ap.add_argument("--reduced", action="store_true",
                    help="small same-family config (CPU rehearsal)")
    args = ap.parse_args()

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    log(f"compile cache: {setup_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if not on_tpu and not args.reduced:
        raise SystemExit(f"no TPU: JAX found {dev.platform} only "
                         "(--reduced runs a CPU rehearsal)")

    cfg = get_config(ARCH)
    if args.reduced:
        cfg = cfg.reduced()
    widths = {f: getattr(cfg, f) for f in PUBLISHED}
    log(f"config: {cfg.name} {widths} ffn_sparsity={cfg.ffn_sparsity}")
    if not args.reduced:
        check(widths == PUBLISHED, f"{ARCH} at its published widths")

    if args.chips == 4:
        phase_four_chips(cfg, devices, on_tpu)
    else:
        phase_kernel(cfg)
        phase_serve(cfg, devices, on_tpu)
    log(f"compile seconds: {compile_s[0]:.1f}")

    if not on_tpu:
        log(f"rehearsal on {dev.platform}: every phase passed; no result "
            "without a TPU")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
