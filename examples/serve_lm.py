"""Serve a (reduced) assigned-architecture LM with the continuous-batching
engine: chunked prefill into a paged KV cache, mid-flight admission,
greedy or temperature/top-k sampling — the decode path the sparse-sparse
topk dispatch targets.

Runs with telemetry on and ends with a human-readable summary: throughput,
TTFT p50/p95, stage breakdown, and the realized k/N per sparse layer (what
fraction of each FFN actually fired, vs the configured k).

Run: PYTHONPATH=src python examples/serve_lm.py --arch smollm-360m
"""

import argparse

import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.obs import Telemetry
from repro.runtime.scheduler import Request, SamplingParams


def _ms(v):
    return "n/a" if v is None else f"{v * 1e3:.0f}ms"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--no-telemetry", action="store_true",
                    help="serve without tracing/metrics (skips the summary)")
    ap.add_argument("--telemetry-jsonl", default=None, metavar="PATH",
                    help="also stream span/request events to a JSONL file")
    args = ap.parse_args()
    cfg = get_config(args.arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    tel = (Telemetry.off() if args.no_telemetry
           else Telemetry.on(jsonl_path=args.telemetry_jsonl,
                             sparsity_every=4))
    engine = Engine(cfg, mesh, max_seq=64, n_slots=args.slots, telemetry=tel)
    rng = np.random.default_rng(0)
    # mixed prompt lengths + budgets: the case continuous batching wins
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        8 + 4 * (i % 3)).tolist(),
                    max_new_tokens=max(1, args.gen - 4 * (i % 3)),
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k, seed=i))
            for i in range(args.requests)]
    out, stats = engine.serve(reqs)
    print(f"arch={cfg.name} served {len(out)} requests in "
          f"{stats['wall_s']:.2f}s: {stats['tok_s']:.1f} tok/s, "
          f"{stats['decode_steps']} decode steps, "
          f"{stats['prefill_calls']} prefill calls (1 per prompt), "
          f"{stats['prefill_chunks']} prefill chunks")
    for uid in sorted(out)[:2]:
        print(f"  req {uid} ({len(out[uid])} toks, "
              f"ttft {stats['ttft_s'][uid]*1e3:.0f}ms):", out[uid][:12])
    if tel.enabled:
        snap = engine.metrics_snapshot()
        hists = snap["metrics"]["histograms"]
        ttft = hists.get("serve.ttft_s", {})
        itl = hists.get("serve.itl_s", {})
        print("-- telemetry ----------------------------------------------")
        print(f"  ttft  p50 {_ms(ttft.get('p50'))}  "
              f"p95 {_ms(ttft.get('p95'))}")
        print(f"  itl   p50 {_ms(itl.get('p50'))}  "
              f"p95 {_ms(itl.get('p95'))}")
        stages = sorted(snap["stages"].items(),
                        key=lambda kv: -kv[1]["total_s"])
        brk = "  ".join(f"{name} {t['total_s']:.2f}s" for name, t in stages)
        print(f"  stages: {brk}")
        layers = snap["sparsity"]["layers"]
        if layers:
            print("  realized sparsity (mean k/N fired per layer):")
            for name in sorted(layers):
                e = layers[name]
                rk = e.get("realized_k_frac")
                cfg_k = e.get("configured_k_frac")
                ov = e.get("winner_overlap")
                line = f"    {name}: k/N {rk:.4f}" if rk is not None \
                    else f"    {name}: k/N n/a"
                if cfg_k:
                    line += f" (configured {cfg_k:.4f})"
                if ov is not None:
                    line += f", step-to-step winner overlap {ov:.2f}"
                print(line)
        else:
            print("  realized sparsity: no sparse layers in this config")
        tel.close()
