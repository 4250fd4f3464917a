"""Driver: a language model with experts and latent attention, served by
the program's ``Engine``.

The window, the check and the control are ``lm_serve``'s.  What such a
model adds is read here: its operations
(``chipbench.counts_mla_moe``), the engine's ``moe.*`` counters (routed
pairs that went to an expert held on this chip, token visits to the
expert layers), and, in a traced run, the device time per decode step of
the ops under the expert layer's and the latent attention's scopes
(``chipbench.scopes``).
"""

from __future__ import annotations

import gc
import time

from chipbench import counts_mla_moe as CN
from chipbench import spec
from chipbench import traffic as TR
from chipbench.device import memory_peak_bytes
from chipbench.harness import Check, Record, Run
from chipbench.probes import ProfiledTracer, RecordingRegistry, TraceWindow
from chipbench.scopes import scope_ms_dir
from chipbench.tracing import profile_options
from chipbench.weights import check_layout, make_params

LM = spec.load_module(spec.BENCH_DIR / "drivers" / "lm_serve.py")

#: The decode step's device program (``jit_<function name>``) and the
#: scopes whose device time per step the per-layer metrics read.
DECODE_MODULE = "jit_decode_step_paged"
SCOPES = ("moe.experts", "mla.attn")
COUNTERS = ("moe.held_assignments", "moe.tokens")
TRACE_PROGRAMS = LM.TRACE_PROGRAMS
control = LM.control


def run(run: Run) -> Record:
    import numpy as np
    from repro.models import transformer as T
    from repro.obs import Telemetry, Tracer
    t = run.cell.traffic
    m = run.cell.config["model"]
    cfg = LM.model_config(run.cell.config)
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "chip")
    params = make_params(run)
    check_layout(params, lambda k: T.init_model(k, cfg)[0])
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "weights")
    registry = RecordingRegistry()
    tw = None
    if run.trace_dir:
        tw = TraceWindow(run.trace_dir, float(t["trace_start_s"]),
                         float(t["trace_seconds"]), profile_options())
        tracer = ProfiledTracer(tw.tick)
    else:
        tracer = Tracer(enabled=True)
    telemetry = Telemetry(registry=registry, tracer=tracer, enabled=True,
                          sparsity_every=0)
    engine = LM.build_engine(run, cfg, params, telemetry)
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "engine")
    LM.warm_up(engine, t, cfg.vocab_size)
    gen = TR.OfflineBatches(t, cfg.vocab_size, run.seed)
    setup_s = time.perf_counter() - run.t_start
    compiled = run.compiles.count
    run.log(f"set-up {setup_s:.3f} s ({compiled} compilations, "
            f"{run.compiles.seconds:.1f} s)")
    if tw:
        tw.arm()
    w = LM.window(run, engine, gen, registry)
    if tw:
        tw.stop()
    counters = {name: registry.counter(name).value for name in COUNTERS}
    run.log(f"counters over the window: {counters}")
    peak = memory_peak_bytes(run.devices)
    run.log(f"window {w['window_s']:.3f} s, batches of "
            f"{', '.join(f'{b:.3f}' for b in w['batch_s'])} s, "
            f"{run.compiles.count - compiled} compilations inside it")
    scope = {}
    if tw and tw.state == "done":
        scope = scope_ms_dir(run.trace_dir, DECODE_MODULE, SCOPES)
        run.log(f"device ms per decode step by scope: {scope}")

    uids = LM.check_sample(run, w)
    items = [(w["asked"][u].prompt, w["served"][u]) for u in uids]
    del engine, params, telemetry
    gc.collect()
    t_check = time.perf_counter()
    got = LM.readings(run, make_params(run), items)
    run.log(f"check: {len(items)} requests, "
            f"{sum(len(s) for _, s in items)} served tokens, "
            f"{time.perf_counter() - t_check:.1f} s")

    asked, served = w["asked"], w["served"]
    complete = [u for u in asked if len(served.get(u, ())) ==
                asked[u].max_new_tokens and all(0 <= x < cfg.vocab_size
                                                for x in served[u])]
    n_tokens = sum(len(v) for v in served.values())
    failed = len(asked) - len(complete)
    checks = [Check(k, got[k], float(v)) for k, v in t["limits"].items()]
    return Record(
        end_to_end={"tok_s": n_tokens / w["window_s"],
                    "itl_p95_ms": 1e3 * float(np.percentile(w["itl_s"], 95)),
                    "setup_s": setup_s},
        attempted=len(asked), failed=failed,
        checks=checks + [Check("incomplete_requests", failed, 0)],
        memory_peak_bytes=peak, window_s=w["window_s"],
        data={"readings": got,
              "decode_steps": w["decode_steps"],
              "decode_tokens": n_tokens - len(served),
              "n_slots": int(t["n_slots"]),
              "decode_step_s": w["decode_step_s"],
              "window_flops": CN.lm_window_flops(
                  m, [(len(asked[u].prompt), len(served[u]))
                      for u in served]),
              "counters": counters,
              "held_share": (counters["moe.held_assignments"]
                             / (counters["moe.tokens"]
                                * m["experts_per_token"])
                             if counters["moe.tokens"] else None),
              "scope_ms": scope,
              "model": m},
        programs=TRACE_PROGRAMS,
        span_names=sorted(getattr(tracer, "names", ())), check_items=items)
