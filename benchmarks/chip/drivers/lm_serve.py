"""Driver: a language model served by the program's ``Engine``.

The window serves successive offline batches of the traffic mix with
``Engine.serve`` (all of a batch's requests queued at once, greedy
sampling) until ``--seconds`` have passed; no batch starts after that,
and the window ends when the last one drains.  The weights come from the
reference file's ``make_weights``, so the check can make them again
without taking anything from the program.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import counts as CN
from chipbench import traffic as TR
from chipbench.device import memory_peak_bytes
from chipbench.harness import Check, Record, Run
from chipbench.probes import ProfiledTracer, RecordingRegistry, TraceWindow
from chipbench.tracing import profile_options
from chipbench.weights import check_layout, make_params

ITL = "serve.itl_s"
STEP = "serve.decode_step_s"


def model_config(config: Dict):
    """The program's ``ModelConfig`` with every size of the config file."""
    from repro.configs import get_config
    from repro.core.api import SparsityConfig
    m = dict(config["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    for fam in ("ffn_sparsity", "proj_sparsity"):
        if fam in m:
            m[fam] = SparsityConfig(**m[fam])
    return dataclasses.replace(get_config(config["program_config"]), **m)


def build_engine(run: Run, cfg, params, telemetry):
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import Engine
    t = run.cell.traffic
    mesh = make_mesh((1, 1), ("data", "model"), devices=run.devices[:1])
    return Engine(cfg, mesh, max_seq=int(t["max_seq"]),
                  n_slots=int(t["n_slots"]), params=params,
                  telemetry=telemetry, kv_layout=t["kv_layout"],
                  page_size=int(t["page_size"]), kv_policy=t["kv_policy"])


def to_requests(batch: List[TR.LMRequest]):
    from repro.runtime.scheduler import Request
    return [Request(uid=r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in batch]


def warm_up(engine, traffic: Dict, vocab: int) -> None:
    """Compile the cell's programs: a chunk, a partial chunk, the decode
    step at ``n_slots``."""
    rng = np.random.default_rng(0)
    n = int(traffic["n_slots"])
    longest = int(traffic["max_seq"]) - 3
    lens = [min(engine.prefill_chunk + 1 + i, longest) for i in range(n)]
    reqs = [TR.LMRequest(uid=i, prompt=rng.integers(0, vocab, p).tolist(),
                         max_new_tokens=3) for i, p in enumerate(lens)]
    engine.serve(to_requests(reqs))


def window(run: Run, engine, gen: TR.OfflineBatches, registry) -> Dict:
    """Serve batches until ``run.seconds``; every request and count."""
    served: Dict[int, List[int]] = {}
    asked: Dict[int, TR.LMRequest] = {}
    steps = 0
    registry.reset()
    t0 = time.perf_counter()
    ends = [t0]
    while len(ends) == 1 or ends[-1] - t0 < run.seconds:
        batch = gen.batch(len(ends) - 1)
        asked.update({r.uid: r for r in batch})
        out, stats = engine.serve(to_requests(batch))
        served.update(out)
        steps += stats["decode_steps"]
        ends.append(time.perf_counter())
    return {"window_s": ends[-1] - t0,
            "batch_s": np.diff(ends).tolist(),
            "served": served, "asked": asked, "decode_steps": steps,
            "itl_s": registry.values(ITL),
            "decode_step_s": registry.values(STEP)}


def check_sample(run: Run, w: Dict) -> List[int]:
    """Uids to compare: drawn from the seed, the longest served among
    them."""
    uids = sorted(w["served"])
    longest = max(uids, key=lambda u: len(w["served"][u]))
    rng = np.random.default_rng(TR.seed_words(run.seed, 3))
    return TR.sample_ids(uids, int(run.cell.traffic["check_requests"]), rng,
                         must=[longest])


def readings(run: Run, params, items, control: bool = False
             ) -> Dict[str, float]:
    """How far the served tokens' reference logits lie below the
    reference's best at their positions (``control``: the tokens the
    float8 reference puts first): the widest gap, the mean gap, and the
    share of tokens that are not the reference's first choice."""
    m = run.cell.config["model"]
    length = int(run.cell.traffic["max_seq"])
    gaps = np.concatenate([run.reference.served_gaps(
        params, m, prompt, served, length, control=control)
        for prompt, served in items])
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps)),
            "flip_share": float(np.mean(gaps > 0)),
            "gaps": gaps.tolist()}


def run(run: Run) -> Record:
    from repro.models import transformer as T
    from repro.obs import Telemetry, Tracer
    t = run.cell.traffic
    cfg = model_config(run.cell.config)
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
    engine = build_engine(run, cfg, params, telemetry)
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "engine")
    warm_up(engine, t, cfg.vocab_size)
    gen = TR.OfflineBatches(t, cfg.vocab_size, run.seed)
    setup_s = time.perf_counter() - run.t_start
    compiled = run.compiles.count
    run.log(f"set-up {setup_s:.3f} s ({compiled} compilations, "
            f"{run.compiles.seconds:.1f} s)")
    if tw:
        tw.arm()
    w = window(run, engine, gen, registry)
    if tw:
        tw.stop()
    peak = memory_peak_bytes(run.devices)
    run.log(f"window {w['window_s']:.3f} s, batches of "
            f"{', '.join(f'{b:.3f}' for b in w['batch_s'])} s, "
            f"{run.compiles.count - compiled} compilations inside it")

    uids = check_sample(run, w)
    items = [(w["asked"][u].prompt, w["served"][u]) for u in uids]
    del engine, params, telemetry
    gc.collect()
    t_check = time.perf_counter()
    got = readings(run, make_params(run), items)
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
                  run.cell.config["model"],
                  [(len(asked[u].prompt), len(served[u])) for u in served]),
              "model": run.cell.config["model"]},
        programs=TRACE_PROGRAMS,
        span_names=sorted(getattr(tracer, "names", ())), check_items=items)


def control(run: Run, record: Record) -> Dict[str, float]:
    """The control's reading on the requests the run's check compared."""
    return readings(run, make_params(run), record.check_items, control=True)


#: Device programs by the program's span that launches them.
TRACE_PROGRAMS = {"prefill.chunk": "prefill.chunk",
                  "decode.step": "decode.step"}
