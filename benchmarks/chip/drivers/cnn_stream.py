"""Driver: a classifier's jitted forward over a stream of input batches.

Set-up makes the weights (the reference file's ``make_weights``) and a
pool of input batches from the seed.  Each call of the window sends one
batch of the pool from the host and fetches its logits, until
``--seconds`` have passed.  A sample of the calls, drawn from the seed,
keeps its logits; after the window every utterance of every sampled call
is compared with the reference.
"""

from __future__ import annotations

import gc
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench import counts as CN
from chipbench import traffic as TR
from chipbench.device import memory_peak_bytes
from chipbench.harness import Check, Record, Run
from chipbench.probes import TraceWindow
from chipbench.tracing import profile_options
from chipbench.weights import check_layout, make_params


def model_config(config: Dict):
    from repro.models.gsc_cnn import GSCConfig
    return GSCConfig(**config["model"])


def make_forward(cfg):
    import jax
    from repro.models import gsc_cnn as G

    def gsc_forward(params, x):
        return G.forward(params, x, cfg)

    return jax.jit(gsc_forward)


def readings(run: Run, params, kept: List[Tuple[int, np.ndarray]],
             pool: TR.InputPool, control: bool = False) -> Dict[str, float]:
    """Gaps between the served logits and the reference's over every
    utterance of the sampled calls, in units of the root mean square of
    that batch's reference logits: the largest and the mean, and the
    share of utterances whose first class differs.  ``control`` reads
    the bfloat16 reference in the program's place."""
    m_json = json.dumps(run.cell.config["model"], sort_keys=True)
    ref = {}
    for idx in sorted({i for i, _ in kept}):
        ref[idx] = np.asarray(run.reference.batch_logits(
            params, pool.batches[idx], m_json))
    errs, flips = [], []
    for idx, out in kept:
        r = ref[idx]
        if control:
            out = np.asarray(run.reference.batch_logits(
                params, pool.batches[idx], m_json, low=True))
        rms = float(np.sqrt(np.mean(r * r)))
        errs.append(np.max(np.abs(out - r), axis=-1) / rms)
        flips.append(np.argmax(out, -1) != np.argmax(r, -1))
    errs, flips = np.concatenate(errs), np.concatenate(flips)
    return {"max_logit_err": float(np.max(errs)),
            "mean_logit_err": float(np.mean(errs)),
            "argmax_flip_share": float(np.mean(flips))}


def run(run: Run) -> Record:
    import jax
    from repro.models import gsc_cnn as G
    t = run.cell.traffic
    cfg = model_config(run.cell.config)
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "chip")
    params = make_params(run)
    check_layout(params, lambda k: G.init_model(k, cfg)[0])
    forward = make_forward(cfg)
    pool = TR.InputPool(t, run.seed)
    run.log(f"set-up: {time.perf_counter() - run.t_start:.3f} s to the "
            "weights and inputs")
    batch = int(t["batch"])
    np.asarray(forward(params, jax.device_put(pool.batches[0])))
    setup_s = time.perf_counter() - run.t_start
    compiled = run.compiles.count
    run.log(f"set-up {setup_s:.3f} s ({compiled} compilations, "
            f"{run.compiles.seconds:.1f} s)")

    tw = None
    if run.trace_dir:
        tw = TraceWindow(run.trace_dir, float(t["trace_start_s"]),
                         float(t["trace_seconds"]), profile_options())
        tw.arm()
    rng = np.random.default_rng(TR.seed_words(run.seed, 4))
    kept, offer = TR.reservoir(rng, int(t["check_calls"]))
    calls = 0
    t0 = time.perf_counter()
    while calls == 0 or time.perf_counter() - t0 < run.seconds:
        idx = pool.index(calls)
        if tw:
            tw.tick()
            with jax.profiler.TraceAnnotation("gsc.call"):
                out = np.asarray(forward(params,
                                         jax.device_put(pool.batches[idx])))
        else:
            out = np.asarray(forward(params,
                                     jax.device_put(pool.batches[idx])))
        offer(calls, (idx, out))
        calls += 1
    window_s = time.perf_counter() - t0
    if tw:
        tw.stop()
    peak = memory_peak_bytes(run.devices)
    run.log(f"window {window_s:.3f} s, {calls} calls, "
            f"{run.compiles.count - compiled} compilations inside it")

    del params, forward
    gc.collect()
    t_check = time.perf_counter()
    got = readings(run, make_params(run), kept, pool)
    run.log(f"check: {len(kept)} calls, {len(kept) * batch} utterances, "
            f"{time.perf_counter() - t_check:.1f} s")
    return Record(
        end_to_end={"words_s": calls * batch / window_s, "setup_s": setup_s},
        attempted=calls * batch, failed=0,
        checks=[Check(k, got[k], float(v)) for k, v in t["limits"].items()],
        memory_peak_bytes=peak, window_s=window_s,
        data={"readings": got,
              "words": calls * batch, "calls": calls,
              "utterance_flops": CN.gsc_utterance_flops(
                  run.cell.config["model"])},
        programs={"gsc_forward": "gsc.forward"}, span_names=["gsc.call"],
        check_items=(kept, pool))


def control(run: Run, record: Record) -> Dict[str, float]:
    """The control's reading on the calls the run's check compared."""
    kept, pool = record.check_items
    return readings(run, make_params(run), kept, pool, control=True)
