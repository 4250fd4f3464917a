"""Telemetry-layer tests: histogram math, tracer nesting + JSONL schema,
scheduler lifecycle records, realized-sparsity accumulation, and the
disabled-mode no-op guarantee (telemetry stages nothing extra — same
Select count, bit-identical jaxpr — on the un-probed decode path)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.api import SparsityConfig
from repro.core.instrument import count_selects
from repro.models import transformer as T
from repro.obs import Telemetry
from repro.obs import sparsity as obs_sparsity
from repro.obs.export import (JsonlWriter, latency_columns,
                              sparsity_columns, validate_event,
                              validate_jsonl)
from repro.obs.metrics import (NULL_REGISTRY, Histogram, Registry,
                               RollingHistogram)
from repro.obs.sparsity import DispatchStats, SparsityStats
from repro.obs.trace import Tracer
from repro.runtime.monitor import LossGuard, StepMonitor
from repro.runtime.scheduler import Request, Scheduler


# ---------------------------------------------------------------------------
# metrics: counters / gauges / histogram math
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = Registry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("g")
    assert g.value is None
    g.set(7)
    g.set(4)
    assert g.value == 4.0
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 4.0


def test_histogram_bucketing_and_percentiles():
    import threading
    h = Histogram("h", "s", threading.Lock(), edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 8.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 8.0
    assert s["sum"] == pytest.approx(13.0)
    # target=2 lands at the end of bucket (1, 2] -> exactly the edge
    assert h.percentile(50.0) == pytest.approx(2.0)
    # everything above the last edge is clamped by the observed max
    assert h.percentile(100.0) == pytest.approx(8.0)


def test_histogram_single_bucket_exact():
    import threading
    h = Histogram("h", "s", threading.Lock(), edges=(1.0, 2.0))
    for _ in range(5):
        h.observe(0.25)
    # all mass in one bucket, min == max -> percentiles are exact
    assert h.percentile(50.0) == pytest.approx(0.25)
    assert h.percentile(99.0) == pytest.approx(0.25)


def test_histogram_empty_and_bad_inputs():
    reg = Registry()
    h = reg.histogram("h")
    assert h.snapshot() == {"count": 0}
    assert h.percentile(50.0) is None
    with pytest.raises(ValueError):
        h.percentile(101.0)
    with pytest.raises(ValueError):
        reg.histogram("bad_edges", edges=(2.0, 1.0))


def test_registry_idempotent_and_kind_mismatch():
    reg = Registry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_reset_keeps_handles():
    reg = Registry()
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    assert c.value == 0.0
    assert h.snapshot() == {"count": 0}
    c.inc()  # the old handle still feeds the registry
    assert reg.snapshot()["counters"]["c"] == 1.0


def test_disabled_registry_hands_out_shared_null():
    tel = Telemetry.off()
    a = tel.registry.counter("a")
    b = tel.registry.histogram("b")
    assert a is b  # one shared null singleton
    a.inc()
    b.observe(1.0)  # no-ops, no raise
    assert tel.registry.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}
    assert NULL_REGISTRY.counter("z") is a


# ---------------------------------------------------------------------------
# rolling histogram: windowed percentiles with an injected clock
# ---------------------------------------------------------------------------

class _FakeClock:
    """Injectable monotonic clock the tests drive by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_rolling_histogram_window_expiry():
    import threading
    clk = _FakeClock()
    # window 6 s in 3 slices of 2 s
    h = RollingHistogram("r", "s", threading.Lock(), edges=(1.0, 2.0, 4.0),
                         window_s=6.0, n_slices=3, clock=clk)
    h.observe(0.5)          # slice epoch 0
    clk.t = 2.5
    h.observe(3.0)          # slice epoch 1
    assert h.count == 2     # both inside the window
    clk.t = 6.1             # epoch 3: slice 0's mass (epoch 0) expired
    assert h.count == 1
    assert h.snapshot()["min"] == pytest.approx(3.0)
    clk.t = 9.0             # past everything
    assert h.count == 0
    assert h.snapshot() == {"count": 0, "window_s": 6.0}
    assert h.percentile(95.0) is None


def test_rolling_histogram_merges_live_slices():
    import threading
    clk = _FakeClock()
    edges = (1.0, 2.0, 4.0)
    roll = RollingHistogram("r", "s", threading.Lock(), edges=edges,
                            window_s=6.0, n_slices=3, clock=clk)
    flat = Histogram("h", "s", threading.Lock(), edges=edges)
    # same observations spread across two live slices must merge to the
    # same percentile estimates the run-lifetime histogram computes
    for t, v in ((0.1, 0.5), (0.2, 1.5), (2.1, 3.0), (2.2, 8.0)):
        clk.t = t
        roll.observe(v)
        flat.observe(v)
    for q in (50.0, 95.0, 100.0):
        assert roll.percentile(q) == pytest.approx(flat.percentile(q))
    s = roll.snapshot()
    assert s["count"] == 4 and s["sum"] == pytest.approx(13.0)
    assert s["window_s"] == 6.0


def test_rolling_histogram_ring_reuses_slots():
    import threading
    clk = _FakeClock()
    h = RollingHistogram("r", "s", threading.Lock(), edges=(1.0,),
                         window_s=2.0, n_slices=2, clock=clk)
    # epoch 0 and epoch 2 share ring position 0: the stale epoch must be
    # zeroed when the slot is reused, not accumulated into
    h.observe(0.5)
    clk.t = 2.1             # epoch 2 evicts epoch 0 lazily on write
    h.observe(0.5)
    assert h.count == 1


def test_rolling_histogram_validation_and_reset():
    import threading
    lock = threading.Lock()
    with pytest.raises(ValueError):
        RollingHistogram("bad", "s", lock, edges=(2.0, 1.0))
    with pytest.raises(ValueError):
        RollingHistogram("bad", "s", lock, window_s=0.0)
    with pytest.raises(ValueError):
        RollingHistogram("bad", "s", lock, n_slices=0)
    h = RollingHistogram("r", "s", lock, edges=(1.0,), clock=_FakeClock())
    with pytest.raises(ValueError):
        h.percentile(-1.0)
    h.observe(0.5)
    h.reset()
    assert h.count == 0


def test_rolling_histogram_registry_accessor():
    reg = Registry()
    clk = _FakeClock()
    h = reg.rolling_histogram("w", window_s=10.0, n_slices=2, clock=clk)
    assert reg.rolling_histogram("w") is h  # idempotent per name
    with pytest.raises(TypeError):
        reg.histogram("w")  # kind mismatch with the plain histogram
    h.observe(0.01)
    snap = reg.snapshot()["histograms"]["w"]
    assert snap["count"] == 1 and snap["window_s"] == 10.0
    reg.reset()
    assert reg.snapshot()["histograms"]["w"] == {"count": 0,
                                                 "window_s": 10.0}
    # the disabled registry hands the shared null out here too
    assert NULL_REGISTRY.rolling_histogram("w").snapshot() is None


# ---------------------------------------------------------------------------
# tracer: nesting, totals, JSONL schema
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_totals():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner", uid=3):
            pass
        with tr.span("inner"):
            pass
    evs = list(tr.events)
    assert [e.name for e in evs] == ["inner", "inner", "outer"]
    assert evs[0].depth == 1 and evs[0].parent == "outer"
    assert evs[0].attrs == {"uid": 3}
    assert evs[2].depth == 0 and evs[2].parent is None
    tot = tr.totals()
    assert tot["inner"]["count"] == 2 and tot["outer"]["count"] == 1
    assert tot["outer"]["total_s"] >= tot["inner"]["total_s"]


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("a"):
        pass
    assert not tr.events and tr.totals() == {}
    # shared null span: no per-call allocation
    assert tr.span("a") is tr.span("b")


def test_tracer_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with JsonlWriter(path) as sink:
        tr = Tracer(enabled=True, sink=sink)
        with tr.span("outer"):
            with tr.span("inner", probed=True):
                pass
    n, errors = validate_jsonl(path)
    assert n == 2 and errors == []
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["name"] == "inner" and lines[0]["parent"] == "outer"


def test_validate_event_rejects_malformed():
    assert validate_event({"kind": "mystery"})
    assert validate_event({"kind": "span", "name": "x"})  # missing keys
    assert validate_event({"kind": "span", "name": "x", "ts": 0.0,
                           "dur_s": -1.0, "depth": 0, "parent": None})
    assert not validate_event({"kind": "span", "name": "x", "ts": 0.0,
                               "dur_s": 0.1, "depth": 0, "parent": None})


# ---------------------------------------------------------------------------
# scheduler lifecycle records (pure policy, no jax)
# ---------------------------------------------------------------------------

def test_scheduler_lifecycle_8_requests_4_slots(tmp_path):
    path = str(tmp_path / "req.jsonl")
    tel = Telemetry.on(jsonl_path=path)
    s = Scheduler(4, telemetry=tel)
    reqs = [Request(uid=i, prompt=[1, 2, 3], max_new_tokens=3)
            for i in range(8)]
    s.submit_many(reqs, now=0.0)
    t = 0.0
    while s.has_work:
        t += 0.010
        for slot in s.admit(now=t):
            s.record_token(slot, 11, now=t)  # first token from prefill
        s.retire_done(now=t)
        t += 0.005
        for slot in s.active_slots():
            s.record_token(slot, 12, now=t)
        s.retire_done(now=t)
    tel.close()
    assert sorted(s.finished) == list(range(8))
    snap = tel.registry.snapshot()
    assert snap["counters"]["serve.requests_submitted"] == 8
    assert snap["counters"]["serve.requests_finished"] == 8
    assert snap["counters"]["serve.tokens_generated"] == 24
    assert snap["histograms"]["serve.ttft_s"]["count"] == 8
    assert snap["histograms"]["serve.itl_s"]["count"] == 16  # 2 itl/req
    # the second wave (uids 4-7) waited for slots; the first did not
    first = [s.records[i].queue_wait_s for i in range(4)]
    second = [s.records[i].queue_wait_s for i in range(4, 8)]
    assert max(first) < min(second)
    for rec in s.records.values():
        assert rec.n_tokens == 3
        assert rec.t_enqueue <= rec.t_admit <= rec.t_first_token \
            <= rec.t_finish
        assert validate_event(rec.to_event()) == []
    n, errors = validate_jsonl(path)
    assert errors == [] and n == 8  # one request event per retirement


def test_request_record_status_marks_in_flight():
    """A snapshot taken mid-serve reports queued/in-flight requests with
    their partial timings instead of dropping them (ISSUE 9 bugfix)."""
    s = Scheduler(1)
    reqs = [Request(uid=i, prompt=[1, 2], max_new_tokens=2)
            for i in range(2)]
    s.submit_many(reqs, now=0.0)
    assert {r.status for r in s.records.values()} == {"queued"}
    s.admit(now=0.1)
    # uid 0 occupies the only slot; uid 1 still queued
    assert s.records[0].status == "in_flight"
    assert s.records[1].status == "queued"
    ev = s.records[0].to_event()
    assert ev["status"] == "in_flight" and ev["t_finish"] == 0.0
    assert validate_event(ev) == []
    for slot in s.active_slots():
        s.record_token(slot, 5, now=0.2)
        s.record_token(slot, 5, now=0.3)
    s.retire_done(now=0.3)
    assert s.records[0].status == "finished"
    assert s.records[0].to_event()["status"] == "finished"
    assert s.records[1].status == "queued"  # untouched by retirement


# ---------------------------------------------------------------------------
# realized-sparsity accumulation
# ---------------------------------------------------------------------------

def _support(idx_rows, u=2, b=3, k=4):
    """(U, B, 1, K) vals/idx with all winners non-zero."""
    idx = np.broadcast_to(np.asarray(idx_rows, np.int32), (u, b, 1, k))
    vals = np.ones((u, b, 1, k), np.float32)
    return vals, np.array(idx)


def test_sparsity_stats_overlap_and_reset():
    st = SparsityStats()
    meta = {"ffn": {"d": 16, "kind": "support"}}
    st.update({"ffn": _support([0, 1, 2, 3])}, meta, active_rows=[0, 1, 2])
    st.update({"ffn": _support([0, 1, 2, 3])}, meta, active_rows=[0, 1, 2])
    sm = st.summary()
    assert set(sm) == {"ffn.u0", "ffn.u1"}
    e = sm["ffn.u0"]
    assert e["realized_k_frac"] == pytest.approx(4 / 16)
    assert e["winner_overlap"] == pytest.approx(1.0)  # identical supports
    assert e["k"] == 4 and e["d"] == 16
    # a fresh request in row 0 must not bridge overlap across requests
    st.reset_row(0)
    st.update({"ffn": _support([4, 5, 6, 7])}, meta, active_rows=[0, 1, 2])
    e = st.summary()["ffn.u0"]
    # rows 1,2 contribute 0.0 overlap (disjoint), row 0 is suppressed:
    # mean over (3 prev samples of 1.0) + (2 new of 0.0) = 3/5
    assert e["winner_overlap"] == pytest.approx(3 / 5)


def test_sparsity_stats_nnz_path():
    st = SparsityStats()
    nnz = np.full((2, 3, 1), 5, np.int32)  # (U, B, S=1)
    st.update({"ffn": (nnz,)}, {"ffn": {"d": 20, "kind": "nnz"}},
              active_rows=[0, 2])
    sm = st.summary()
    assert sm["ffn.u0"]["realized_k_frac"] == pytest.approx(5 / 20)
    assert "winner_overlap" not in sm["ffn.u0"]  # no index form
    assert "k" not in sm["ffn.u0"]


def test_dispatch_stats_seal_and_flop_shares():
    ds = DispatchStats()
    ds.on_event({"path": "topk", "batch": 4, "d_in": 512, "d_out": 128,
                 "n": 4, "k": 64, "pallas": False, "interpret": False})
    ds.on_event({"path": "hadamard", "batch": 4, "d_in": 128, "d_out": 512,
                 "n": 4, "pallas": False, "interpret": False})
    ds.on_event({"path": "topk", "batch": 4, "d_in": 512, "d_out": 128,
                 "n": 4, "k": 64, "pallas": True, "interpret": True})
    ds.seal()
    ds.on_event({"path": "dense", "batch": 4, "d_in": 8, "d_out": 8})
    # sealed: the dense site came after the seal and is not counted
    assert ds.summary() == {"topk[jnp]": 1, "hadamard[jnp]": 1,
                            "topk[pallas-interpret]": 1}


# ---------------------------------------------------------------------------
# snapshot -> bench columns
# ---------------------------------------------------------------------------

def test_latency_and_sparsity_columns():
    reg = Registry()
    reg.histogram("serve.ttft_s").observe(0.1)
    snap = {
        "metrics": reg.snapshot(),
        "sparsity": {
            "layers": {"a": {"realized_k_frac": 0.1, "winner_overlap": 0.5},
                       "b": {"realized_k_frac": 0.3}},
            "paths": {"topk[jnp]": 2},
        },
    }
    lat = latency_columns(snap)
    assert lat["ttft_p50_ms"] == pytest.approx(100.0)
    assert "itl_p50_ms" not in lat  # absent histogram -> no columns
    sp = sparsity_columns(snap)
    assert sp["realized_k_frac"] == pytest.approx(0.2)
    assert sp["winner_overlap"] == pytest.approx(0.5)
    assert set(sp) == {"realized_k_frac", "winner_overlap"}


# ---------------------------------------------------------------------------
# monitor rides the registry
# ---------------------------------------------------------------------------

def test_step_monitor_feeds_registry():
    reg = Registry()
    m = StepMonitor(straggler_factor=2.0, warmup_steps=1, trip_after=2,
                    registry=reg)
    for i, dur in enumerate((0.1, 0.1, 1.0, 1.0)):
        m.record(i, dur)
    s = m.summary()
    assert s["steps"] == 4 and s["flagged"] == 2
    assert s["max_s"] == pytest.approx(1.0)
    assert s["ema_s"] == pytest.approx(m.ema)
    assert reg.snapshot()["histograms"]["monitor.step_s"]["count"] == 4
    assert m.should_reshard


def test_loss_guard_counts_rollbacks():
    reg = Registry()
    g = LossGuard(spike_factor=2.0, registry=reg)
    assert g.check(1.0)
    assert not g.check(float("nan"))
    assert not g.check(10.0)
    assert reg.snapshot()["counters"]["monitor.loss_rollbacks"] == 2


# ---------------------------------------------------------------------------
# disabled-mode no-op: telemetry stages nothing on the decode path
# ---------------------------------------------------------------------------

def _sparse_cfg():
    return get_config("smollm-360m").reduced(
        d_model=64, d_ff=256, vocab_size=128, n_heads=2, n_kv_heads=2,
        head_pad=0, compute_dtype="float32", param_dtype="float32",
        ffn_sparsity=SparsityConfig(n=4, k_frac=0.125))


def test_probe_adds_no_select_and_off_path_is_unchanged():
    cfg = _sparse_cfg()
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    cache, _ = T.init_cache(cfg, 2, 16)
    toks = jnp.ones((2, 1), jnp.int32)

    def plain(p, c, t):
        return T.serve_step(p, c, {"tokens": t}, 4, cfg)

    with count_selects() as c_off:
        jaxpr_before = str(jax.make_jaxpr(plain)(params, cache, toks))

    def probed(p, c, t):
        with obs_sparsity.capture_supports() as cap:
            logits, new_cache = T.serve_step(p, c, {"tokens": t}, 4, cfg)
        return logits, new_cache, cap.take_arrays()

    with count_selects() as c_on:
        probed_jaxpr = jax.make_jaxpr(probed)(params, cache, toks)
    # the probe returns the winner supports as extra outputs...
    n_plain_out = len(jax.make_jaxpr(plain)(
        params, cache, toks).jaxpr.outvars)
    assert len(probed_jaxpr.jaxpr.outvars) > n_plain_out
    # ...but stages NO extra Select: the supports are the ones the k-WTA
    # layers already computed (one top_k per sparse layer, unchanged)
    assert c_on.top_k == c_off.top_k > 0
    # and once the capture closes, the un-probed path re-traces
    # bit-identically: no state leaks from the probed trace
    jaxpr_after = str(jax.make_jaxpr(plain)(params, cache, toks))
    assert jaxpr_after == jaxpr_before
    assert obs_sparsity.drain_pending() == ()  # inactive capture: no-op


def test_engine_off_vs_on_same_tokens():
    # telemetry must never change what the engine generates
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import Engine
    cfg = _sparse_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    reqs = [Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=4)
            for i in range(3)]
    out_off, _ = Engine(cfg, mesh, max_seq=16, n_slots=2).serve(reqs)
    tel = Telemetry.on(sparsity_every=1)
    eng = Engine(cfg, mesh, max_seq=16, n_slots=2, telemetry=tel)
    out_on, _ = eng.serve(reqs)
    assert out_off == out_on
    snap = eng.metrics_snapshot()
    assert snap["sparsity"]["layers"]  # probed run measured something
    assert snap["metrics"]["histograms"]["serve.ttft_s"]["count"] == 3
