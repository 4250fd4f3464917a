"""What the harness hands the program: a registry that keeps every
observation, and a tracer that hands each span opening to the trace
window."""

from chipbench.probes import ProfiledTracer, RecordingRegistry, TraceWindow


def test_recording_registry_keeps_every_observation():
    reg = RecordingRegistry()
    h = reg.histogram("serve.itl_s")
    for v in (0.010, 0.012, 0.5):
        h.observe(v)
    assert reg.values("serve.itl_s") == [0.010, 0.012, 0.5]
    assert h.count == 3            # the buckets still fill as before
    reg.reset()
    assert reg.values("serve.itl_s") == []


def test_profiled_tracer_ticks_and_records_names():
    ticks = []
    tracer = ProfiledTracer(lambda: ticks.append(1))
    with tracer.span("decode.step"):
        with tracer.span("sample"):
            pass
    assert len(ticks) == 2
    assert tracer.names == {"decode.step", "sample"}
    assert tracer.totals()["decode.step"]["count"] == 1


def test_trace_window_waits_until_armed():
    tw = TraceWindow("unused", start_s=1e9, length_s=1.0)
    tw.tick()
    assert tw.state == "idle"
    tw.arm()
    tw.tick()
    assert tw.state == "armed"      # too early to start
    tw.stop()
    assert tw.state == "armed"      # nothing was traced, nothing stops
