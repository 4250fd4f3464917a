"""The trace reduction: busy and idle time, gaps put down to the host's
spans, device time per program and per kernel."""

import types

import pytest

from chipbench import tracing as TC


def ev(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 duration_ns=end - start,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v)
                          for k, v in lines.items()])


def fake_profile():
    host = plane("/host:CPU", python=[
        ev("decode.step", 0, 400), ev("sample", 400, 1000),
        ev("prefill.chunk", 1000, 1100), ev("decode.step", 1100, 1600),
        ev("noise", 1600, 5000)],
        runtime=[ev("launch", 10, 20, run_id=7),
                 ev("launch", 1005, 1010, run_id=9),
                 ev("launch", 1010, 1020, run_id=8)])
    dev = plane("/device:TPU:0", **{
        "XLA Ops": [ev("while.1", 100, 350), ev("fusion.1", 100, 300),
                    ev("topk_gather_kernel", 300, 350),
                    ev("fusion.2", 1050, 1500)],
        "XLA Modules": [ev("jit__lambda", 100, 350, run_id=7),
                        ev("jit_convert_element_type", 1040, 1045,
                           run_id=9),
                        ev("jit__lambda", 1050, 1500, run_id=8)]})
    return types.SimpleNamespace(planes=[host, dev])


def test_reduce_profile_by_hand():
    r = TC.reduce_profile(fake_profile(),
                          {"decode.step": "decode.step",
                           "prefill.chunk": "prefill.chunk"},
                          ["decode.step", "sample", "prefill.chunk"])
    assert r.window_s == pytest.approx(1600e-9)
    assert r.busy_s == pytest.approx((350 - 100 + 1500 - 1050) * 1e-9)
    assert r.idle_pct() == pytest.approx(100 * (1 - 700 / 1600))
    # gaps: [0,100) in decode.step, [350,1050) mid 700 in sample,
    # [1500,1600) in decode.step
    assert r.idle[0] == ("sample", pytest.approx(700e-9))
    assert sorted(n for n, _ in r.idle) == ["decode.step", "decode.step",
                                           "sample"]
    assert r.programs["decode.step"] == {
        "jit__lambda": (1, pytest.approx(250e-9))}
    # the scalar's conversion enqueued in the same span is left out
    assert r.program_ms("prefill.chunk") == pytest.approx(450e-9 * 1e3)
    assert len(r.programs["prefill.chunk"]) == 2
    assert r.kernel("topk_gather") == (1, pytest.approx(50e-9))
    top = r.breakdown()["device_ops"]
    # the loop op holds the others and is not counted again
    assert top[0][0] == "fusion.2" and len(top) == 3
    assert "while.1" not in r.ops


def test_module_named_by_part_of_its_name():
    r = TC.reduce_profile(fake_profile(), {"jit__lambda": "step"},
                          ["decode.step"])
    assert r.programs["step"]["jit__lambda"][0] == 2


def test_union_and_gaps():
    assert TC.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert TC.gaps([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]


def test_trace_without_device_ops_is_refused():
    prof = fake_profile()
    prof.planes = prof.planes[:1]
    with pytest.raises(ValueError):
        TC.reduce_profile(prof, {}, ["decode.step"])


def test_recorded_tpu_trace():
    """``testdata/small.xplane.pb``, recorded on one TPU v5 lite by
    ``testdata/record_trace.py``: six steps of ``small_step`` (a matmul
    and the ``topk_gather`` kernel), each followed by 20 ms of host work
    in a ``host_wait`` span."""
    from chipbench import spec
    r = TC.reduce_dir(str(spec.BENCH_DIR / "testdata"),
                      {"step": "small_step"}, ["step", "host_wait"])
    (runs, _), = r.programs["small_step"].values()
    assert runs == 6
    assert 0 < r.program_ms("small_step") < 20
    calls, seconds = r.kernel("topk_gather")
    assert calls == 6 and seconds > 0
    assert 6 * 0.02 < r.window_s < 6 * 0.05
    assert 0 < r.busy_s < 0.2 * r.window_s
    assert [n for n, _ in r.idle[:6]] == ["host_wait"] * 6
    assert all(s > 0.019 for _, s in r.idle[:6])
    assert r.breakdown()["device_ops"][0][1] > 0
