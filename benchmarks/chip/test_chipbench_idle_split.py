"""The device's idle share split by the host span each gap fell in: the
three readers sum to the whole idle share."""

import types

import pytest

from chipbench import idle_split, spec
from chipbench import tracing as TC
from test_chipbench_tracing import fake_profile

READERS = ["idle_share.serve.no_span", "idle_share.serve.program_io",
           "idle_share.serve.host_loop"]


def ctx_for(span_names):
    reduced = TC.reduce_profile(fake_profile(),
                                {"decode.step": "decode.step"}, span_names)
    return types.SimpleNamespace(
        trace=reduced, record=types.SimpleNamespace(span_names=span_names))


@pytest.mark.parametrize("span_names, expect", [
    # gaps: [0,100) and [1500,1600) in decode.step, [350,1050) in sample
    (["decode.step", "sample", "prefill.chunk"],
     {"program_io": 200, "host_loop": 700, "no_span": 0}),
    # sample is not a program span: its gap is the host in none
    (["decode.step", "prefill.chunk"],
     {"program_io": 200, "host_loop": 0, "no_span": 700}),
])
def test_readers_sum_to_the_idle_share(span_names, expect):
    ctx = ctx_for(span_names)
    got = {name: spec.metric_reader(name).read(ctx) for name in READERS}
    assert sum(got.values()) == pytest.approx(ctx.trace.idle_pct())
    for name, value in got.items():
        cls = name.rsplit(".", 1)[1]
        assert value == pytest.approx(100 * expect[cls] / 1600)


def test_classes():
    names = {"decode.fetch", "prefill.inputs", "kv.cow", "kv.grow", "sample",
             "serve.setup", "prefill"}
    assert idle_split.classify("decode.fetch", names) == "program_io"
    assert idle_split.classify("prefill.inputs", names) == "program_io"
    assert idle_split.classify("kv.cow", names) == "program_io"
    # the contiguous loop's fused prefill is the loop's own span
    assert idle_split.classify("prefill", names) == "host_loop"
    assert idle_split.classify("kv.grow", names) == "host_loop"
    assert idle_split.classify("serve.setup", names) == "host_loop"
    assert idle_split.classify("no annotated span", names) == "no_span"


def test_untraced_run_reads_nothing():
    ctx = types.SimpleNamespace(trace=None,
                                record=types.SimpleNamespace(span_names=[]))
    assert all(spec.metric_reader(n).read(ctx) is None for n in READERS)
