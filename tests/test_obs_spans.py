"""The paged serve loop's spans: every host step of an iteration sits in a
named span, the device programs are enqueued directly inside
``decode.step`` and ``prefill.chunk`` (a device trace labels a program
by the innermost span open at its enqueue), and an enabled tracer's
spans land in a ``jax.profiler`` trace under their bare names."""

import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.api import SparsityConfig
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.obs import Telemetry
from repro.obs.trace import Tracer
from repro.runtime.scheduler import Request

#: spans the paged loop adds around its host steps
NEW_SPANS = {"decode.inputs", "decode.fetch", "prefill.inputs",
             "prefill.fetch", "kv.grow", "kv.tables", "kv.prefix", "retire",
             "serve.setup", "serve.iteration", "serve.drain"}


def _paged_engine():
    cfg = get_config("smollm-360m").reduced(
        d_model=64, d_ff=256, vocab_size=128, n_heads=2, n_kv_heads=2,
        head_pad=0, compute_dtype="float32", param_dtype="float32",
        ffn_sparsity=SparsityConfig(n=4, k_frac=0.125))
    mesh = make_mesh((1, 1), ("data", "model"))
    return Engine(cfg, mesh, max_seq=32, n_slots=2, kv_layout="paged",
                  page_size=4, prefill_chunk=8,
                  telemetry=Telemetry.on(sparsity_every=0))


def _requests():
    """Two slots, three requests: a 12-token prompt prefills in two
    chunks, decode crosses page boundaries (chains grow), and the short
    budget retires at prefill and frees its slot for the third."""
    return [Request(uid=0, prompt=list(range(1, 13)), max_new_tokens=6),
            Request(uid=1, prompt=[5, 6, 7], max_new_tokens=1),
            Request(uid=2, prompt=[1, 2, 3, 4, 5], max_new_tokens=9)]


@pytest.fixture(scope="module")
def engine():
    eng = _paged_engine()
    eng.serve(_requests())  # compile every program once
    return eng


def test_every_host_step_sits_in_a_span(engine):
    tracer = engine.telemetry.tracer
    tracer.events.clear()
    t0 = time.perf_counter()
    reqs = _requests()
    _, stats = engine.serve(reqs)
    wall = time.perf_counter() - t0
    assert stats["prefill_chunks"] > len(reqs)  # a prompt took two
    assert stats["grown_pages"] > 0
    events = list(tracer.events)
    assert NEW_SPANS <= {e.name for e in events}
    parents = {e.name: e.parent for e in events}
    assert parents["decode.inputs"] == parents["decode.fetch"] == \
        "decode.step"
    assert parents["prefill.inputs"] == "prefill.chunk"
    # set-up, the iterations and the drain cover the call; inside each
    # iteration, its named steps cover the iteration
    top = sum(e.dur_s for e in events if e.depth == 0)
    assert top >= 0.9 * wall, (top, wall)
    loop = sum(e.dur_s for e in events if e.name == "serve.iteration")
    steps = sum(e.dur_s for e in events if e.parent == "serve.iteration")
    assert steps >= 0.9 * loop, (steps, loop)


def test_programs_are_enqueued_directly_in_their_spans(engine):
    tracer = engine.telemetry.tracer
    seen = {}

    def spy(name, fn):
        def call(*args):
            seen.setdefault(name, set()).add(tracer.current())
            return fn(*args)
        return call

    step, chunk = engine._step_paged, engine._chunk_jit
    engine._step_paged = spy("step", step)
    engine._chunk_jit = spy("chunk", chunk)
    try:
        engine.serve(_requests())
    finally:
        engine._step_paged, engine._chunk_jit = step, chunk
    assert seen == {"step": {"decode.step"}, "chunk": {"prefill.chunk"}}


def _trace_names(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        body()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in pd.planes for line in plane.lines
            for ev in line.events}


def test_enabled_spans_are_profiler_annotations(tmp_path):
    on, off = Tracer(enabled=True), Tracer(enabled=False)

    def body():
        with on.span("obs.outer", uid=3):
            with on.span("obs.inner"):
                jnp.ones(4).block_until_ready()
        with off.span("obs.disabled"):
            jnp.ones(4).block_until_ready()

    names = _trace_names(tmp_path, body)
    # bare names: attrs stay in the JSONL event, not in the annotation
    assert {"obs.outer", "obs.inner"} <= names
    assert not any(n.startswith("obs.disabled") for n in names)
    assert [e.name for e in on.events] == ["obs.inner", "obs.outer"]


def test_engine_trace_holds_its_spans_and_named_programs(tmp_path, engine):
    names = _trace_names(tmp_path, lambda: engine.serve(_requests()))
    assert NEW_SPANS | {"decode.step", "prefill.chunk", "sample",
                        "schedule.admit"} <= names
    assert {"PjitFunction(decode_step_paged)",
            "PjitFunction(prefill_chunk)"} <= names
    assert not any("lambda" in n for n in names)
