"""Record the small TPU trace that ``test_chipbench_tracing.py`` reads.

    python3 benchmarks/chip/testdata/record_trace.py   # on one TPU chip

Six steps, each annotated ``step``: a jitted program ``small_step`` (a
matmul and the ``topk_gather`` kernel at a small shape), then 20 ms of
host work annotated ``host_wait`` with the device idle.  Writes
``benchmarks/chip/testdata/small.xplane.pb`` and prints what the test
expects of it.
"""

import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.tracing import profile_options  # noqa: E402
from repro.kernels import topk_gather_matmul  # noqa: E402

STEPS = 6
WAIT_S = 0.02


def small_step(x, vals, p_idx, s_off, packed, route):
    y = topk_gather_matmul(vals, p_idx, s_off, packed, route)
    return (x @ x).sum() + y.sum()


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    b, k, p, g, n = 2, 8, 32, 32, 4
    key = jax.random.PRNGKey(0)
    args = (jax.random.normal(key, (512, 512)),
            jax.random.normal(key, (b, k)),
            jax.random.randint(key, (b, k), 0, p),
            jax.random.randint(key, (b, k), 0, n),
            jax.random.normal(key, (p, g, n)),
            jnp.broadcast_to(jnp.arange(n, dtype=jnp.int8), (p, g, n)))
    step = jax.jit(small_step)
    step(*args).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=profile_options())
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("step"):
                step(*args).block_until_ready()
                with jax.profiler.TraceAnnotation("host_wait"):
                    time.sleep(WAIT_S)
        jax.profiler.stop_trace()
        (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True)
        shutil.copy(src, HERE / "small.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {STEPS} steps, {WAIT_S} s of host_wait each, "
          f"{os.path.getsize(HERE / 'small.xplane.pb')} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
