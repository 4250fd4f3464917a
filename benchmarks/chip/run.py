"""Run one cell of ``BENCHMARK.json`` once on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and last ``checks``, each number
compared with its limit.  Exits non-zero with no result where JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE / "drivers"), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import device, harness, spec
    harness.give_cache_dir()
    cell = spec.load_cell(args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            devices, T_START)
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
