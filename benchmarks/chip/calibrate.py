"""Readings that a cell's limit is set from: the program's number on
many seeds and the control's on some, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 5 [--out file]

For every seed the cell runs as ``run.py`` does (set-up, a window of
``--seconds``, the check); for the control seeds the control is then
read on the same requests or calls: the plain reference computed one
step below the configuration's precision, put in the program's place.
Prints one JSON line per seed.
"""

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chipbench import device, harness, spec
    harness.give_cache_dir()
    cell = spec.load_cell(args.workload)
    devices = device.require_tpu(cell.chips)
    harness.use_compile_cache()
    reference = spec.reference_module(cell.config)
    driver = spec.driver_module(cell.config)
    compiles = harness.CompileClock()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, reference=reference, seed=seed,
                          seconds=args.seconds, devices=devices,
                          t_start=time.perf_counter(), compiles=compiles)
        rec = driver.run(run)
        row = {"workload": cell.name, "seed": seed,
               "program": rec.data["readings"],
               "end_to_end": rec.end_to_end, "attempted": rec.attempted,
               "failed": rec.failed}
        if seed in controls:
            row["control"] = driver.control(run, rec)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
