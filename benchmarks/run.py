"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Modules:
  bench_gsc           — Tables 2/3/4 (end-to-end GSC throughput + energy)
  bench_sparse_matmul — Figure 6 (structured-sparsity matmul paths)
  bench_resources     — Figures 15-18 (conv-block resource scaling)
  bench_kwta          — Figures 19-20 (k-WTA cost scaling)

Usage: PYTHONPATH=src python -m benchmarks.run [--only gsc,...]
                                               [--json OUT]

``--json OUT`` additionally writes every collected row to a JSON file
(``{"schema_version", "rows": [{"name", "us_per_call", ...derived}],
"benches": [...]}``).  These are CPU (and Pallas interpret-mode) timings
at toy widths; the system's speed is measured on the chip
(``benchmarks/chip/``).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback


def _report(name: str, us_per_call: float, derived=None) -> None:
    d = json.dumps(derived or {}, sort_keys=True).replace(",", ";")
    print(f"{name},{us_per_call:.2f},{d}", flush=True)


class _Collector:
    """Wraps the CSV reporter; also accumulates rows for ``--json``."""

    def __init__(self):
        self.rows = []

    def __call__(self, name: str, us_per_call: float, derived=None) -> None:
        _report(name, us_per_call, derived)
        row = {"name": name, "us_per_call": round(float(us_per_call), 2)}
        row.update(derived or {})
        self.rows.append(row)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: gsc,sparse_matmul,"
                         "resources,kwta")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write collected rows to OUT as JSON")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import bench_gsc, bench_kwta, bench_resources, \
        bench_sparse_matmul
    mods = {"gsc": bench_gsc, "sparse_matmul": bench_sparse_matmul,
            "resources": bench_resources, "kwta": bench_kwta}
    sel = (args.only.split(",") if args.only else list(mods))
    report = _Collector()
    print("name,us_per_call,derived")
    failed = []
    for name in sel:
        try:
            mods[name].run(report)
        except Exception:  # noqa: BLE001 — report and continue
            failed.append(name)
            traceback.print_exc()
    if args.json:
        from repro.obs.export import SCHEMA_VERSION
        with open(args.json, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION,
                       "benches": [n for n in sel if n not in failed],
                       "failed": failed, "rows": report.rows}, f, indent=2)
        print(f"wrote {len(report.rows)} rows to {args.json}",
              file=sys.stderr)
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
