"""The command refuses to measure without a TPU, and without the program
beside it: it exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import spec

RUN = spec.BENCH_DIR / "run.py"


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", "smollm-360m.decode-s4",
         "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj)


def test_no_tpu_no_result():
    proc = _run(spec.ROOT, RUN)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path, tmp_path / RUN.relative_to(spec.ROOT)))
