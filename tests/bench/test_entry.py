"""bench/run.py measures nothing off the chip: on a CPU it exits
non-zero and prints no result, as it does without the program."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = SPEC["workloads"][0]["name"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = SPEC["command"] + ["--workload", CELL, "--seed", str(2**31 + 7),
                             "--seconds", "1", "--trace", "0"]
    return subprocess.run([sys.executable] + cmd[1:], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return not last.startswith("{")


def test_exits_nonzero_on_cpu():
    r = _run(ROOT)
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]
    assert "no TPU" in r.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and _no_result(r.stdout)
