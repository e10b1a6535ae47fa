"""The entry point refuses to measure anything but the chip."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import registry


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "fl-resnet34-fwq", "--seed", str(2 ** 31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except ValueError:
            continue
    return False


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(registry.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_the_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    for path in registry.benchmark()["paths"]:
        shutil.copytree(registry.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
