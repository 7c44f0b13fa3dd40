"""Without a TPU the harness fails and prints no result: it never falls
back to the CPU. The same holds in a directory that holds only
BENCHMARK.json and the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import run


def launch(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen25-0.5b.chat",
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_result():
    p = launch(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_cell_fails():
    with pytest.raises(run.CellError):
        run.load_cell("no-such-cell")


def test_bare_checkout_fails_without_result(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = launch(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
