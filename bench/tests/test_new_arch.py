"""A configuration of another architecture is new files and new entries
of BENCHMARK.json only: in a copy of the benchmark, an architecture under
a new name (copies of the Qwen2 modules), its configuration at the tiny
size, a traffic mix, a limit, a kernel module with its roofline reader,
and the cell and metric entries. No file of the copy changes, and the new
cell runs whole (the chip check skipped) and reads correct."""
import json
import os
import shutil
import subprocess
import sys

from bench import run
from bench.tests.test_faults import MIX

CELL = "tiny-copy.chat"
RUN = """
import argparse, json, sys
sys.path.insert(0, ".")
from bench import plugins, run, trace
args = argparse.Namespace(workload="%s", seed=2 ** 31 + 21, seconds=4.0,
                          trace=0)
out = run.run_cell(args, require_tpu=False)
print(json.dumps({"correct": out["correct"], "attempted": out["attempted"],
                  "metrics": sorted(out["metrics"]),
                  "kernels": sorted(trace.KERNELS),
                  "arch": sys.modules["bench.archs.qwen2copy"].__file__}))
""" % CELL


def files(root):
    return {str(p.relative_to(root)): p.read_bytes() for p in root.rglob("*")
            if p.is_file()}


def test_a_new_architecture_is_new_files_only(tiny, tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = files(tmp_path)
    b = tmp_path / "bench"

    shutil.copy(b / "archs" / "qwen2.py", b / "archs" / "qwen2copy.py")
    shutil.copy(b / "reference" / "qwen2.py", b / "reference" / "qwen2copy.py")
    cfg = dict(tiny, name="tiny-copy", reference="qwen2copy")
    (b / "configs" / "tiny-copy.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-chat.json").write_text(json.dumps(MIX))
    (b / "limits" / f"{CELL}.json").write_text('{"max_logit_gap": 0.05}')
    (b / "kernels" / "awq_matmul_copy.py").write_text(
        "from bench.kernels.awq_matmul import work  # noqa: F401\n")
    shutil.copy(b / "layer_metrics" / "awq_matmul_roofline.py",
                b / "layer_metrics" / "awq_matmul_copy_roofline.py")
    spec = json.loads(before["BENCHMARK.json"])
    spec["configs"].append({
        "name": "tiny-copy", "source": "a copy of qwen25-0.5b-awq",
        "file": "bench/configs/tiny-copy.json", "reduced": [],
        "why": "a test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-copy", "traffic": "tiny-chat",
        "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": "awq_matmul_copy_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "itl_p95_ms", "workloads": [CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))

    (tmp_path / "src").symlink_to(run.ROOT / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", RUN], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["attempted"] > 0
    assert out["metrics"] == ["itl_p95_ms", "setup_s"]
    assert "awq_matmul_copy" in out["kernels"]
    assert out["arch"].startswith(str(tmp_path))

    after = files(tmp_path)
    for path, data in before.items():
        if path != "BENCHMARK.json":
            assert after[path] == data, path
    new = json.loads(after["BENCHMARK.json"])
    old = json.loads(before["BENCHMARK.json"])
    for key, value in old.items():
        n = len(value) if isinstance(value, list) else None
        assert (new[key][:n] if n is not None else new[key]) == value, key
