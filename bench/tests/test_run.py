"""``bench/run.py`` refuses to measure anywhere but on the chips a cell asks for."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_every_cell_finds_its_files_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in bench["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{mix['driver']}.py").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
        assert (ROOT / "bench" / "configs" / f"{w['config']}.json").exists()
    for m in bench["per_layer"]:
        stem = m["name"].split(".")[0]
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists() or \
            (ROOT / "bench" / "metrics" / f"{stem}.py").exists()
