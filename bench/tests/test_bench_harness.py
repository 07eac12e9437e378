import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness, run

ROOT = harness.ROOT


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_refuses_a_non_tpu_platform(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.device_info(ROOT, 1, rehearsal=False)
    rc = run.main(["--workload", "wiki128-kl.stream", "--seed", "1",
                   "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_refuses_an_unknown_device_kind(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v99")])
    with pytest.raises(harness.NoDevice, match="no peaks"):
        harness.device_info(ROOT, 1, rehearsal=False)
    assert run.main(["--workload", "wiki128-kl.stream", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_refuses_too_few_chips(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v5 lite")])
    assert harness.device_info(ROOT, 1, False)["peaks"]["hbm_bytes_per_s"]
    with pytest.raises(harness.NoDevice, match="needs 4 chips"):
        harness.device_info(ROOT, 4, False)


def test_no_result_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for extra in ([], ["--rehearsal", "300"]):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "wiki128-kl.stream",
             "--seed", "1", "--seconds", "1", *extra],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == ""


def test_every_cell_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        files = harness.load_cell(ROOT, w["name"])
        assert files["end_to_end"] and files["per_layer"]
        for m in files["per_layer"]:
            assert callable(harness.load_reader(ROOT, m["name"]))
