"""Discovery by name, the result line, and refusing to measure without a
chip."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

REPO = harness.REPO_ROOT

STUB_DRIVER = '''
from harness import Record, Window


class Run:
    def __init__(self, traffic):
        self.n = traffic["requests"]

    def install_control(self):
        self.n = 0

    def warm(self):
        pass

    def window(self, seconds):
        recs = [Record("op", 0.0, 0.1 * (i + 1), elements=10**6)
                for i in range(self.n)]
        return Window(recs, 0.0, 1.0)

    def release(self):
        pass

    def checks(self):
        return {"wrong": {"value": 0, "limit": 0}}


def build(config, traffic, seed, *, spans):
    return Run(traffic)
'''

STUB_METRIC = '''
def read(reading):
    return len(reading.records) * {scale}
'''


@pytest.fixture
def bench(tmp_path):
    """A benchmark tree in a temporary directory: one config, one mix, a
    stub driver and two metrics, found by name alone."""
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "drivers", "metrics"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "cfg-a.json").write_text(json.dumps({"x": 1}))
    (root / "traffic" / "mix-a.json").write_text(json.dumps(
        {"driver": "stub", "requests": 4}))
    (root / "drivers" / "stub.py").write_text(STUB_DRIVER)
    (root / "metrics" / "count.x").mkdir()     # not a metric file
    (root / "metrics" / "reqs.py").write_text(STUB_METRIC.format(scale=1))
    (root / "metrics" / "reqs_layer.py").write_text(
        STUB_METRIC.format(scale=10))
    spec = {
        "configs": [{"name": "cfg-a", "file": "bench/configs/cfg-a.json"}],
        "workloads": [{"name": "cell-a", "config": "cfg-a",
                       "traffic": "mix-a", "chips": 1}],
        "end_to_end": [
            {"name": "reqs", "unit": "1", "workloads": ["cell-a"]},
            {"name": "setup_s", "unit": "s"},
            {"name": "absent", "unit": "1", "workloads": ["cell-b"]}],
        "per_layer": [{"name": "reqs_layer", "unit": "1"}],
    }
    shutil.copy(harness.BENCH_ROOT / "metrics" / "setup_s.py",
                root / "metrics" / "setup_s.py")
    return root, spec


def test_a_cell_is_found_by_name(bench):
    root, spec = bench
    cell = harness.resolve(spec, "cell-a", trace=False,
                           spec_dir=root.parent, root=root)
    assert cell.config == {"x": 1}
    assert cell.traffic["driver"] == "stub"
    assert [m["name"] for m in cell.metrics] == ["reqs", "setup_s"]
    traced = harness.resolve(spec, "cell-a", trace=True,
                             spec_dir=root.parent, root=root)
    assert [m["name"] for m in traced.metrics] == ["reqs_layer"]
    with pytest.raises(harness.SetupError, match="unknown workload"):
        harness.resolve(spec, "cell-b", trace=False, spec_dir=root.parent,
                        root=root)


def test_a_missing_file_is_an_error(bench):
    root, spec = bench
    (root / "metrics" / "reqs.py").unlink()
    with pytest.raises(harness.SetupError, match="reqs.py"):
        harness.resolve(spec, "cell-a", trace=False, spec_dir=root.parent,
                        root=root)


def _line(bench, control=False):
    root, spec = bench
    cell = harness.resolve(spec, "cell-a", trace=False,
                           spec_dir=root.parent, root=root)
    return harness.run_cell(cell, 2**40 + 3, 1.0, False,
                            t_start=time.perf_counter(),
                            control=control, log=lambda s: None).line()


def test_the_result_line(bench):
    line = _line(bench)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert (line["attempted"], line["failed"]) == (4, 0)
    assert line["metrics"]["reqs"] == {"value": 4.0, "unit": "1"}
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"] == {"wrong": {"value": 0, "limit": 0}}
    json.dumps(line)


def test_a_window_with_no_answers_is_not_correct(bench):
    assert _line(bench, control=True)["correct"] is False


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_only_a_known_tpu_measures():
    peaks = harness.load_peaks()
    assert harness.check_device([_Dev("tpu", "TPU v5 lite")], 1,
                                peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError, match="no TPU"):
        harness.check_device([_Dev("cpu", "cpu")], 1, peaks)
    with pytest.raises(harness.SetupError, match="not in peaks"):
        harness.check_device([_Dev("tpu", "TPU v99")], 1, peaks)
    with pytest.raises(harness.SetupError, match="4 chips"):
        harness.check_device([_Dev("tpu", "TPU v5 lite")], 4, peaks)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "arith-add32-64K", "--seed", str(2**40 + 1), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    out = _run(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files, the system under test is missing: exit nonzero, no
    result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_ROOT, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "system under test is missing" in out.stderr


def test_the_spec_names_files_that_exist():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        for trace in (False, True):
            cell = harness.resolve(spec, w["name"], trace=trace)
            assert cell.metrics, (w["name"], trace)
    assert Path(REPO / spec["command"][1]).is_file()
