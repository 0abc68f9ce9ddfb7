"""What ``run.py`` refuses: an unknown cell, a per-layer metric with no
reader, a device missing from the table of peaks, and a machine with no
TPU; none of them prints a result."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _run(args, cwd=BENCH.parent):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_no_tpu_fails_without_a_result(cell):
    proc = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_unknown_cell_refused():
    proc = _run(["--workload", "plan.nowhere", "--seed", "1", "--seconds", "1"])
    _no_result(proc)
    assert "unknown workload" in proc.stderr


def test_metric_without_reader_refused(bench_dir):
    spec = json.loads((bench_dir / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "made_up_ms", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "device",
                              "moves": "plan_ms"})
    f = bench_dir / "BENCHMARK.alt.json"
    f.write_text(json.dumps(spec))
    with pytest.raises(harness.BenchError, match="made_up_ms"):
        harness.load_cell("plan.tiny", bench_dir / "bench", f)


def test_device_kind_missing_from_peaks():
    class Dev:
        device_kind = "TPU v99"
    cell = harness.Cell(name="x", chips=1, config={}, traffic={}, end_to_end=[],
                        per_layer=[], peaks=json.loads((BENCH / "peaks.json").read_text()))
    with pytest.raises(harness.BenchError, match="TPU v99"):
        harness.peak_of(cell, Dev())
    Dev.device_kind = "TPU v5 lite"
    assert harness.peak_of(cell, Dev())["bf16_flops_per_s"] == 197e12


def test_bare_directory_fails(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directory cannot run: the program is not there."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "serve.stablelm.chat", "--seed", "1", "--seconds", "1"],
                          cwd=tmp_path, env=env, capture_output=True, text=True,
                          timeout=300)
    _no_result(proc)


def test_sub_seed_takes_seeds_past_32_bits():
    a = harness.sub_seed(2**31 + 12345, "x")
    assert 0 <= a < 2**31
    assert a == harness.sub_seed(2**31 + 12345, "x")
    assert a != harness.sub_seed(2**31 + 12346, "x")
    assert a != harness.sub_seed(2**31 + 12345, "y")


def test_percentile_counts_missing_as_slowest():
    assert harness.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert harness.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, float("inf")], 95) == float("inf")
