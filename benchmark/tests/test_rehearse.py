"""run.py end to end on the CPU at the configurations' tiny `rehearse` size
(Pallas interpreted), every cell, both --trace settings; the four-chip path on
four virtual devices; and the refusal to measure without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_py(*argv, devices=1, manifest=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, str(BENCH / "run.py"), *argv]
    if manifest:
        cmd += ["--manifest", str(manifest)]
    return subprocess.run(cmd, cwd=REPO, env=env, text=True,
                          capture_output=True, timeout=900)


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line


def metric_names(manifest, group, cell):
    return {m["name"] for m in manifest[group]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_cell(cell, trace, manifest):
    line = result_of(run_py("--workload", cell, "--seed", str(2**31 + 7),
                            "--seconds", "1", "--trace", str(trace),
                            "--rehearse"))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    if trace:
        assert set(dev) == DEVICE_KEYS | {"busy_s", "window_s"}
        # a reader that finds nothing (no TPU kernel in a CPU trace) is
        # left out; the rest are the cell's per-layer metrics
        assert set(line["metrics"]) <= metric_names(manifest, "per_layer",
                                                    cell)
        assert len(line["metrics"]) >= 3
    else:
        assert set(dev) == DEVICE_KEYS
        assert set(line["metrics"]) == metric_names(manifest, "end_to_end",
                                                    cell)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert m["value"] is None, "a CPU run may print no device number"


def test_four_chip_path_on_virtual_devices(tmp_path, manifest):
    """The data-parallel mix (tree_learner=data over the cell's chips) as a
    four-chip cell of a scratch manifest, on four virtual CPU devices."""
    m = json.loads(json.dumps(manifest))
    cell = {"name": "dp4_rehearsal", "config": m["workloads"][0]["config"],
            "traffic": "train_data_parallel", "chips": 4, "why": "test"}
    m["workloads"].append(cell)
    for metric in (*m["end_to_end"], *m["per_layer"]):
        if m["workloads"][0]["name"] in metric.get("workloads", []):
            metric["workloads"].append(cell["name"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    argv = ("--workload", cell["name"], "--seed", "11", "--seconds", "1",
            "--trace", "0", "--rehearse")
    line = result_of(run_py(*argv, devices=4, manifest=path))
    assert line["correct"] is True and line["device"]["count"] == 4
    assert "tree_learner=data" in (BENCH / "traffic"
                                   / "train_data_parallel.json").read_text()
    # fewer devices than the cell asks for: refused, no result line
    proc = run_py(*argv, devices=2, manifest=path)
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")


def test_without_a_tpu_there_is_no_result():
    proc = run_py("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), "a result line without a TPU"
    assert "needs a TPU" in proc.stderr
