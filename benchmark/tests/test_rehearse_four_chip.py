"""Every four-chip cell of the real manifest, rehearsed on four virtual CPU
devices at its configuration's tiny `rehearse` size, both --trace settings:
test_rehearse.py's `test_rehearse_cell` gives every cell ONE device, on which
run.py refuses a four-chip cell (exit 2, "needs 4 chips"), so its cases for
such a cell cannot pass; these are their stand-ins, with its assertions and
`count == 4`."""
import json

import pytest

from conftest import REPO
from test_rehearse import (DEVICE_KEYS, RESULT_KEYS, metric_names, result_of,
                           run_py)

FOUR_CHIP = [w["name"] for w in
             json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]
             if w["chips"] == 4]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", FOUR_CHIP)
def test_rehearse_four_chip_cell(cell, trace, manifest):
    line = result_of(run_py("--workload", cell, "--seed", str(2**31 + 7),
                            "--seconds", "1", "--trace", str(trace),
                            "--rehearse", devices=4))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 4
    if trace:
        assert set(dev) == DEVICE_KEYS | {"busy_s", "window_s"}
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


def test_one_device_refuses_a_four_chip_cell():
    """The two cases of test_rehearse_cell this file stands in for."""
    for cell in FOUR_CHIP:
        proc = run_py("--workload", cell, "--seed", "1", "--seconds", "1",
                      "--trace", "0", "--rehearse")
        assert proc.returncode == 2 and "needs 4 chips" in proc.stderr
        assert not proc.stdout.strip().startswith("{")
