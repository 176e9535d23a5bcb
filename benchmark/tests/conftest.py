"""benchmark/tests run on the CPU box: `python -m pytest benchmark/tests -q`.
Nothing here touches JAX while it is imported; the rehearsals run run.py in
child processes with their platform in the environment."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())
