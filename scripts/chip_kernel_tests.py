"""Run the kernel-level unit tests ON THE CHIP (dev tool, through the chip tool).

tier-1 runs these files on the CPU with Pallas interpreted; this runs the same
assertions against the compiled Mosaic kernels: the stream kernel (bucketed
M-axis, int8 exactness, final sprint), `predict_stream`, batched multiclass
(K > 1 route folding), row compaction and GOSS route fusion (`route_replay`).
JAX is initialised on the TPU before pytest imports anything, and `--noconftest` keeps tests/conftest.py from being loaded as a
plugin (a test module that imports its helpers still can: by then the
platform is fixed).  Tests whose expectation is CPU-specific can fail here for
that reason — read each failure; this is an investigation, not a gate.

    chiprun --timeout 1800 -- python scripts/chip_kernel_tests.py [pytest args]
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests"))

DEFAULT = ["tests/test_stream_kernel.py",
           "tests/test_predict_kernel.py", "tests/test_multiclass_batched.py",
           "tests/test_sample_compact.py", "tests/test_hist_backends.py"]


def main() -> int:
    from lightgbm_tpu.runtime import configure_compile_cache, require_tpu
    dev = require_tpu("chip_kernel_tests (compiled kernels, not interpret)")
    configure_compile_cache()
    print(f"kernel tests on {dev['device_kind']} x{dev['device_count']}",
          flush=True)
    import pytest
    args = sys.argv[1:] or DEFAULT
    return pytest.main(["--noconftest", "-q", "-p", "no:cacheprovider",
                        "-m", "slow or not slow", "-rf", "--tb=short",
                        "--rootdir", str(ROOT), *args])


if __name__ == "__main__":
    sys.exit(main())
