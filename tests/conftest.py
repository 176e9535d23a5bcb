"""Test configuration: force an 8-device CPU platform so sharding/multi-chip paths are
testable without TPU hardware (mirrors the reference's strategy of testing distributed
mode with localhost multi-process, SURVEY.md §4 tier 2)."""
import os

# Force the CPU platform with 8 virtual devices: both are read when jax
# creates its first backend, so they are set before the first `import jax`.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

from lightgbm_tpu.runtime import configure_compile_cache

# persistent compilation cache: repeated test runs skip XLA compiles
configure_compile_cache()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_synthetic_regression(n=2000, f=10, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    y = (X[:, 0] * 2.0 + np.sin(X[:, 1] * 3.0) + X[:, 2] * X[:, 3]
         + 0.1 * rs.randn(n))
    return X, y


def make_synthetic_binary(n=2000, f=10, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    logit = X[:, 0] * 1.5 - X[:, 1] + X[:, 2] * X[:, 3] * 0.5
    p = 1.0 / (1.0 + np.exp(-logit))
    y = (rs.rand(n) < p).astype(np.float64)
    return X, y


def make_synthetic_multiclass(n=3000, f=10, k=4, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    centers = rs.randn(k, f) * 1.5
    logits = X @ centers.T
    y = np.argmax(logits + 0.5 * rs.randn(n, k), axis=1).astype(np.float64)
    return X, y


def make_synthetic_ranking(nq=100, docs_per_q=(5, 40), f=10, seed=0):
    rs = np.random.RandomState(seed)
    sizes = rs.randint(docs_per_q[0], docs_per_q[1], size=nq)
    n = int(sizes.sum())
    X = rs.randn(n, f)
    rel_score = X[:, 0] * 2.0 + X[:, 1] + 0.3 * rs.randn(n)
    # map to 0-4 relevance grades within query
    y = np.zeros(n)
    start = 0
    for s in sizes:
        seg = rel_score[start:start + s]
        ranks = np.argsort(np.argsort(seg))
        y[start:start + s] = np.minimum(4, (ranks * 5) // max(s, 1))
        start += s
    return X, y, sizes


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


# ---------------------------------------------------------------------------
# two-process collective capability probe (slow tier)
# ---------------------------------------------------------------------------
#
# The localhost multi-process suites need the jax CPU backend to run
# cross-process collectives (the gloo implementation; the default CPU
# client refuses with "Multiprocess computations aren't implemented on
# the CPU backend").
# Probe it ONCE per session with a minimal 2-process allgather and skip
# the dependent tests with the root cause in the reason — the slow tier
# must be green-or-skipped, never red, on hosts without the capability.

_PROBE_CHILD = r"""
import sys
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"localhost:{sys.argv[1]}", num_processes=2,
                           process_id=int(sys.argv[2]))
import jax.numpy as jnp
from jax.experimental import multihost_utils
multihost_utils.process_allgather(jnp.ones((2,)))
"""

_two_process_probe_result = []   # memo: [error-string-or-None]


def two_process_collectives_error():
    """None when 2-process jax CPU collectives work here; otherwise the
    root-cause line from the failing probe."""
    if _two_process_probe_result:
        return _two_process_probe_result[0]
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    from lightgbm_tpu.runtime import child_env
    procs = [subprocess.Popen(
        [_sys.executable, "-c", _PROBE_CHILD, str(port), str(r)],
        env=child_env("cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
        for r in range(2)]
    outs, err = [], None
    for p in procs:
        try:
            outs.append(p.communicate(timeout=180)[0].decode())
        except subprocess.TimeoutExpired:
            p.kill()
            outs.append(p.communicate()[0].decode())
            err = "2-process collective probe timed out"
    if err is None and any(p.returncode != 0 for p in procs):
        tail = next(o for p, o in zip(procs, outs) if p.returncode != 0)
        lines = [ln.strip() for ln in tail.splitlines() if ln.strip()]
        root = [ln for ln in lines if "rror" in ln]
        err = (root or lines or ["probe failed"])[-1]
    _two_process_probe_result.append(err)
    return err


@pytest.fixture
def require_two_process_collectives():
    """Skip (root cause in the reason) when this host's jax CPU backend
    cannot run cross-process collectives."""
    err = two_process_collectives_error()
    if err is not None:
        pytest.skip("jax CPU backend refuses 2-process collectives on "
                    f"this host: {err}")
