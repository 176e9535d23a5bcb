"""lightgbm_tpu.runtime — the platform helper, the interpret seam, the compile
cache helper and the one-process-per-chip launch checks."""
import pathlib

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import runtime
from lightgbm_tpu.utils.log import LightGBMError

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- platform + interpret seam -----------------------------------------------

def test_cpu_is_not_the_chip_and_kernels_are_interpreted():
    assert runtime.platform_name() == "cpu"
    assert not runtime.on_tpu()
    assert runtime.pallas_interpret()
    rec = runtime.device_record()
    assert rec == {"platform": "cpu", "device_kind": jax.devices()[0].device_kind,
                   "device_count": len(jax.devices())}
    with pytest.raises(LightGBMError, match="'cpu'"):
        runtime.require_tpu("this test")


def test_lowering_for_flips_both_answers_and_restores():
    with runtime.lowering_for("tpu"):
        assert runtime.on_tpu() and not runtime.pallas_interpret()
    assert not runtime.on_tpu() and runtime.pallas_interpret()


def test_one_helper_decides_the_platform():
    """No module asks JAX for the backend on its own, and no Pallas module
    keeps a private interpret switch."""
    offenders = []
    for p in (REPO / "lightgbm_tpu").rglob("*.py"):
        if p.name == "runtime.py":
            continue
        text = p.read_text()
        if "default_backend()" in text or "_INTERPRET" in text \
                or "interpret=True" in text:
            offenders.append(str(p.relative_to(REPO)))
    assert offenders == []


# -- compile cache -----------------------------------------------------------

def test_cache_env_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path / "x"))

    def no_update(*a, **k):
        raise AssertionError(f"jax.config.update{a} with {runtime.CACHE_ENV} set")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert runtime.configure_compile_cache() == str(tmp_path / "x")
    assert runtime.compile_cache_dir() == str(tmp_path / "x")


def test_cache_default_is_the_checkout(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    assert runtime.CHECKOUT == REPO
    assert runtime.configure_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_no_temp_dir_cache_path_remains():
    needle = "/" + "tmp"
    hits = [str(p.relative_to(REPO))
            for d in ("lightgbm_tpu", "tests")
            for p in (REPO / d).rglob("*.py") if needle in p.read_text()]
    assert hits == []


# -- spawned processes -------------------------------------------------------

def test_child_env_states_platform_cache_and_checkout(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo",
            "PYTHONPATH": "elsewhere"}
    env = runtime.child_env("cpu", base=base)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--foo"
    assert env[runtime.CACHE_ENV] == str(REPO / ".jax_cache")
    assert env["PYTHONPATH"].split(":")[0] == str(REPO)
    env = runtime.child_env("cpu", n_cpu_devices=4, base={})
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    assert "XLA_FLAGS" not in runtime.child_env("tpu", base={})
    with pytest.raises(LightGBMError, match="virtual devices"):
        runtime.child_env("tpu", n_cpu_devices=4, base={})
    assert base["PYTHONPATH"] == "elsewhere"        # caller's dict untouched


def test_child_platform_follows_the_parent(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.child_platform() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    jax.devices()                     # this process has a backend: cpu
    assert runtime.child_platform() == "cpu"


def test_require_chips(monkeypatch):
    runtime.require_chips(64, "cpu", "cpu children")      # not chip-holding
    jax.devices()
    assert runtime.backend_initialized()
    with pytest.raises(LightGBMError, match="holds the chip"):
        runtime.require_chips(1, "tpu", "child of a JAX parent")
    monkeypatch.setattr(runtime, "backend_initialized", lambda: False)
    monkeypatch.setattr(runtime, "probe_devices", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1})
    runtime.require_chips(1, "tpu", "one replica")
    with pytest.raises(LightGBMError,
                       match="3 chip-holding processes were asked for and "
                             r"this host has 1 chip\(s\) — a chip belongs to "
                             "one process"):
        runtime.require_chips(3, "tpu", "ServingFleet(replicas=3)")
    monkeypatch.setattr(runtime, "probe_devices", lambda: {
        "platform": "cpu", "device_kind": "cpu", "device_count": 8})
    with pytest.raises(LightGBMError, match="has 0 chip"):
        runtime.require_chips(1, "tpu", "no chip here")


def test_probe_devices_runs_outside_this_process():
    assert runtime.probe_devices()["platform"] == "cpu"


def _tiny_model(tmp_path):
    rs = np.random.RandomState(0)
    X = rs.randn(300, 4)
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbosity": -1},
                    lgb.Dataset(X, label=(X[:, 0] > 0).astype(float)),
                    num_boost_round=2)
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    return path


def test_fleet_refuses_chip_replicas_at_launch(tmp_path):
    """More chip-holding replicas than chips — or a parent that already
    holds the chip — fails in start(), not after startup_timeout_s."""
    from lightgbm_tpu.serving.fleet import ServingFleet
    model = _tiny_model(tmp_path)
    fleet = ServingFleet(model, replicas=3, platform="tpu",
                         fleet_dir=str(tmp_path / "fleet"))
    assert fleet.platform == "tpu"
    with pytest.raises(LightGBMError, match="a chip belongs to one process"):
        fleet.start()
    assert not fleet._procs                     # nothing was spawned
    assert ServingFleet(model, replicas=1,
                        fleet_dir=str(tmp_path / "f2")).platform == "cpu"


def test_train_distributed_refuses_chip_workers_at_launch(tmp_path):
    from lightgbm_tpu.parallel.cluster import train_distributed
    data = tmp_path / "d.csv"
    data.write_text("1,0.5\n0,0.1\n")
    with pytest.raises(LightGBMError, match="a chip belongs to one process"):
        train_distributed({"objective": "binary"}, str(data),
                          num_processes=2, platform="tpu")
