"""Fault-tolerance suite (docs/ROBUSTNESS.md).

Covers the checkpoint/resume bit-identity contract, manifest validation of
corrupt/truncated snapshots, the nan_guard policy paths, the chaos harness
no-op guarantee, and (slow tier) the supervising distributed launcher:
fail-fast on worker crash, hang detection via stale heartbeats, and
kill -> relaunch -> resume recovery within ``dist_retries``.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import LightGBMError
from lightgbm_tpu.robustness import chaos, checkpoint
from lightgbm_tpu.robustness.checkpoint import (latest_valid_snapshot,
                                                list_snapshots,
                                                validate_checkpoint)

from conftest import (make_synthetic_binary, make_synthetic_multiclass,
                      make_synthetic_ranking)

REPO = Path(__file__).resolve().parent.parent


def _binary_params(output_model, **extra):
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1, "snapshot_freq": 4, "output_model": str(output_model)}
    p.update(extra)
    return p


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

def test_resume_bit_identity_binary(tmp_path):
    X, y = make_synthetic_binary(n=1200)
    M = tmp_path / "out" / "model.txt"       # exercises dir creation too
    params = _binary_params(M)
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    snap = str(M) + ".snapshot_iter_4"
    assert os.path.exists(snap)
    assert os.path.exists(snap + ".manifest.json")
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                        resume_from=snap)
    assert resumed.model_to_string() == full.model_to_string()


@pytest.mark.slow
def test_resume_bit_identity_multiclass_batched(tmp_path):
    X, y = make_synthetic_multiclass(n=1500, k=4)
    M = tmp_path / "mc.txt"
    params = {"objective": "multiclass", "num_class": 4, "num_leaves": 12,
              "verbosity": -1, "snapshot_freq": 3, "output_model": str(M),
              "multiclass_batched": True}
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    assert full.engine._mc_batched_last   # the widened lockstep path ran
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                        resume_from=str(M) + ".snapshot_iter_3")
    assert resumed.model_to_string() == full.model_to_string()


def test_resume_with_bagging_and_feature_fraction(tmp_path):
    """Per-iteration RNG consumers (bagging keys, the feature-fraction
    host RandomState) must continue exactly where the snapshot left off."""
    X, y = make_synthetic_binary(n=1500)
    M = tmp_path / "bag.txt"
    params = _binary_params(M, bagging_fraction=0.7, bagging_freq=2,
                            feature_fraction=0.8)
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                        resume_from=str(M) + ".snapshot_iter_4")
    assert resumed.model_to_string() == full.model_to_string()


def test_corrupt_checkpoint_rejected(tmp_path):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    params = _binary_params(M)
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    snap = str(M) + ".snapshot_iter_4"
    text = open(snap).read()
    open(snap, "w").write(text[:len(text) // 2])
    with pytest.raises(LightGBMError, match="checksum"):
        validate_checkpoint(snap)
    with pytest.raises(LightGBMError, match="checksum"):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                  resume_from=snap)


def test_missing_manifest_rejected(tmp_path):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    params = _binary_params(M)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    plain = tmp_path / "plain_model.txt"
    bst.save_model(str(plain))               # a model file, not a checkpoint
    with pytest.raises(LightGBMError, match="manifest"):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                  resume_from=str(plain))


def test_resume_params_mismatch_rejected(tmp_path):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    lgb.train(_binary_params(M), lgb.Dataset(X, label=y), num_boost_round=4)
    snap = str(M) + ".snapshot_iter_4"
    bad = _binary_params(M, learning_rate=0.27)
    with pytest.raises(LightGBMError, match="learning_rate"):
        lgb.train(bad, lgb.Dataset(X, label=y), num_boost_round=8,
                  resume_from=snap)


def test_resume_and_init_model_conflict(tmp_path):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    params = _binary_params(M)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    with pytest.raises(LightGBMError, match="not both"):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                  resume_from=str(M) + ".snapshot_iter_4", init_model=bst)


def test_snapshot_prune_and_atomicity(tmp_path):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "snapdir" / "model.txt"
    params = _binary_params(M, snapshot_freq=2, snapshot_keep=2)
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    snaps = list_snapshots(str(M))
    assert [it for it, _ in snaps] == [6, 8]       # pruned to the 2 newest
    leftovers = [p for p in os.listdir(M.parent) if ".tmp." in p]
    assert leftovers == []                         # tmp files always cleaned
    for _, p in snaps:
        assert os.path.exists(p + ".manifest.json")
        assert os.path.exists(p + ".state.npz")


def test_truncated_model_string_rejected(tmp_path):
    X, y = make_synthetic_binary(n=800)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    s = bst.model_to_string()
    with pytest.raises(LightGBMError, match="truncated model"):
        lgb.Booster(model_str=s[:int(len(s) * 0.5)])
    # cutting before the marker but after all trees must also be caught
    cut = s[:s.index("end of trees")]
    with pytest.raises(LightGBMError, match="end of trees"):
        lgb.Booster(model_str=cut)


def test_nonfinite_init_model_rejected(tmp_path):
    X, y = make_synthetic_binary(n=800)
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    s = bst.model_to_string()
    lines = s.split("\n")
    for i, ln in enumerate(lines):
        if ln.startswith("leaf_value="):
            vals = ln[len("leaf_value="):].split(" ")
            vals[0] = "nan"
            lines[i] = "leaf_value=" + " ".join(vals)
            break
    poisoned = lgb.Booster(model_str="\n".join(lines))
    with pytest.raises(LightGBMError):
        lgb.train({"objective": "binary", "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2,
                  init_model=poisoned)


# ---------------------------------------------------------------------------
# nan_guard
# ---------------------------------------------------------------------------

def test_nan_guard_warn_skips_poisoned_iteration(tmp_path, monkeypatch):
    X, y = make_synthetic_binary(n=1000)
    monkeypatch.setenv(chaos.ENV_VAR, "nan_grad:iter=3")
    params = {"objective": "binary", "verbosity": -1, "telemetry": True}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst.engine.nan_iterations == 1
    assert bst.num_trees() == 6               # skipped iter keeps a no-op tree
    lm = lgb.Booster(model_str=bst.model_to_string())._loaded_trees
    assert all(np.isfinite(t.leaf_value).all() for t in lm.trees)
    trees = lm.trees
    assert trees[2].num_leaves == 1 and float(trees[2].leaf_value[0]) == 0.0
    counters = lgb.telemetry.global_registry.snapshot()["counters"]
    assert counters.get("train/nan_skipped") == 1


def test_nan_guard_raise(monkeypatch):
    X, y = make_synthetic_binary(n=1000)
    monkeypatch.setenv(chaos.ENV_VAR, "nan_grad:iter=2")
    with pytest.raises(LightGBMError, match="nan_guard=raise"):
        lgb.train({"objective": "binary", "verbosity": -1,
                   "nan_guard": "raise"},
                  lgb.Dataset(X, label=y), num_boost_round=6)


def test_nan_guard_invalid_mode():
    X, y = make_synthetic_binary(n=200)
    with pytest.raises(ValueError, match="nan_guard"):
        lgb.train({"objective": "binary", "verbosity": -1,
                   "nan_guard": "explode"},
                  lgb.Dataset(X, label=y), num_boost_round=1)


def test_nan_guard_keeps_objective_state(monkeypatch):
    """A skipped iteration must also keep the objective's PREVIOUS
    per-iteration state: lambdarank's position-bias update is computed from
    the poisoned lambdas, and writing it back would re-poison every later
    iteration's gradients."""
    X, y, sizes = make_synthetic_ranking(nq=60)
    rs = np.random.RandomState(0)
    pos = np.concatenate([np.arange(s) % 10 for s in sizes])
    monkeypatch.setenv(chaos.ENV_VAR, "nan_grad:iter=2")
    bst = lgb.train({"objective": "lambdarank",
                     "lambdarank_position_bias_regularization": 0.1,
                     "verbosity": -1},
                    lgb.Dataset(X, label=y, group=sizes, position=pos),
                    num_boost_round=5)
    assert bst.engine.nan_iterations == 1
    assert np.isfinite(np.asarray(bst.engine.objective.pos_biases)).all()
    assert np.isfinite(np.asarray(bst.engine.score)).all()


def test_nan_guard_init_score(monkeypatch):
    X, y = make_synthetic_binary(n=400)
    init = np.zeros(len(y))
    init[7] = np.nan
    with pytest.raises(LightGBMError, match="init_score"):
        lgb.train({"objective": "binary", "verbosity": -1,
                   "nan_guard": "raise"},
                  lgb.Dataset(X, label=y, init_score=init), num_boost_round=2)
    # warn mode: non-finite entries zeroed, training proceeds finite
    bst = lgb.train({"objective": "binary", "verbosity": -1},
                    lgb.Dataset(X, label=y, init_score=init),
                    num_boost_round=2)
    assert np.isfinite(bst.predict(X, raw_score=True)).all()


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------

def test_chaos_noop_when_env_unset(monkeypatch):
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    assert not chaos.active()
    assert not chaos.has("kill")
    chaos.maybe_kill(1)                        # must not exit
    chaos.heartbeat_hook(1)                    # must not sleep/hang
    import jax.numpy as jnp
    g = jnp.arange(4.0)
    assert chaos.inject_nan_grad(g, 1) is g    # exact pass-through


def test_chaos_parse_and_cli(monkeypatch):
    monkeypatch.setenv(chaos.ENV_VAR,
                       "kill:iter=5,rank=1,once=/run/m; nan_grad:iter=3,count=4")
    ds = chaos.directives()
    assert [d.name for d in ds] == ["kill", "nan_grad"]
    assert ds[0].iteration == 5 and ds[0].rank == 1 and ds[0].once == "/run/m"
    assert ds[1].count == 4
    assert chaos.main() == 0
    monkeypatch.setenv(chaos.ENV_VAR, "kill:bogus_key=1")
    with pytest.raises(ValueError, match="unknown option"):
        chaos.directives()


def test_chaos_closed_loop_directives(monkeypatch, tmp_path):
    """The refit/promotion chaos hooks (docs/ROBUSTNESS.md chaos matrix):
    poison_refit NaNs leaf values, torn_pointer half-writes promote.json,
    and all three parse with the standard option grammar."""
    monkeypatch.setenv(
        chaos.ENV_VAR,
        "poison_refit:iter=1,count=3; kill_refit:once=/run/m; "
        "torn_pointer:once=/run/m2")
    ds = chaos.directives()
    assert [d.name for d in ds] == ["poison_refit", "kill_refit",
                                    "torn_pointer"]
    assert ds[0].count == 3 and ds[1].once == "/run/m"
    vals = np.linspace(-1.0, 1.0, 8)
    poisoned = chaos.inject_nan_refit(vals, tree_index=1)
    assert np.isnan(poisoned[:3]).all() and np.isfinite(poisoned[3:]).all()
    assert np.isfinite(vals).all()             # input untouched
    # unmatched tree index: exact pass-through
    assert chaos.inject_nan_refit(vals, tree_index=2) is vals
    monkeypatch.delenv(chaos.ENV_VAR)
    assert chaos.inject_nan_refit(vals, tree_index=1) is vals
    assert chaos.maybe_tear_pointer(str(tmp_path), "{}") is False
    chaos.maybe_kill_refit()                   # must not exit


def test_prune_never_deletes_promoted_snapshot(tmp_path):
    """snapshot_keep pruning must skip any snapshot a live promote.json
    generation points at — current target or rollback target — else a
    replica restart/rollback would load a deleted file."""
    from lightgbm_tpu.robustness.checkpoint import prune_snapshots
    from lightgbm_tpu.serving.fleet import promote_pointer

    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    lgb.train(_binary_params(M, snapshot_freq=2),
              lgb.Dataset(X, label=y), num_boost_round=8)
    snaps = dict(list_snapshots(str(M)))
    assert set(snaps) == {2, 4, 6, 8}
    # promote iter-2 (-> prev of nothing), then iter-4: the pointer now
    # pins 4 (current) AND 2 (rollback target)
    promote_pointer(str(fleet), snaps[2])
    promote_pointer(str(fleet), snaps[4])
    prune_snapshots(str(M), keep=1, fleet_dir=str(fleet))
    kept = set(dict(list_snapshots(str(M))))
    assert kept == {2, 4, 8}                   # newest + both pinned
    # without the fleet dir the same call would have deleted them
    prune_snapshots(str(M), keep=1, fleet_dir="")
    assert set(dict(list_snapshots(str(M)))) == {8}


def test_checkpoint_threads_fleet_dir_pin(tmp_path):
    """Booster.checkpoint must thread serve_fleet_dir into pruning: a
    training run with snapshot_keep=1 keeps the promoted snapshot."""
    from lightgbm_tpu.serving.fleet import promote_pointer

    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    p = _binary_params(M, snapshot_freq=2, serve_fleet_dir=str(fleet))
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    snaps = dict(list_snapshots(str(M)))
    assert set(snaps) == {2, 4}
    promote_pointer(str(fleet), snaps[2])
    bst.checkpoint(str(M), keep=1)             # prunes, but pin survives
    assert set(dict(list_snapshots(str(M)))) == {2, 4}


def test_chaos_truncate_snapshot_skipped_by_latest_valid(tmp_path,
                                                         monkeypatch):
    X, y = make_synthetic_binary(n=800)
    M = tmp_path / "model.txt"
    params = _binary_params(M, snapshot_freq=4)
    monkeypatch.setenv(chaos.ENV_VAR, "truncate_snapshot:iter=8")
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    monkeypatch.delenv(chaos.ENV_VAR)
    snaps = dict(list_snapshots(str(M)))
    assert set(snaps) == {4, 8}
    with pytest.raises(LightGBMError):
        validate_checkpoint(snaps[8])          # chaos corrupted it
    assert latest_valid_snapshot(str(M)) == snaps[4]


# ---------------------------------------------------------------------------
# kill / resume through the real process boundary (slow tier)
# ---------------------------------------------------------------------------

def _write_csv(tmp_path, n=900):
    rs = np.random.RandomState(3)
    X = rs.randn(n, 6)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    path = tmp_path / "train.csv"
    np.savetxt(path, np.column_stack([y, X]), delimiter=",", fmt="%.6g")
    return path


def _clean_env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "LGBTPU_CHAOS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
def test_cli_kill_then_resume_bit_identity(tmp_path):
    """A CLI run killed by the chaos harness at iteration 9 leaves valid
    snapshots; resuming from iteration 5 reproduces the uninterrupted
    model byte-for-byte (params block included)."""
    csv = _write_csv(tmp_path)
    M = tmp_path / "model.txt"
    params = _binary_params(M, snapshot_freq=5)
    full = lgb.train(params, lgb.Dataset(str(csv)), num_boost_round=12)

    env = _clean_env()
    env["LGBTPU_CHAOS"] = "kill:iter=9"
    cli = [sys.executable, "-m", "lightgbm_tpu", f"data={csv}",
           "objective=binary", "num_leaves=15", "min_data_in_leaf=5",
           "verbosity=-1", "num_iterations=12", "snapshot_freq=5",
           f"output_model={M}"]
    out = subprocess.run(cli, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 137, out.stdout + out.stderr
    assert not M.exists()                      # killed before the final save
    snap = str(M) + ".snapshot_iter_5"
    validate_checkpoint(snap)

    resumed = lgb.train(params, lgb.Dataset(str(csv)), num_boost_round=12,
                        resume_from=snap)
    assert resumed.model_to_string() == full.model_to_string()


@pytest.mark.slow
def test_dist_failfast_on_worker_crash(tmp_path, monkeypatch,
                                       require_two_process_collectives):
    """Regression for the sequential rank-order await: a crashed rank 1
    must fail the run immediately, not after rank 0's full timeout."""
    csv = _write_csv(tmp_path, n=1200)
    monkeypatch.setenv(chaos.ENV_VAR, "kill:iter=2,rank=1")
    t0 = time.time()
    with pytest.raises(LightGBMError, match=r"worker 1/2 failed"):
        lgb.train_distributed({"objective": "binary", "verbosity": -1},
                              str(csv), num_boost_round=200,
                              num_processes=2, timeout=900)
    assert time.time() - t0 < 300   # far under the 900 s attempt timeout


@pytest.mark.slow
def test_dist_kill_retry_resume_bit_identity(
        tmp_path, monkeypatch, require_two_process_collectives):
    csv = _write_csv(tmp_path, n=1200)
    params = {"objective": "binary", "verbosity": -1}
    clean = lgb.train_distributed(dict(params), str(csv), num_boost_round=6,
                                  num_processes=2)
    ref = clean.model_to_string().split("\nparameters:")[0]

    marker = tmp_path / "kill.marker"
    monkeypatch.setenv(chaos.ENV_VAR, f"kill:iter=4,rank=1,once={marker}")
    bst = lgb.train_distributed(
        dict(params, dist_retries=2, dist_backoff=0.2, snapshot_freq=2),
        str(csv), num_boost_round=6, num_processes=2, timeout=900)
    assert marker.exists()                     # the kill really fired
    got = bst.model_to_string().split("\nparameters:")[0]
    assert got == ref


@pytest.mark.slow
def test_dist_hang_detector_fires_and_recovers(
        tmp_path, monkeypatch, require_two_process_collectives):
    csv = _write_csv(tmp_path, n=1000)
    marker = tmp_path / "hang.marker"
    monkeypatch.setenv(chaos.ENV_VAR, f"hang:iter=3,rank=1,once={marker}")
    bst = lgb.train_distributed(
        {"objective": "binary", "verbosity": -1, "dist_retries": 1,
         "dist_backoff": 0.1, "snapshot_freq": 2},
        str(csv), num_boost_round=6, num_processes=2, timeout=900,
        hang_timeout=10)
    assert marker.exists()
    assert bst.num_trees() == 6
