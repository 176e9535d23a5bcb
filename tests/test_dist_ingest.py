"""Distributed data ingestion (reference: DatasetLoader::LoadFromFile rank
sharding + bin-mapper sync, dataset_loader.cpp:211,733-741; test model:
tests/distributed/_test_distributed.py — localhost multi-process).

The 2-process test launches real `jax.distributed` processes on localhost;
each parses a DISJOINT shard of the csv, mappers sync via allgather, the
binned shards assemble into one global row-sharded array, and the trained
model must match single-process training on the full file.
"""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset_io import load_data_file
from lightgbm_tpu.runtime import child_env


def _write_csv(path, n=4000, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + np.sin(X[:, 1]) + 0.1 * rng.randn(n) > 0).astype(float)
    np.savetxt(path, np.column_stack([y, X]), delimiter=",", fmt="%.10g")
    return X, y


def test_shard_loading_concat_equals_full(tmp_path):
    p = str(tmp_path / "d.csv")
    X, y = _write_csv(p)
    w = np.random.RandomState(1).rand(len(X))
    np.savetxt(p + ".weight", w, fmt="%.8f")
    full_X, full_y, full_ex = load_data_file(p, {})
    parts = [load_data_file(p, {}, rank=r, num_machines=3) for r in range(3)]
    np.testing.assert_allclose(np.vstack([q[0] for q in parts]), full_X)
    np.testing.assert_allclose(np.concatenate([q[1] for q in parts]), full_y)
    np.testing.assert_allclose(
        np.concatenate([q[2]["weight"] for q in parts]), full_ex["weight"])
    starts = [q[2]["start_row"] for q in parts]
    assert starts == [0, len(parts[0][0]), len(parts[0][0]) + len(parts[1][0])]


_CHILD = r"""
import os, sys
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
port, rank, data, out = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=rank)
import lightgbm_tpu as lgb
ds = lgb.Dataset(data)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "min_data_in_leaf": 5, "tree_learner": "data",
                 "hist_backend": "stream"},
                ds, num_boost_round=5)
assert ds._dist is not None and ds._dist["nproc"] == 2
if rank == 0:
    open(out, "w").write(bst.model_to_string())
"""


def _models_structurally_equal(a: str, b: str):
    a = a.split("\nparameters:")[0]
    b = b.split("\nparameters:")[0]
    la, lb = a.splitlines(), b.splitlines()
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        if xa == xb:
            continue
        ka, _, va = xa.partition("=")
        kb, _, vb = xb.partition("=")
        assert ka == kb
        if ka == "tree_sizes":
            continue
        fa = np.array([float(t) for t in va.split()])
        fb = np.array([float(t) for t in vb.split()])
        np.testing.assert_allclose(fa, fb, rtol=3e-4, atol=3e-4, err_msg=ka)


@pytest.mark.slow
def test_two_process_distributed_training(tmp_path, require_two_process_collectives):
    data = str(tmp_path / "train.csv")
    _write_csv(data)
    out = str(tmp_path / "dist_model.txt")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    # platform, device count and compile cache stated for the child
    env = child_env("cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(port), str(r), data, out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o[-4000:]}"

    # single-process reference on the full file
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "hist_backend": "stream"},
                    lgb.Dataset(data), num_boost_round=5)
    dist_model = open(out).read()
    _models_structurally_equal(bst.model_to_string(), dist_model)


_CHILD_VALID = r"""
import os, sys, json
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
port, rank, data, vdata, out = (sys.argv[1], int(sys.argv[2]), sys.argv[3],
                                sys.argv[4], sys.argv[5])
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=rank)
import lightgbm_tpu as lgb
ds = lgb.Dataset(data)
vs = lgb.Dataset(vdata, reference=ds)
evals = {}
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "min_data_in_leaf": 5, "tree_learner": "data",
                 "metric": "binary_logloss"},
                ds, num_boost_round=30, valid_sets=[vs],
                valid_names=["valid"],
                callbacks=[lgb.early_stopping(3, verbose=False),
                           lgb.record_evaluation(evals)])
if rank == 0:
    json.dump({"best_iteration": bst.best_iteration,
               "logloss": evals["valid"]["binary_logloss"]}, open(out, "w"))
"""


@pytest.mark.slow
def test_two_process_valid_early_stopping_matches_single(
        tmp_path, require_two_process_collectives):
    """Rank-aligned validation under distributed loading (reference:
    LoadFromFileAlignWithOtherDataset): early stopping must pick the same
    best_iteration as single-process training on the full files."""
    data = str(tmp_path / "train.csv")
    vdata = str(tmp_path / "valid.csv")
    _write_csv(data)
    _write_csv(vdata, n=1200, seed=9)
    out = str(tmp_path / "dist_es.json")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # platform, device count and compile cache stated for the child
    env = child_env("cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD_VALID, str(port), str(r), data, vdata,
         out], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o[-4000:]}"
    import json
    got = json.load(open(out))

    evals = {}
    ds = lgb.Dataset(data)
    vs = lgb.Dataset(vdata, reference=ds)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "metric": "binary_logloss"},
                    ds, num_boost_round=30, valid_sets=[vs],
                    valid_names=["valid"],
                    callbacks=[lgb.early_stopping(3, verbose=False),
                               lgb.record_evaluation(evals)])
    assert got["best_iteration"] == bst.best_iteration
    np.testing.assert_allclose(got["logloss"],
                               evals["valid"]["binary_logloss"],
                               rtol=2e-3, atol=2e-3)


_CHILD_RANK = r"""
import os, sys
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
port, rank, data, out = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=rank)
import lightgbm_tpu as lgb
ds = lgb.Dataset(data)
bst = lgb.train({"objective": "lambdarank", "num_leaves": 15,
                 "verbosity": -1, "min_data_in_leaf": 5,
                 "tree_learner": "data"},
                ds, num_boost_round=5)
assert ds.get_group() is not None
if rank == 0:
    open(out, "w").write(bst.model_to_string())
"""


def _write_ranking_csv(path, nq=120, seed=3):
    rng = np.random.RandomState(seed)
    sizes = rng.randint(5, 30, size=nq)
    n = int(sizes.sum())
    X = rng.randn(n, 5)
    rel = X[:, 0] * 2 + X[:, 1] + 0.3 * rng.randn(n)
    y = np.zeros(n)
    start = 0
    for s in sizes:
        seg = rel[start:start + s]
        ranks = np.argsort(np.argsort(seg))
        y[start:start + s] = np.minimum(4, (ranks * 5) // max(s, 1))
        start += s
    np.savetxt(path, np.column_stack([y, X]), delimiter=",", fmt="%.10g")
    np.savetxt(path + ".query", sizes, fmt="%d")
    return sizes


@pytest.mark.slow
def test_two_process_lambdarank_matches_single(
        tmp_path, require_two_process_collectives):
    """Query-boundary-respecting sharding: lambdarank under multi-process
    tree_learner=data must reproduce single-process training."""
    data = str(tmp_path / "rank.csv")
    _write_ranking_csv(data)
    out = str(tmp_path / "dist_rank_model.txt")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # platform, device count and compile cache stated for the child
    env = child_env("cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD_RANK, str(port), str(r), data, out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o[-4000:]}"

    bst = lgb.train({"objective": "lambdarank", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(data), num_boost_round=5)
    _models_structurally_equal(bst.model_to_string(), open(out).read())


def test_query_aligned_sharding_keeps_queries_whole(tmp_path):
    p = str(tmp_path / "r.csv")
    sizes = _write_ranking_csv(p, nq=37, seed=5)
    parts = [load_data_file(p, {}, rank=r, num_machines=3) for r in range(3)]
    gs = [q[2]["group"] for q in parts]
    np.testing.assert_array_equal(np.concatenate(gs), sizes)
    assert sum(len(q[0]) for q in parts) == int(sizes.sum())
    for q in parts:
        assert int(q[2]["group"].sum()) == len(q[0])


def test_streamed_query_aligned_shards(tmp_path):
    """Streaming ingest shards ranking files on QUERY boundaries: every
    rank's chunk stream reproduces exactly a whole-query row slice (no
    query straddles a shard) and the group slices concatenate back to the
    full .query sidecar."""
    from lightgbm_tpu.ingest import _FileSource

    p = str(tmp_path / "r.csv")
    sizes = _write_ranking_csv(p, nq=37, seed=5)
    full = np.loadtxt(p, delimiter=",")
    bounds = set(np.concatenate([[0], np.cumsum(sizes)]).tolist())
    rows_seen = 0
    groups = []
    for r in range(3):
        src = _FileSource(p, {}, chunk_rows=64, rank=r, nproc=3)
        chunks = [c[1] for c in src.chunks()]
        X = np.vstack(chunks) if chunks else \
            np.empty((0, full.shape[1] - 1))
        assert src.start_row == rows_seen
        # the shard's first and last rows sit ON query boundaries
        assert rows_seen in bounds and (rows_seen + len(X)) in bounds, \
            f"rank {r} shard straddles a query"
        assert int(src.group_slice.sum()) == len(X)
        np.testing.assert_allclose(
            X, full[rows_seen:rows_seen + len(X), 1:])
        groups.append(np.asarray(src.group_slice))
        rows_seen += len(X)
    assert rows_seen == len(full)
    np.testing.assert_array_equal(np.concatenate(groups), sizes)


def test_query_aligned_byte_range_empty_rank(tmp_path):
    """More ranks than queries: the starved rank reads zero bytes and an
    empty group slice instead of double-reading rows."""
    from lightgbm_tpu.dataset_io import query_aligned_byte_range

    p = str(tmp_path / "tiny.csv")
    sizes = _write_ranking_csv(p, nq=1, seed=7)
    shards = [query_aligned_byte_range(p, sizes, r, 3) for r in range(3)]
    nonempty = [s for s in shards if s[1] > s[0]]
    assert len(nonempty) == 1
    assert sum(int(np.sum(s[3])) for s in shards) == int(sizes.sum())


_CHILD_RANK_STREAM = r"""
import os, sys, json
import numpy as np
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
port, rank, data, out = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=rank)
import lightgbm_tpu as lgb
ds = lgb.Dataset(data, params={"ingest_mode": "stream",
                               "ingest_chunk_rows": 256})
bst = lgb.train({"objective": "lambdarank", "num_leaves": 15,
                 "verbosity": -1, "min_data_in_leaf": 5,
                 "tree_learner": "data"},
                ds, num_boost_round=5)
assert ds.get_group() is not None
assert ds._dist is not None and ds._dist["nproc"] == 2
if rank == 0:
    open(out, "w").write(bst.model_to_string())
"""


@pytest.mark.slow
def test_two_process_lambdarank_streamed_matches_inmem(
        tmp_path, require_two_process_collectives):
    """Streamed distributed ranking no longer falls back (or errors) on
    .query files: chunk boundaries snap to query boundaries, and the
    2-process streamed model must match single-process INMEM training —
    structural identity implies NDCG parity, asserted explicitly."""
    data = str(tmp_path / "rank.csv")
    sizes = _write_ranking_csv(data)
    out = str(tmp_path / "dist_rank_stream_model.txt")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # platform, device count and compile cache stated for the child
    env = child_env("cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD_RANK_STREAM, str(port), str(r), data,
         out], env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o[-4000:]}"

    bst = lgb.train({"objective": "lambdarank", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(data), num_boost_round=5)
    dist_model = open(out).read()
    _models_structurally_equal(bst.model_to_string(), dist_model)

    # NDCG parity vs inmem on the full file
    full = np.loadtxt(data, delimiter=",")
    y, X = full[:, 0], full[:, 1:]
    qb = np.concatenate([[0], np.cumsum(sizes)]).astype(int)
    from test_ranking import _ndcg_at
    bst_d = lgb.Booster(model_str=dist_model)
    n_in = _ndcg_at(np.asarray(bst.predict(X)), y, qb)
    n_st = _ndcg_at(np.asarray(bst_d.predict(X)), y, qb)
    assert abs(n_in - n_st) < 0.02, (n_in, n_st)


def test_shard_loading_skips_blank_and_comment_lines(tmp_path):
    """Blank/comment lines must not shift per-row sidecar alignment."""
    p = str(tmp_path / "d.csv")
    rng = np.random.RandomState(2)
    X = rng.randn(30, 3)
    y = (X[:, 0] > 0).astype(float)
    lines = [",".join(f"{v:.8f}" for v in [y[i], *X[i]]) for i in range(30)]
    lines.insert(7, "")          # blank line inside rank 0's shard
    lines.insert(20, "")
    (tmp_path / "d.csv").write_text("\n".join(lines) + "\n")
    w = rng.rand(30)
    np.savetxt(p + ".weight", w, fmt="%.8f")
    parts = [load_data_file(p, {}, rank=r, num_machines=2) for r in range(2)]
    wc = np.concatenate([q[2]["weight"] for q in parts])
    np.testing.assert_allclose(wc, w)
    np.testing.assert_allclose(np.concatenate([q[1] for q in parts]), y)


@pytest.mark.slow
def test_train_distributed_launcher(tmp_path,
                                    require_two_process_collectives):
    """lgb.train_distributed — the dask.py `_train` analog (dask.py:124-215):
    spawns local workers, shards the file by rows, trains data-parallel, and
    returns rank 0's Booster with evals_result_ attached. Must reproduce the
    single-process model structurally (same psum'd histograms)."""
    data = str(tmp_path / "train.csv")
    _write_csv(data)
    valid = str(tmp_path / "valid.csv")
    _write_csv(valid, n=800, seed=9)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "hist_backend": "stream"}
    bst = lgb.train_distributed(params, data, num_boost_round=5,
                                num_processes=2, valid_paths=[valid],
                                valid_names=["va"])
    assert bst.num_trees() == 5
    assert "va" in bst.evals_result_ and \
        len(next(iter(bst.evals_result_["va"].values()))) == 5
    ref = lgb.train(params, lgb.Dataset(data), num_boost_round=5)
    _models_structurally_equal(ref.model_to_string(), bst.model_to_string())
