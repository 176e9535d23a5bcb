"""CLI multi-machine wiring (reference: the parallel_learning example conf:
num_machines + machine_list_file, python-package/lightgbm/dask.py:196-215
machine assembly, src/network/linkers_socket.cpp:83 find-own-rank).

A localhost-simulated 2-"host" run: two processes each execute the REAL CLI
entry (`lightgbm_tpu.cli.main`) on the same conf with their own
local_listen_port; each locates its rank in the machine list, connects via
jax.distributed, ingests its row shard, and trains the same SPMD program.
The resulting model must match single-process CLI training on the full
file."""
import socket
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.runtime import child_env


_CHILD = r"""
import os, sys
workdir, port = sys.argv[1], sys.argv[2]
os.chdir(workdir)
from lightgbm_tpu import cli
rc = cli.main(["config=train.conf", f"local_listen_port={port}"])
assert rc == 0
"""


def _free_ports(k):
    socks = [socket.socket() for _ in range(k)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


@pytest.mark.slow
def test_two_machine_cli_matches_single(tmp_path,
                                        require_two_process_collectives):
    rng = np.random.RandomState(0)
    n = 3000
    X = rng.randn(n, 5)
    y = (X[:, 0] + np.sin(X[:, 1]) > 0).astype(float)
    data = tmp_path / "train.csv"
    np.savetxt(data, np.column_stack([y, X]), delimiter=",", fmt="%.10g")

    p0, p1 = _free_ports(2)
    conf_body = (
        "task = train\nobjective = binary\ndata = train.csv\n"
        "num_trees = 5\nnum_leaves = 15\nmin_data_in_leaf = 5\n"
        "tree_learner = data\nhist_backend = stream\nverbosity = -1\n"
        "num_machines = 2\nmachine_list_file = mlist.txt\n")

    # single-process reference run (no machines keys)
    single = tmp_path / "single"
    single.mkdir()
    (single / "train.csv").symlink_to(data)
    (single / "train.conf").write_text(conf_body.replace(
        "num_machines = 2\nmachine_list_file = mlist.txt\n", ""))
    env = child_env("cpu")
    out = subprocess.run([sys.executable, "-c", _CHILD, str(single), "12400"],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr

    # two "machines", each in its own working dir with its own port
    procs = []
    dirs = []
    for rank, port in ((0, p0), (1, p1)):
        d = tmp_path / f"m{rank}"
        d.mkdir()
        (d / "train.csv").symlink_to(data)
        (d / "train.conf").write_text(conf_body)
        (d / "mlist.txt").write_text(
            f"127.0.0.1 {p0}\n127.0.0.1 {p1}\n")
        dirs.append(d)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(d), str(port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=900)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, o

    from test_dist_ingest import _models_structurally_equal

    ref = (single / "LightGBM_model.txt").read_text()
    for d in dirs:
        got = (d / "LightGBM_model.txt").read_text()
        # identical split structure; leaf sums differ ~1e-7 (two-shard
        # psum association vs one shard), like the dist-ingest suite
        _models_structurally_equal(got, ref)
