#!/usr/bin/env python3
"""sha256 of the lowered v5e text of the one-chip fused iteration of the
benchmark's three training configurations (and, since PR 36, of `mslr_like`
over a table that takes the bucketed one-hot M-axis, as the cell's does;
since PR 37 a fifth line, `higgs_goss_like`: the SAMPLED iteration of
`higgs_like` under goss 0.2 / 0.1, past the sampler's warm-up),
outside debug locations: the
identity criterion of a PR that must leave the one-chip program alone
(PERF.md section 6, PRs 32 and 35).  No chip: the topology is described
(tests/test_tpu_aot_compile.py `_lower_iteration`).

    JAX_PLATFORMS=cpu python scripts/lowered_iteration_digest.py [out_dir]

Run it from the root of each checkout to compare (a `git archive` of the
parent and the tree); equal lines mean equal programs.  Debug locations are
in two places and both go: the text's own `loc(...)`, and the Python call
stack serialized inside every Mosaic kernel body (`tpu_custom_call`'s
`backend_config`), which is decoded and printed again without it.
"""
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.getcwd()
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def kernel_body(b64):
    """A serialized Mosaic module -> its assembly without debug info."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = jmlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(b64))
        return module.operation.get_asm(enable_debug_info=False)


def canonical(text):
    out = []
    for line in text.splitlines():
        if line.startswith("#loc"):
            continue
        line = re.sub(r"\s*loc\(.*?\)$", "", line)
        m = re.search(r'backend_config = "(.*?)"(, |})', line)
        if m and "tpu_custom_call" in line:
            cfg = json.loads(re.sub(
                r"\\([0-9A-Fa-f]{2})", lambda k: chr(int(k.group(1), 16)),
                m.group(1)))
            body = cfg["custom_call_config"].pop("body")
            line = line.replace(m.group(1), json.dumps(cfg, sort_keys=True)
                                + "\n" + kernel_body(body))
        out.append(line)
    return "\n".join(out)


def mslr_like_bucketed(aot):
    """`mslr_like` with columns of the cell's kind: the AOT file's table is
    all continuous, so its one-hot M-axis is uniform, where `mslr_train`'s
    is bucketed (small integer counts beside the scores).  Ninety of the 136
    columns become counts of 5, 12 and 25 values; the program then runs the
    bucketed axis, which main() asserts."""
    import numpy as np
    params, X, y, kw = aot._mslr_like()
    rs = np.random.RandomState(11)
    X = X.copy()
    for lo, hi, card in ((0, 40, 5), (40, 70, 12), (70, 90, 25)):
        X[:, lo:hi] = rs.randint(0, card, (len(X), hi - lo))
    return params, X, y, kw


def higgs_goss_like(aot):
    """`higgs_like` under upstream's documented one-side sampling (the cell
    `higgs_goss_train`): `data_sample_strategy=goss`, `top_rate` 0.2,
    `other_rate` 0.1, `learning_rate` 0.1."""
    params, X, y, kw = aot._higgs_like()
    return (dict(params, learning_rate=0.1, data_sample_strategy="goss",
                 top_rate=0.2, other_rate=0.1), X, y, kw)


def lower_sampled(aot, chip, params, X, y, kw):
    """aot._lower_iteration past the sampler's warm-up: the first
    `update()` lowers the program tree 10 would launch (`sample_mode=goss`,
    the analytic compaction capacity).  The warm-up's predicate is patched
    here, in the script, so that the same lines come from a parent
    checkout's AOT file; the iteration number itself enters the program
    only through key VALUES, which are arguments."""
    from unittest import mock
    from lightgbm_tpu.models.sample_strategy import GOSSStrategy
    with mock.patch.object(GOSSStrategy, "_is_warmup",
                           lambda self, iteration: False):
        return aot._lower_iteration(chip, params, X, y, kw)


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu import runtime
    import test_tpu_aot_compile as aot
    out_dir = sys.argv[1] if len(sys.argv) > 1 else None
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    with runtime.lowering_for("tpu"):
        for name, make in (("higgs_like", aot._higgs_like),
                           ("mslr_like", aot._mslr_like),
                           ("epsilon_like", aot._epsilon_like),
                           ("mslr_like_bucketed",
                            lambda: mslr_like_bucketed(aot))):
            eng, lowered = aot._lower_iteration(chip, *make())
            assert (eng._grow_params.bin_buckets is not None) == (
                name == "mslr_like_bucketed"), name
            emit(name, lowered, out_dir)
        eng, lowered = lower_sampled(aot, chip, *higgs_goss_like(aot))
        assert eng._compact_cap > 0, "the sampled line lowered no compaction"
        emit("higgs_goss_like", lowered, out_dir)


def emit(name, lowered, out_dir):
    from lightgbm_tpu.robustness.checkpoint import atomic_write_text
    text = canonical(lowered.as_text())
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        atomic_write_text(os.path.join(out_dir, name + ".txt"), text)
    print(name, hashlib.sha256(text.encode()).hexdigest(), flush=True)


if __name__ == "__main__":
    main()
