"""Kernels that compile — kept true from the CPU box.

The installed libtpu can describe a v5e topology with no chip attached
(``jax.experimental.topologies``), so the programs the chip will run are
AOT-compiled here, by the real Mosaic/XLA:TPU compiler: the interpret seam is
off (``runtime.lowering_for("tpu")``) and the engine resolves its
configuration exactly as it does on the chip (stream backend, int8
histograms, 64 splits a round, fused iteration, the TPU block-size tiers).
Nothing compiled here is executed; whether the compiled kernels give the
right ANSWERS is chip_smoke.py's job, on the chip.

A topology that cannot be built is an error, not a skip: without it this
file proves nothing and must not pass quietly.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from lightgbm_tpu import runtime
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.pallas import stream_kernel
from lightgbm_tpu.utils.log import LightGBMError


@pytest.fixture(scope="module")
def topo():
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def tpu(topo):
    dev = topo.devices[0]
    assert dev.platform == "tpu" and "v5" in dev.device_kind
    with runtime.lowering_for("tpu"):
        yield SingleDeviceSharding(dev)


def _abstract(tree, sharding):
    """Every array leaf -> a ShapeDtypeStruct placed on the topology's
    device, which is what makes ``.lower()`` target the TPU."""
    def one(a):
        if isinstance(a, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        return a
    return jax.tree.map(one, tree)


class _Lowered(Exception):
    pass


def _rows(n, f, seed):
    rs = np.random.RandomState(seed)
    return rs.randn(n, f).astype(np.float32), rs


def _higgs_like():
    """bench.py main(): 28 features, 255 leaves, 63 bins, quantized."""
    X, rs = _rows(8192, 28, 0)
    return ({"objective": "binary", "num_leaves": 255, "max_bin": 63,
             "use_quantized_grad": True, "num_grad_quant_bins": 64},
            X, (rs.rand(len(X)) < 0.5).astype(np.float64), {})


def _mslr_like():
    """bench.py run_ranking(): 136 features, lambdarank, quantized."""
    X, rs = _rows(4096, 136, 1)
    return ({"objective": "lambdarank", "num_leaves": 255, "max_bin": 63,
             "use_quantized_grad": True, "num_grad_quant_bins": 64,
             "ndcg_eval_at": [10]},
            X, rs.randint(0, 5, len(X)).astype(np.float64),
            {"group": np.full(32, 128)})


def _multiclass_k10():
    """bench.py run_multiclass(): K=10 batched growth, bf16 two-pass."""
    X, rs = _rows(4096, 28, 2)
    return ({"objective": "multiclass", "num_class": 10, "num_leaves": 255,
             "max_bin": 63},
            X, rs.randint(0, 10, len(X)).astype(np.float64), {})


def _epsilon_like():
    """benchmark cell epsilon_train: 2,000 dense columns, 63 bins, 255
    leaves, quantized — sixteen M-tiles of 128 groups."""
    X, rs = _rows(4096, 2000, 3)
    return ({"objective": "binary", "num_leaves": 255, "max_bin": 63,
             "use_quantized_grad": True, "num_grad_quant_bins": 64},
            X, (rs.rand(len(X)) < 0.5).astype(np.float64), {})


def _criteo_like():
    """benchmark configuration criteo_dp_like on one chip: 67 dense columns,
    63 bins, 255 leaves, quantized — G = 67 on one M-tile."""
    X, rs = _rows(8192, 67, 4)
    return ({"objective": "binary", "num_leaves": 255, "max_bin": 63,
             "use_quantized_grad": True, "num_grad_quant_bins": 64},
            X, (rs.rand(len(X)) < 0.03).astype(np.float64), {})


def _lower_iteration(sharding, params, X, y, ds_kw):
    """Build the Booster as on the chip and lower — for the topology's TPU —
    the program its first ``update()`` would launch (the fused iteration),
    instead of running it."""
    real = gbdt_mod.watched_jit
    got = []

    def capturing(fn=None, *, name=None, **kw):
        jitted = real(fn, name=name, **kw)
        if name != "fused_iter":
            return jitted

        def call(*args, **kwargs):
            a, k = _abstract((args, kwargs), sharding)
            got.append(jitted.lower(*a, **k))
            raise _Lowered()
        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gbdt_mod, "watched_jit", capturing)
        bst = lgb.Booster(dict(params, verbosity=-1),
                          lgb.Dataset(X, label=y, **ds_kw))
        eng = bst.engine
        gp = eng._grow_params
        assert gp.hist_backend == "stream" and gp.max_splits_per_round == 64
        assert eng._can_fuse_iteration() and eng._use_leaf_gather_kernel
        with pytest.raises(_Lowered):
            bst.update()
    return eng, got[0]


def _digest_module():
    """scripts/lowered_iteration_digest.py as a module: the tables and the
    lowering of the lines it prints beyond this file's own."""
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "lowered_iteration_digest", Path(__file__).resolve().parents[1]
        / "scripts" / "lowered_iteration_digest.py")
    digest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(digest)
    return digest


@pytest.fixture(scope="module")
def iterations(tpu):
    """The three iteration programs ``python bench.py`` runs and the wide
    benchmark cell's, at their full widths and small N: lowered one after
    another (tracing holds the GIL), compiled side by side (XLA does not)."""
    import sys
    from concurrent.futures import ThreadPoolExecutor
    lowered = {name: _lower_iteration(tpu, *make())
               for name, make in (("higgs", _higgs_like),
                                  ("mslr", _mslr_like),
                                  ("multiclass", _multiclass_k10),
                                  ("epsilon", _epsilon_like),
                                  ("criteo", _criteo_like))}
    # the cell higgs_goss_train's SAMPLED iteration, past the warm-up
    digest, me = _digest_module(), sys.modules[__name__]
    lowered["higgs_goss"] = digest.lower_sampled(
        me, tpu, *digest.higgs_goss_like(me))
    with ThreadPoolExecutor(len(lowered)) as pool:
        texts = {name: pool.submit(lambda lo=lo: lo.compile().as_text())
                 for name, (_, lo) in lowered.items()}
        return {name: (lowered[name][0], fut.result())
                for name, fut in texts.items()}


def test_higgs_like_iteration_compiles(iterations):
    eng, text = iterations["higgs"]
    assert "tpu_custom_call" in text                 # Mosaic, not interpret
    assert eng._grow_params.int_hist and eng._pack_block == 4096
    assert (eng.dd.num_groups, eng.dd.max_bins) == (28, 63)


def test_mslr_like_lambdarank_iteration_compiles(iterations):
    eng, text = iterations["mslr"]
    assert "tpu_custom_call" in text
    assert eng._grow_params.int_hist and eng._pack_block == 1024
    assert eng.dd.num_groups == 136


def test_epsilon_like_iteration_compiles(iterations):
    """hist_backend=auto resolves `stream` at G = 2,000 on a TPU and the
    fused iteration compiles: the route-only pass, the tiles' sweeps and the
    tiled factored root, each a `route_and_hist` call of its own result type."""
    eng, text = iterations["epsilon"]
    assert "tpu_custom_call" in text
    assert eng._grow_params.int_hist and eng._grow_params.bin_buckets is None
    assert tuple(eng._stream_tiling) == (1024, 128, 16, 128 * 64)
    assert eng._packed.shape[0] == 2048 and eng._root_pass == "factored"
    n = eng._packed.shape[1]
    assert _route_and_hist_kinds(text) == {
        # the route-only pass before a tiled pass: leaf ids, counts, slots
        f"(s32[1,{n}], f32[1,64], s32[1,{n}])",
        # the sweeps: one call, a histogram block a tile
        "s32[16,8192,128]",
        # the tiled factored root
        "s32[32768,128]",
        # a tree's last round routes and counts only
        f"(s32[1,{n}], f32[1,128])",
    } | SMALL_KINDS["epsilon"](n)


def test_criteo_like_iteration_compiles(iterations):
    """G = 67 on one chip: between `higgs_like` (28) and `mslr_like` (136),
    one M-tile, the uniform one-hot axis; the kernel calls the four-chip cell
    runs a shard at a time."""
    eng, text = iterations["criteo"]
    assert "tpu_custom_call" in text
    gp = eng._grow_params
    assert gp.int_hist and gp.hist_reduce_limbs == 1 and eng.mesh is None
    assert (eng.dd.num_groups, eng.dd.max_bins) == (67, 63)
    assert eng._pack_block == 2048 and eng._stream_tiling.num_tiles == 1
    assert eng._root_pass == "factored"
    n, m_rows = eng._packed.shape[1], eng._stream_tiling.tile_m_rows
    assert {f"(s32[1,{n}], s32[{m_rows},128], f32[1,64])",
            f"(s32[1,{n}], f32[1,128])"} <= _route_and_hist_kinds(text)


@pytest.mark.parametrize("name, kind, m_rows", [
    pytest.param("higgs", "words", 28 * 64, id="higgs_g28_t4096"),
    pytest.param("mslr", "words", 136 * 64, id="mslr_uniform_g136_t1024"),
    pytest.param("epsilon", "words", 128 * 64, id="epsilon_128_group_tile"),
    pytest.param("criteo", "words", 68 * 64, id="criteo_g67_t2048"),
])
def test_onehot_build_of_the_cells_programs(iterations, name, kind, m_rows):
    """The 64-slot passes of the three uniform-axis cells (and of this
    file's all-continuous `mslr_like` table, whose axis is uniform too)
    build their bin one-hot in words and compile for the v5e under
    SCOPED_VMEM_LIMIT (the one-tile kernels raise no limit) /
    TILES_VMEM_LIMIT (the sweeps), at the cells' block sizes and with whole
    words of four groups on the M-axis (4,352 rows for 67 groups)."""
    eng, text = iterations[name]
    assert eng._poll_tiling["onehot_build"] == kind
    assert eng._grow_params.bin_buckets is None
    plan = eng._stream_tiling
    assert plan.tile_m_rows == m_rows
    # the record's rows are the table's own groups' (the tiles' pads left out)
    assert eng._poll_tiling["hist_m_rows"] == (
        eng.dd.num_groups * 64 if plan.tile_groups else m_rows)
    n = eng._packed.shape[1]
    want = ("s32[16,8192,128]" if plan.tile_groups
            else f"(s32[1,{n}], s32[{m_rows},128], f32[1,64])")
    assert want in _route_and_hist_kinds(text)
    est = stream_kernel.stream_vmem_estimate(m_rows, plan.block_rows, True)
    assert est <= stream_kernel.SCOPED_VMEM_LIMIT


def test_higgs_goss_like_sampled_iteration_compiles(iterations):
    """`_higgs_like` plus `data_sample_strategy=goss`, `top_rate` 0.2,
    `other_rate` 0.1, past the sampler's warm-up: the program the cell
    `higgs_goss_train` times.  The v5e compiler takes the histogram passes
    over the compact view (the analytic capacity, whole kernel blocks), the
    small-slot and factored-root calls at that length, `route_replay`
    over every row - its call under a name and a result type no
    `route_and_hist` pattern of the benchmark's readers matches - and the
    streaming compaction (`compact_rows`) that builds the view."""
    import re
    eng, text = iterations["higgs_goss"]
    assert "tpu_custom_call" in text
    assert eng._grow_params.int_hist and eng._pack_block == 4096
    assert eng._route_replay_fused()
    cap, n = eng._compact_cap, eng._packed.shape[1]
    assert 0 < cap < n and cap % eng._pack_block == 0
    kinds = _route_and_hist_kinds(text)
    assert {f"(s32[1,{cap}], s32[1792,128], f32[1,64])",
            f"(s32[1,{cap}], f32[1,128])", "s32[512,128]"} <= kinds
    # no pass of the sampled tree streams the whole table
    assert not any(f"[1,{n}]" in k for k in kinds)
    replay = re.findall(r"^\s*%route_replay[.\d]* = (.*?) custom-call\(",
                        text, re.M)
    assert [re.sub(r"\{[^}]*\}", "", r) for r in replay] == [f"s32[1,{n}]"]
    # the compact view is one Mosaic call under its own name (PR 38) ...
    compact = re.findall(r"^\s*%compact_rows[.\d]* = (.*?) custom-call\(",
                         text, re.M)
    assert [re.sub(r"\{[^}]*\}", "", c) for c in compact] == [
        f"(s8[32,{cap}], f32[8,{cap}])"]
    assert eng._stream_tiling.tile_groups == 0        # compact_kind: stream
    # ... and nothing sorts the table's rows: the partition never did since
    # PR 38, and since PR 40 the sampler's threshold is a select of count
    # passes (models/sample_strategy.py kth_largest)
    assert not re.findall(rf"\[{n}\]\S*(?:, \S+)*\) sort\(", text)
    # a pass counts its 15 candidates in ONE multi-output reduction and
    # makes no (N, digits) compare array, in a fusion's body or out of it
    assert re.search(r"= \((?:s32\[\]\S*, (?:/\*index=\d+\*/)?){14}s32\[\]"
                     r"\S*\) fusion\(", text)
    assert not re.findall(rf"\[(?:{n},(?:3|15)|(?:3|15),{n})\]", text)


@pytest.mark.parametrize("groups, dtype, block, blocks, cap_blocks", [
    # the cell higgs_goss_train: 31.5M rows -> the analytic capacity
    pytest.param(32, jnp.int8, 4096, 7690, 2880, id="higgs_goss_cell"),
    pytest.param(160, jnp.int8, 1024, 2048, 2048, id="g136_t1024_pad"),
    pytest.param(64, jnp.int32, 512, 4096, 1024, id="packed_words_t512"),
    pytest.param(32, jnp.int8, 256, 4096, 1536, id="t256_mask_block_padded"),
])
def test_compact_rows_compiles_at_real_shapes(tpu, groups, dtype, block,
                                              blocks, cap_blocks):
    """pallas/compact_kernel.py through Mosaic for the v5e: the per-chunk
    prefix in SMEM blocks, the byte views of the 32-bit rows, the aligned
    dynamic windows of the accumulator, the aliased zero results."""
    from lightgbm_tpu.pallas.compact_kernel import compact_rows
    n, cap = blocks * block, cap_blocks * block
    compiled = compact_rows.lower(
        jax.ShapeDtypeStruct((groups, n), dtype, sharding=tpu),
        jax.ShapeDtypeStruct((8, n), jnp.float32, sharding=tpu),
        mask_row=2, capacity=cap, block_rows=block).compile()
    assert "tpu_custom_call" in compiled.as_text()
    bins_c, w_c = compiled.out_info
    assert (bins_c.shape, bins_c.dtype) == ((groups, cap), dtype)
    assert (w_c.shape, w_c.dtype) == ((8, cap), jnp.float32)


def test_the_sampled_program_is_pinned(tpu):
    """scripts/lowered_iteration_digest.py's fifth line, `higgs_goss_like`:
    the lowered v5e sampled iteration hashes, outside debug locations, to
    what PR 40 left.  PR 37's (a2ab3ec) reads 786bb408... here, PR 38's and
    PR 39's a5238574...: PR 38 took the partition's `sort_key_val` and the
    two row gathers out for pallas/compact_kernel.py's one Mosaic call, and
    PR 40 the sampler's threshold sort for an exact select of eight count
    passes (models/sample_strategy.py kth_largest); the four dense lines
    are the parent's.  A PR that means to change the sampled program
    re-pins this line and says so."""
    import hashlib
    import sys
    digest, me = _digest_module(), sys.modules[__name__]
    eng, lowered = digest.lower_sampled(me, tpu, *digest.higgs_goss_like(me))
    assert eng._compact_cap == 4096
    text = digest.canonical(lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "32c82febfb5f3634333f7ffdac2a330d72fa853c72d63ab740ca8c97265229ff")


def test_the_bucketed_program_is_the_parents(tpu):
    """Rule 0 for the cell that must not move: the lowered v5e iteration of
    `mslr_like` over a table that takes the bucketed one-hot M-axis, as
    `mslr_train`'s does, keeps the compare-built one-hot and hashes, outside
    debug locations, to what PR 36's parent lowers it to
    (scripts/lowered_iteration_digest.py run from a `git archive` of
    5355464; PERF.md section 6).  A PR that means to change the bucketed
    program re-pins this line and says so."""
    import hashlib
    import sys
    digest = _digest_module()
    eng, lowered = _lower_iteration(
        tpu, *digest.mslr_like_bucketed(sys.modules[__name__]))
    assert eng._grow_params.bin_buckets is not None
    assert eng._poll_tiling["onehot_build"] == "compare"
    text = digest.canonical(lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "a1d992a2e47ae07858daf84608bb405355c68ef9d1b19414e58203424e801f48")


def test_four_chip_pieces_compile_at_the_cells_real_shard(topo, tpu):
    """`criteo_train_dp4`'s programs for the v5e 2x2 mesh at the cell's real
    shard (105.25M rows trained, 26,312,704 a chip): the table packed a
    shard at a time, the grower's mesh branch - the stream kernel inside
    shard_map, the histogram crossing in two limbs, the int32 slot-count
    psum - and the score update's gather kernel a shard.  The engine is a
    small one on four of this process's CPU devices (its layouts and grow
    parameters are what the chip's would be); shapes stand for the table."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.ops.grow import grow_tree
    from lightgbm_tpu.parallel.mesh import shard_map_rows
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for the small meshed engine")
    params, X, y, _ = _criteo_like()
    eng = lgb.Booster(dict(params, tree_learner="data", mesh_shape="data:4",
                           verbosity=-1), lgb.Dataset(X, label=y)).engine
    assert eng._mesh_stream and eng._use_leaf_gather_kernel
    rows = 105_250_816
    assert rows % (4 * eng._pack_block) == 0
    eng.dd = eng.dd._replace(bins=jax.ShapeDtypeStruct((rows, 67),
                                                       jnp.uint8))
    assert eng._resolved_int_hist() and eng._resolved_reduce_limbs() == 2
    gp = eng._grow_params._replace(hist_reduce_limbs=2)
    mesh = Mesh(np.array(topo.devices), ("data",))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def replicated(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype, P()), tree)

    bins = sds((rows, 67), jnp.uint8, P("data", None))
    per_row = sds((rows,), jnp.float32, P("data"))
    packed = sds((eng._packed.shape[0], rows), eng._packed.dtype,
                 P(None, "data"))
    grown = jax.jit(functools.partial(
        grow_tree, params=gp, mesh=mesh, row_axis="data",
        with_passes=True)).lower(
            bins, per_row, per_row, per_row, sds((67,), jnp.bool_, P()),
            layout=replicated(eng.dd.layout),
            routing=replicated(eng.dd.routing), packed=packed,
            gh_scales=sds((2,), jnp.float32, P())).compile()
    text = grown.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    shard = rows // 4
    assert f"(s32[1,{shard}], " in text          # the kernel runs a shard
    # two limbs of the 64-slot block and of the root's cross the mesh
    assert re.search(r"s32\[128,67,63,2\][^\n]* all-reduce", text)
    assert re.search(r"s32\[2,67,63,2\][^\n]* all-reduce", text)
    tree, leaf_id, passes = grown.out_info
    assert tree.leaf_count.dtype == jnp.int32
    assert tree.internal_count.dtype == jnp.int32
    assert passes.shape == (3,)
    # what one chip holds while a tree grows, beside its arguments
    mem = grown.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 * 2 ** 30

    pack = jax.jit(shard_map_rows(
        lambda b: stream_kernel.pack_bins_T(
            b, eng._pack_block, max_bins=63).bins_T,
        mesh, (P("data"),), P(None, "data"))).lower(bins).compile()
    assert pack.out_info.shape == packed.shape
    gather = jax.jit(shard_map_rows(
        lambda lid, values: stream_kernel.leaf_gather(lid, values), mesh,
        (P("data"), P()), P("data"))).lower(
            sds((rows,), jnp.int32, P("data")),
            sds((255,), jnp.float32, P())).compile()
    assert "tpu_custom_call" in gather.as_text()


def _route_and_hist_kinds(text):
    """The result types of a compiled program's `route_and_hist` calls."""
    import re
    calls = re.findall(r"^\s*%route_and_hist[.\d]* = (.*?) custom-call\(",
                       text, re.M)
    return {re.sub(r"\{[^}]*\}", "", c) for c in calls}


# the small-slot pass's calls (a round that splits one or two leaves) by
# program: result types of their own, under the jitted name
SMALL_KINDS = {
    # one tile: the fused call, the histogram block first
    "higgs": lambda n: {f"(s32[512,128], s32[1,{n}], f32[1,1])",
                        f"(s32[1024,128], s32[1,{n}], f32[1,2])"},
    "mslr": lambda n: {f"(s32[2304,128], s32[1,{n}], f32[1,1])",
                       f"(s32[4608,128], s32[1,{n}], f32[1,2])"},
    # tiled: the route pre-pass with its counts 8 wide, then the factored
    # call over grid (tile, row block), a 4-D block
    "epsilon": lambda n: {f"(s32[1,{n}], f32[1,8], s32[1,{n}])",
                          "s32[16,1,2048,128]", "s32[16,1,4096,128]"},
}


@pytest.mark.parametrize("name", sorted(SMALL_KINDS))
def test_small_slot_kernels_compile_under_their_own_result_types(iterations,
                                                                 name):
    """G = 28 T = 4096, G = 136 T = 1024 (bucketed) and the 128-group tile:
    the S = 1 and S = 2 small-slot kernels compile for the v5e inside the
    fused iteration's switch, every branch's call still named
    `%route_and_hist.N`, and none of the accepted trace readers' patterns for
    the 64-slot passes, the tiled sweeps or the root matches them - while the
    new reader matches them and nothing else."""
    import importlib.util
    import re
    from pathlib import Path
    eng, text = iterations[name]
    n = eng._packed.shape[1]
    kinds = _route_and_hist_kinds(text)
    assert SMALL_KINDS[name](n) <= kinds
    if name != "epsilon":
        m_rows = eng._stream_tiling.tile_m_rows
        assert f"(s32[1,{n}], s32[{m_rows},128], f32[1,64])" in kinds

    layers = Path(__file__).resolve().parents[1] / "benchmark" / "layers"

    def reader(stem):
        spec = importlib.util.spec_from_file_location(
            stem.replace(".", "_"), layers / f"{stem}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    lines = {re.sub(r"\{[^}]*\}", "", m[2]): m[1] + m[2]
             for m in re.finditer(
                 r"^\s*(%route_and_hist[.\d]* = )(.*?) custom-call\(", text,
                 re.M)}
    small = [lines[k] for k in SMALL_KINDS[name](n)]
    others = [v for k, v in lines.items() if k not in SMALL_KINDS[name](n)]
    roof, root = reader("hist_kernel_roofline"), reader("root_pass_ms_per_tree")
    new = reader("small_pass_ms_per_tree")
    tiles = (layers / "hist_tiles_roofline.py").read_text()
    sweeps = re.search(r'^SWEEPS = re.compile\(r"(.*)"\)$', tiles, re.M)[1]
    accepted = [roof.PASS, re.compile(sweeps), re.compile(root.FACTORED),
                re.compile(root.ONEHOT)]
    for line in small:
        assert not any(p.match(line) for p in accepted), line
        assert any(re.match(p, line)
                   for p in (new.FUSED, new.TILES, new.PREPASS)), line
        # the tiled pre-pass reads as a pre-pass to hist_tiles_roofline, and
        # is left out there by its width: 2 x 8 columns are not 128
        pre = re.match(new.PREPASS, line)
        assert not pre or "f32[1,8]" in line
    for line in others:
        assert not any(re.match(p, line)
                       for p in (new.FUSED, new.TILES, new.PREPASS)), line


def test_route_replay_compiles_at_2000_groups(tpu):
    """The fused route replay (GOSS / bagging with route_fusion, refit) over
    a table too wide to widen whole: hist_backend=auto sends such a table to
    the stream kernel too, so the replay must compile there."""
    bins_T = jax.eval_shape(
        lambda b: stream_kernel.pack_bins_T(b, 1024, max_bins=63).bins_T,
        jax.ShapeDtypeStruct((4096, 2000), jnp.uint8))
    assert bins_T.dtype == jnp.int8 \
        and bins_T.shape[0] > stream_kernel.WIDE_ROUTE_GROUPS
    rounds, L = 12, 256
    args = _abstract((np.zeros(bins_T.shape, np.int8),
                      np.zeros((rounds * stream_kernel.NUM_TAB, L),
                               np.float32),
                      np.zeros((), np.int32)), tpu)
    text = stream_kernel.route_replay.lower(
        *args, num_leaves=L, block_rows=1024,
        rounds_buf=rounds).compile().as_text()
    assert "tpu_custom_call" in text


def test_multiclass_k10_iteration_compiles(iterations):
    eng, text = iterations["multiclass"]
    assert "tpu_custom_call" in text
    assert not eng._grow_params.int_hist
    assert eng._use_batched_multiclass()


def test_predict_stream_compiles(tpu):
    """Booster.predict's device walk (basic._try_device_predict) at the
    trained-model shape: 255 leaves, packed i32 bins, 8 trees."""
    from lightgbm_tpu.pallas.predict_kernel import (CAT_DIGITS,
                                                    ROWS_PER_TREE,
                                                    predict_stream)
    n_trees, L = 8, 255
    args = _abstract((np.zeros((8, 20480), np.int32),
                      np.zeros((n_trees * ROWS_PER_TREE, L), np.float32),
                      np.zeros((CAT_DIGITS, 128), np.float32)), tpu)
    text = predict_stream.lower(*args, L, n_trees, 12).compile().as_text()
    assert "tpu_custom_call" in text


def test_serving_programs_compile(tpu, tmp_path):
    """serve_leaves (what a TPU serves with: its float64 is not IEEE, so
    accumulation stays on the host) and serve_predict (the f64 program a
    backend with real doubles runs)."""
    from lightgbm_tpu.serving import compiled as sc
    rs = np.random.RandomState(3)
    X = rs.randn(600, 28)
    with runtime.lowering_for("cpu"):           # train for real, on the CPU
        bst = lgb.train({"objective": "binary", "num_leaves": 31,
                         "verbosity": -1}, lgb.Dataset(
                             X, label=(X[:, 0] > 0).astype(float)),
                        num_boost_round=4)
    pred = sc.CompiledPredictor(bst._all_trees(), 1, 28, max_batch=64)
    rows = _abstract(pred._encode(X[:64]), tpu)
    pack = _abstract(pred._pack, tpu)
    sc._get_walk().lower(pack, *rows, max_depth=pred.max_depth).compile()
    with jax.enable_x64():
        lv = jax.ShapeDtypeStruct(pred._lv_dev.shape, jnp.float64,
                                  sharding=tpu)
        sc._get_score().lower(pack, lv, *rows, max_depth=pred.max_depth,
                              num_class=1).compile()


def test_over_limit_block_rows_rejected_before_the_compiler(tpu, monkeypatch):
    """A bf16 one-hot at G=136 sits on the 16 MiB scoped-VMEM limit: the
    compiler's own words are "Scoped allocation with size 17.36M and limit
    16.00M" for T=512 with a 128-slot histogram block, and 21.84M for
    T=1024 at the default 64 slots (12.80M, T=512, 64 slots still fits).
    The block-rows check says so first, and the tiers it picks stay under."""
    est = stream_kernel.stream_vmem_estimate
    limit = stream_kernel.SCOPED_VMEM_LIMIT
    m = 136 * 64
    assert stream_kernel.stream_block_rows(63, 136, False) == 256
    assert stream_kernel.stream_block_rows(63, 136, True) == 1024
    assert stream_kernel.stream_block_rows(63, 28, True) == 4096
    assert stream_kernel.stream_block_rows(63, 28, False) == 2048
    # a table too wide for one tile at any block size is cut, not refused:
    # 2,000 groups go 128 a tile at the 136-group table's block size
    assert tuple(stream_kernel.stream_tiling(63, 2000, True)) \
        == (1024, 128, 16, 8192)
    assert tuple(stream_kernel.stream_tiling(63, 2000, False)) \
        == (1024, 64, 32, 4096)
    assert est(8192, 1024, True) <= limit < est(2000 * 64, 256, True)
    assert est(m, 512, False) <= limit < est(m, 512, False, hist_channels=256)
    assert est(m, 512, False) <= limit < est(m, 1024, False)
    assert est(28 * 64, 2048, False) <= limit < est(28 * 64, 4096, False)
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "512")
    with pytest.raises(LightGBMError, match="scoped VMEM.*limit is 16 MiB"):
        stream_kernel.stream_block_rows(63, 136, False, hist_channels=256)
    assert stream_kernel.stream_block_rows(63, 136, False) == 512
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "1024")
    with pytest.raises(LightGBMError, match="scoped VMEM.*limit is 16 MiB"):
        stream_kernel.stream_block_rows(63, 136, False)
    assert stream_kernel.stream_block_rows(63, 136, True) == 1024  # int8 fits
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "512")      # tiles follow it
    assert tuple(stream_kernel.stream_tiling(63, 2000, True)) \
        == (512, 224, 9, 224 * 64)
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "1000")
    with pytest.raises(LightGBMError, match="multiple of 128"):
        stream_kernel.stream_block_rows(63, 28, True)
