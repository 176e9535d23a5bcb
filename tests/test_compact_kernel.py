"""pallas/compact_kernel.py against the sorted route it replaced.

The streaming compaction kernel (interpreted here; tests/test_tpu_aot_compile
compiles it for the v5e, chip_smoke.py runs it there) must give, column for
column and bit for bit, what `jnp.take(x, plan_sample_rows(mask,
capacity).perm, axis=1)` gave on the live columns — the stable order the
model-string identities of tests/test_sample_compact.py rest on — and zeros
behind them.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.compact import (compact_transposed_view,
                                      plan_sample_rows)
from lightgbm_tpu.pallas.compact_kernel import (CHUNK, compact_kind,
                                                compact_rows)

T = 256                      # rows a kernel block: two chunks


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


def _operands(mask, rs, bins_dtype=np.int8, groups=32, channels=8,
              mask_row=2):
    """A packed table and weight rows over `mask` (0/1, one a row): bins of
    every byte value, weights no sum could vouch for."""
    n = len(mask)
    info = np.iinfo(bins_dtype)
    bins = rs.randint(info.min, info.max, (groups, n)).astype(bins_dtype)
    w = (rs.standard_normal((channels, n)) * mask).astype(np.float32)
    w[mask_row] = mask
    return jnp.asarray(bins), jnp.asarray(w)


def _assert_is_the_stable_take(bins_T, w_T, mask_row, capacity, got):
    """`got` == take by the stable permutation on the live columns (the
    in-bag rows of rank >= capacity dropped as perm[:capacity] drops them),
    zero bytes from the in-bag count on, and the count of live columns."""
    plan = plan_sample_rows(w_T[mask_row], capacity)
    live = min(int(plan.nc), capacity)
    perm = np.asarray(plan.perm)[:live]
    *got, placed = got
    assert np.asarray(placed).tolist() == [live]
    for out, src in zip(got, (bins_T, w_T)):
        assert out.shape == (src.shape[0], capacity) and out.dtype == src.dtype
        assert np.array_equal(_bits(out[:, :live]),
                              _bits(np.asarray(src)[:, perm]))
        assert not _bits(out[:, live:]).any()
    return live


def _clustered(n, rs):
    """In-bag rows in the first third alone: full blocks, then empty ones."""
    return (np.arange(n) < n // 3).astype(np.float32)


def _spilling(n, rs):
    """161 in-bag rows a block of 256: every input block ends inside an
    output block and most spill into the next."""
    return (np.arange(n) % T < 161).astype(np.float32)


def _share(p):
    return lambda n, rs: (rs.rand(n) < p).astype(np.float32)


def _whole_blocks(n, rs):
    """An in-bag count that is an exact multiple of the block (3 * T)."""
    m = np.zeros(n, np.float32)
    m[rs.permutation(n)[:3 * T]] = 1
    return m


def _ragged(n, rs):
    """A table whose real rows end inside a chunk: 37 rows past the last
    whole one, zero-weight padding to the block behind them."""
    m = (rs.rand(n) < 0.5).astype(np.float32)
    m[n - T + CHUNK + 37:] = 0
    m[n - T + CHUNK:n - T + CHUNK + 37] = 1
    return m


@pytest.mark.parametrize("make_mask, capacity", [
    pytest.param(_share(0.0), 2 * T, id="share_0"),
    pytest.param(_share(0.3), 4 * T, id="share_0.3"),
    pytest.param(_share(1.0), 8 * T, id="share_1_pad_capacity"),
    pytest.param(_clustered, 4 * T, id="clustered_first_third"),
    pytest.param(_spilling, 6 * T, id="a_spill_every_block"),
    pytest.param(_whole_blocks, 4 * T, id="count_a_multiple_of_the_block"),
    pytest.param(_whole_blocks, 3 * T, id="count_fills_the_capacity"),
    pytest.param(_share(0.6), 2 * T, id="count_over_capacity_drops_the_rest"),
    pytest.param(_share(1.0), T, id="all_rows_one_block_of_capacity"),
    pytest.param(_ragged, 5 * T, id="rows_end_inside_a_chunk"),
])
def test_kernel_is_the_stable_take(make_mask, capacity):
    rs = np.random.RandomState(3)
    n = 8 * T
    bins_T, w_T = _operands(make_mask(n, rs), rs)
    got = compact_rows(bins_T, w_T, mask_row=2, capacity=capacity,
                       block_rows=T)
    _assert_is_the_stable_take(bins_T, w_T, 2, capacity, got)


@pytest.mark.parametrize("kind", ["normals", "subnormals", "huge",
                                  "specials"])
def test_float_rows_come_back_bit_for_bit(kind):
    """No finite float32 is rounded on its way through the dot: the rows go
    as bytes.  (Signed zeros, infinities and NaN payloads survive too.)"""
    rs = np.random.RandomState(5)
    n = 4 * T
    mask = (rs.rand(n) < 0.4).astype(np.float32)
    weights = {
        "normals": rs.standard_normal((8, n)),
        "subnormals": rs.standard_normal((8, n)) * 1e-41,
        "huge": rs.standard_normal((8, n)) * 1e30,
        "specials": rs.choice(np.array(
            [-0.0, np.inf, -np.inf, np.nan, 1.5, np.float32(2 ** -149)],
            np.float32), (8, n)),
    }[kind]
    bins_T, w_T = _operands(mask, rs)
    # the weights as given on the in-bag rows (x * 1 keeps every bit)
    w_T = jnp.where(jnp.asarray(mask) > 0, jnp.asarray(weights, jnp.float32),
                    0).at[2].set(jnp.asarray(mask))
    got = compact_rows(bins_T, w_T, mask_row=2, capacity=2 * T, block_rows=T)
    live = _assert_is_the_stable_take(bins_T, w_T, 2, 2 * T, got)
    assert live > T
    want = np.asarray(w_T)[:, np.nonzero(mask)[0]].view(np.int32)
    assert np.array_equal(np.asarray(got[1])[:, :live].view(np.int32), want)


@pytest.mark.parametrize("bins_dtype, groups, channels, mask_row, block", [
    pytest.param(np.int8, 32, 8, 2, 256, id="u8_layout_c8_mask2"),
    pytest.param(np.int8, 64, 16, 6, 256, id="u8_layout_c16_mask6"),
    pytest.param(np.int32, 8, 8, 2, 256, id="packed_words_c8_mask2"),
    pytest.param(np.int32, 16, 16, 6, 128, id="packed_words_one_chunk_blocks"),
    pytest.param(np.int8, 32, 8, 2, 1024, id="u8_layout_block_1024"),
])
def test_operand_shapes(bins_dtype, groups, channels, mask_row, block):
    """C and the mask row are arguments (grow_tree_k: mask_row = 2K over a
    wider w_T), and both layouts pack_bins_T gives go through as bytes."""
    rs = np.random.RandomState(7)
    n = 4 * block
    bins_T, w_T = _operands((rs.rand(n) < 0.35).astype(np.float32), rs,
                            bins_dtype, groups, channels, mask_row)
    got = compact_rows(bins_T, w_T, mask_row=mask_row, capacity=2 * block,
                       block_rows=block)
    _assert_is_the_stable_take(bins_T, w_T, mask_row, 2 * block, got)


def test_rows_and_capacity_must_be_whole_blocks():
    bins_T, w_T = _operands(np.ones(2 * T, np.float32),
                            np.random.RandomState(0))
    with pytest.raises(ValueError, match="multiples of the block"):
        compact_rows(bins_T, w_T, mask_row=2, capacity=T + CHUNK,
                     block_rows=T)
    with pytest.raises(ValueError, match="multiple of the stream kernel"):
        compact_transposed_view(bins_T, w_T, 2, T + CHUNK, T)


@pytest.mark.parametrize("tile_groups, kind", [(0, "stream"), (128, "take")])
def test_the_route_is_chosen_on_the_tiling(tile_groups, kind):
    """A table of one M-tile streams; a tiled one keeps the sort and the
    gathers (its columns past the in-bag count hold out-of-bag rows under
    zero weights, as they always did).  The live columns are the same."""
    rs = np.random.RandomState(11)
    n = 4 * T
    bins_T, w_T = _operands((rs.rand(n) < 0.3).astype(np.float32), rs)
    assert compact_kind(tile_groups) == kind
    b_c, w_c, placed = compact_transposed_view(bins_T, w_T, 2, 2 * T, T,
                                               tile_groups=tile_groups)
    plan = plan_sample_rows(w_T[2], 2 * T)
    nc, perm = int(plan.nc), np.asarray(plan.perm)
    assert np.asarray(placed).tolist() == [nc]
    assert np.array_equal(np.asarray(b_c)[:, :nc],
                          np.asarray(bins_T)[:, perm[:nc]])
    assert np.array_equal(_bits(w_c[:, :nc]), _bits(np.asarray(w_T)[:, perm[:nc]]))
    assert not np.asarray(w_c)[:, nc:].any()
    # zero bins behind the in-bag rows are the kernel's; the take's tail
    # holds the out-of-bag rows it sorted there
    assert np.asarray(b_c)[:, nc:].any() == (kind == "take")


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four devices")
@pytest.mark.parametrize("shares", [(0.3, 0.3, 0.3, 0.3), (0.9, 0.0, 0.4, 1.0)],
                         ids=["even_shards", "uneven_shards_one_overflows"])
def test_every_shard_compacts_its_own_rows(shares):
    """Under the row mesh a shard's view is its own rows' take, nothing
    crosses devices; a shard over the capacity drops its own last rows."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rs = np.random.RandomState(13)
    shard, capacity = 4 * T, 3 * T
    mask = np.concatenate([(rs.rand(shard) < p) for p in shares]).astype(
        np.float32)
    bins_T, w_T = _operands(mask, rs)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P(None, "data"))
    b_c, w_c, placed = jax.jit(lambda b, w: compact_transposed_view(
        b, w, 2, capacity, T, mesh=mesh, row_axis="data"))(
            jax.device_put(bins_T, rows), jax.device_put(w_T, rows))
    assert b_c.shape == (32, 4 * capacity) and w_c.shape == (8, 4 * capacity)
    assert placed.shape == (4,)
    for d in range(4):
        mine = slice(d * shard, (d + 1) * shard)
        _assert_is_the_stable_take(
            bins_T[:, mine], w_T[:, mine], 2, capacity,
            (np.asarray(b_c)[:, d * capacity:(d + 1) * capacity],
             np.asarray(w_c)[:, d * capacity:(d + 1) * capacity],
             np.asarray(placed)[d:d + 1]))
