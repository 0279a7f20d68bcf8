"""The grouped-matmul kernels over a pass's occupancy, and the tiles they
choose from the call's shapes.

A pass (``MoELayer.held_rows``) is sized above the rows a step sends, so
the staircase's trailing items hold no row. Those items skip the MXU and
repeat the last real item's blocks; the forward and ``dlhs`` must still
give, row for row, what the staircase sum at the same tiles gives (the
form every earlier kernel computed, padding items and all), and
``choose_tiles`` must keep a step inside the VMEM budget at the expert
cells' shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.pallas import _compat
from paddle_tpu.kernels.pallas import grouped_matmul as gm

# (group sizes, rows of the pass): every group empty, one group, the
# rows sent at a quarter, half and all of the pass, uneven groups, rows
# past the last group
OCCUPANCY = {
    "empty": ([0, 0, 0, 0], 64),
    "one_group": ([0, 0, 37, 0], 64),
    "quarter": ([5, 3, 0, 8], 64),
    "half": ([9, 0, 14, 9], 64),
    "full": ([16, 17, 15, 16], 64),
    "uneven": ([1, 50, 0, 2, 0, 7], 96),
    "rows_past": ([3, 0, 5, 1, 0, 7], 21),
}


def _staircase_sum(lhs, rhs, sizes, tm, tn, transpose_rhs=False):
    """[sum(sizes), m]: each row tile's items in staircase order, each
    column block's product of the whole tile against its group's weight
    block, masked to the group's rows and added to a float32
    accumulator, stored in lhs's dtype."""
    rhs = jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs
    n, m = lhs.shape[0], rhs.shape[2]
    ends = np.cumsum(sizes)
    starts = ends - np.asarray(sizes)
    x = jnp.pad(lhs, ((0, -n % tm), (0, 0)))
    out = []
    for tile in range(-(-int(ends[-1]) // tm)):
        rows = tile * tm + np.arange(tm)[:, None]
        blocks = []
        for j in range(m // tn):
            acc = jnp.zeros((tm, tn), jnp.float32)
            for g, (lo, hi) in enumerate(zip(starts, ends)):
                if lo == hi or hi <= tile * tm or lo >= (tile + 1) * tm:
                    continue
                contrib = jax.lax.dot_general(
                    x[tile * tm:(tile + 1) * tm],
                    rhs[g, :, j * tn:(j + 1) * tn],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc += jnp.where((rows >= lo) & (rows < hi), contrib, 0.0)
            blocks.append(acc.astype(lhs.dtype))
        out.append(jnp.concatenate(blocks, axis=1))
    if not out:
        return jnp.zeros((0, m), lhs.dtype)
    return jnp.concatenate(out)[:int(ends[-1])]


def _operands(sizes, rows, k=24, m=256, seed=0):
    """Small whole numbers as float32: every product and sum is exact, so
    any difference is a row added, lost or masked wrongly, whatever order
    a dot sums in."""
    ks = jax.random.split(jax.random.key(seed), 3)
    draw = lambda key, shape: jax.random.randint(
        key, shape, -8, 9).astype(jnp.float32)
    return (draw(ks[0], (rows, k)), draw(ks[1], (len(sizes), k, m)),
            draw(ks[2], (rows, m)), jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("tm,tn", [(16, 128), (8, 256)])
@pytest.mark.parametrize("case", list(OCCUPANCY))
def test_forward_and_dlhs_are_the_staircase_sum_bit_for_bit(case, tm, tn):
    sizes, rows = OCCUPANCY[case]
    lhs, rhs, g, gs = _operands(sizes, rows)
    sent = sum(sizes)
    out = gm._gmm_pallas_raw(lhs, rhs, gs, None, tm, tn)
    np.testing.assert_array_equal(
        out[:sent], _staircase_sum(lhs, rhs, sizes, tm, tn))
    # dlhs: g [rows, 256] against each expert's [24, 256] transposed;
    # its one column block is the whole 24
    dlhs = gm._gmm_pallas_raw(g, rhs, gs, None, tm, None,
                              transpose_rhs=True)
    np.testing.assert_array_equal(
        dlhs[:sent], _staircase_sum(g, rhs, sizes, tm, 24, True))


@pytest.mark.parametrize("case", list(OCCUPANCY))
def test_an_expert_without_rows_gets_a_zero_weight_gradient(case):
    sizes, rows = OCCUPANCY[case]
    lhs, rhs, g, gs = _operands(sizes, rows, seed=1)
    # rows past the last group hold anything, NaN included
    lhs = jnp.where(jnp.arange(rows)[:, None] < sum(sizes), lhs, jnp.nan)
    drhs = np.asarray(gm._drhs_pallas(lhs, g, gs, len(sizes), 16,
                                      jnp.float32))
    empty = np.asarray(sizes) == 0
    assert (drhs[empty] == 0).all()
    assert np.isfinite(drhs).all()
    ends = np.cumsum(sizes)
    for e, (lo, hi) in enumerate(zip(ends - np.asarray(sizes), ends)):
        np.testing.assert_allclose(
            drhs[e], np.asarray(lhs[lo:hi]).T @ np.asarray(g[lo:hi]),
            rtol=0, atol=0)


@pytest.mark.parametrize("case", list(OCCUPANCY))
@pytest.mark.parametrize("tm", [8, 32])
def test_staircase_items_counts_the_metadatas_active_items(case, tm):
    sizes, rows = OCCUPANCY[case]
    num_row_tiles = -(-rows // tm)
    for visit_empty in (False, True):
        _, _, lo, hi = gm._group_metadata(
            jnp.asarray(sizes, jnp.int32), num_row_tiles, tm, visit_empty)
        active, total = gm.staircase_items(sizes, rows, tm)
        assert active == int((np.asarray(lo) < np.asarray(hi)).sum())
        assert total == lo.shape[0]
    # passes side by side count each
    both = gm.staircase_items([sizes, sizes], rows, tm)
    assert list(both[0]) == [active] * 2 and list(both[1]) == [total] * 2


@pytest.mark.parametrize("case", ["quarter", "uneven", "empty"])
def test_padding_items_repeat_the_last_real_items_blocks(case):
    """No block index moves past the last real item: padding fetches
    nothing, stores nothing, and the last real tile is stored at the
    final grid step."""
    sizes, rows = OCCUPANCY[case]
    tm = 16
    for visit_empty in (False, True):
        tile, gid, lo, hi = (np.asarray(a) for a in gm._group_metadata(
            jnp.asarray(sizes, jnp.int32), -(-rows // tm), tm, visit_empty))
        real = int(gm.staircase_items(sizes, rows, tm)[0])
        if visit_empty:
            real += int((np.asarray(sizes) == 0).sum())
        last = max(real - 1, 0)
        assert (lo[real:] == hi[real:]).all()
        assert (tile[real:] == tile[last]).all()
        assert (gid[real:] == gid[last]).all()


# the three expert cells: (rows of a pass, held experts, hidden, expert)
CELLS = {
    "mellum2": (65536, 16, 2304, 896),
    "kanana2": (24576, 16, 2048, 768),
    "qwen3next": (40960, 32, 2048, 512),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_choose_tiles_at_the_expert_cells_shapes(cell):
    n, e, d, f = CELLS[cell]
    for k, m in ((d, f), (f, d)):
        for kernel in gm.KERNELS:
            # dlhs reads the forward's output width and writes its depth
            kk, mm = (m, k) if kernel == gm.DLHS else (k, m)
            tm, tk, tn = gm.choose_tiles(n, kk, mm, e, 2, kernel)
            # one column block over the whole width, the whole depth
            assert (tk, tn) == (kk, mm)
            assert gm._vmem_bytes(kernel, tm, tk, tn, 2) \
                <= gm.VMEM_BUDGET_BYTES < gm._VMEM_LIMIT_BYTES
            assert tm in gm.ROW_TILES
            assert e * tm <= gm.BOUNDARY_SHARE * n or tm == gm.ROW_TILES[0]


def test_choose_tiles_narrows_what_does_not_fit():
    # an expert of 7168 x 2048 bf16 (29 MB) cannot be one block
    tm, tk, tn = gm.choose_tiles(65536, 7168, 2048, 8, 2, gm.FWD)
    assert tk == 7168 and tn < 2048 and 2048 % tn == 0
    assert 7168 * tn * 2 <= 8 * 2**20
    assert gm._vmem_bytes(gm.FWD, tm, tk, tn, 2) <= gm.VMEM_BUDGET_BYTES
    tm, tk, tn = gm.choose_tiles(65536, 7168, 2048, 8, 2, gm.DRHS)
    assert tk * tn * 4 <= 8 * 2**20
    # a pass of few rows an expert keeps the narrowest row tile
    assert gm.choose_tiles(4096, 2048, 512, 32, 2, gm.FWD)[0] == 128
    # an odd width is one block
    assert gm.choose_tiles(64, 24, 40, 4, 4, gm.FWD)[1:] == (24, 40)


def test_gmm_tiles_series_moves_once_a_traced_call():
    sizes, rows = OCCUPANCY["half"]
    lhs, rhs, _, gs = _operands(sizes, rows)
    moved = lambda before: {
        key: c - before.get(key, 0)
        for key, c in _compat.gmm_tiles().items()
        if c != before.get(key, 0)}
    chosen = {kernel: (kernel, *gm.choose_tiles(rows, k, m, 4, 4, kernel))
              for kernel, k, m in ((gm.FWD, 24, 256), (gm.DLHS, 256, 24),
                                   (gm.DRHS, 24, 256))}
    # the row tile is cut to the rows there are
    chosen = {kernel: (name, min(tm, rows), tk, tn)
              for kernel, (name, tm, tk, tn) in chosen.items()}

    fwd = jax.jit(lambda a, b: gm.grouped_matmul(a, b, gs, impl="pallas"))
    before = _compat.gmm_tiles()
    fwd(lhs, rhs)
    fwd(lhs, rhs)  # compiled: not traced again
    assert moved(before) == {chosen[gm.FWD]: 1}

    before = _compat.gmm_tiles()
    jax.jit(jax.grad(lambda a, b: gm.grouped_matmul(
        a, b, gs, impl="pallas").sum(), argnums=(0, 1)))(lhs, rhs)
    assert moved(before) == {chosen[kernel]: 1 for kernel in gm.KERNELS}

    # a caller's tiles win: tm for all three, tn for the forward
    before = _compat.gmm_tiles()
    jax.grad(lambda a, b: gm.grouped_matmul(
        a, b, gs, impl="pallas", tm=16, tn=128).sum(),
        argnums=(0, 1))(lhs, rhs)
    assert moved(before) == {
        (gm.FWD, 16, 24, 128): 1, (gm.DLHS, 16, 256, 24): 1,
        (gm.DRHS, 16, 24, 256): 1}
    from paddle_tpu.observability import get_registry

    assert 'paddle_tpu_kernels_gmm_tiles{kernel="grouped_matmul"' \
        in get_registry().render_prometheus()
