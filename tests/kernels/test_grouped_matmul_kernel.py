"""The Pallas kernels of the experts' grouped matmuls
(``ops/pallas/grouped_matmul.py``) in interpret mode on the CPU: the three
modes against ``lax.ragged_dot`` (values) and against plain reverse mode of
it (both gradients), in float32 and bfloat16, with group ends inside a tile
and on its edges, groups of no rows, rows of no group that hold inf and NaN
going in and zeros coming out; at the widths of each of the benchmark's
eight expert cells with the rows scaled down; the tile plan as a table; the
schedule's invariants; and the expert layer with the kernels against the
layer without them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_galvatron_tpu.ops.pallas import grouped_matmul as gm

pytestmark = pytest.mark.kernels


# rows, contracted width of the forward product, its columns
M, K, N = 640, 256, 384
# group sizes: a tile is 512 rows here (then one of 128), a piece 128
GROUPS = {
    "ends_inside_tiles": [100, 156, 200, 100, 84],
    "ends_on_tile_edges": [128, 384, 0, 128, 0],
    "a_group_of_zero_rows": [100, 0, 156, 284, 100],
    "first_and_last_empty": [0, 300, 340, 0],
    "rows_of_no_group": [100, 0, 156, 100, 20],
    "a_tile_of_no_group": [60, 40],
    "every_group_empty": [0, 0, 0],
    "groups_of_a_few_rows": [1, 2, 3, 4, 5, 0, 6],
}


def _reference(mode, a, b, sizes, out_dtype):
    """``lax.ragged_dot`` and JAX's own transposes of it."""
    G = sizes.shape[0]
    product = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=out_dtype)
    if mode == "fwd":
        return product(a, b)
    if mode == "drows":
        like = jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), a.dtype)
        return jax.linear_transpose(lambda r: product(r, b), like)(a)[0]
    like = jax.ShapeDtypeStruct((G, a.shape[1], b.shape[1]), a.dtype)
    return jax.linear_transpose(lambda w: product(a, w), like)(b)[0]


def _operands(mode, sizes, dtype, rows=M, k=K, n=N, garbage=True):
    """(what the kernel is handed, the same with zeros in the rows of no
    group): rows of no group hold NaN on one side and inf on the other."""
    G, total = len(sizes), sum(sizes)
    keys = jax.random.split(jax.random.key(rows + 7 * G), 3)
    x = jax.random.normal(keys[0], (rows, k), dtype)
    w = jax.random.normal(keys[1], (G, k, n), dtype)
    g = jax.random.normal(keys[2], (rows, n), dtype)
    mine = (jnp.arange(rows) < total)[:, None]
    clean = {"fwd": (jnp.where(mine, x, 0), w),
             "drows": (jnp.where(mine, g, 0), w),
             "dweights": (jnp.where(mine, x, 0), jnp.where(mine, g, 0))}[mode]
    if not garbage:
        return clean, clean, mine
    dirty = {"fwd": (jnp.where(mine, x, jnp.nan), w),
             "drows": (jnp.where(mine, g, jnp.inf), w),
             "dweights": (jnp.where(mine, x, jnp.nan),
                          jnp.where(mine, g, -jnp.inf))}[mode]
    return dirty, clean, mine


def _close(got, want, dtype, what=""):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    scale = float(np.abs(want).max()) or 1.0
    if dtype == "bfloat16":
        # one rounding of a float32 sum to 8 bits, summed in another order
        np.testing.assert_allclose(got, want, rtol=2 ** -7,
                                   atol=2 ** -8 * scale, err_msg=what)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=what)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", gm.MODES)
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_a_mode_is_ragged_dots_and_writes_zeros_in_rows_of_no_group(
        case, mode, dtype):
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    (a, b), (a0, b0), mine = _operands(mode, GROUPS[case], dtype)
    out_dtype = jnp.float32 if mode == "fwd" else jnp.dtype(dtype)
    got = gm.grouped_matmul(mode, a, b, sizes, out_dtype, interpret=True)
    want = _reference(mode, a0, b0, sizes, out_dtype)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    if mode != "dweights":
        # what ragged_dot leaves in a row of no group is unspecified; the
        # kernels write zeros there
        want = jnp.where(mine, want, 0)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32)[sum(GROUPS[case]):], 0.0)
    _close(got, want, dtype if mode != "fwd" else "float32"
           if dtype == "float32" else "bfloat16")


# the eight expert cells: groups a device holds, hidden width, the first
# product's columns (gate | up where gated), the second product's rows, as
# the expert layer hands them to its grouped matmuls (Nemotron-H's 2688 and
# 1856 arrive padded by ``moe._whole_tiles``), and a first chunk's rows
CELLS = {
    "olmoe_c1_s4k": (64, 2048, 2048, 1024, 32768),
    "mellum2_c4_ep4": (16, 2304, 1792, 896, 40960),
    "lfm2moe_c1_s8k": (8, 2048, 3072, 1536, 5120),
    "xing4_c1_b1_s4k": (8, 3584, 2048, 1024, 2560),
    "kimilin_c1_b1_s8k": (8, 2304, 2048, 1024, 2560),
    "laguna_c1_b1": (8, 3072, 2048, 1024, 3200),
    "kimivl_c1_b1_s4k": (8, 2048, 2816, 1408, 3840),
    "nemotronh_c1_s8k": (8, 3072, 2048, 2048, 7680),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_plan_fits_every_cell_and_no_width_off_the_lane_tiles(cell):
    G, H, W, F, rows = CELLS[cell]
    for k, n in ((H, W), (F, H)):
        plan = gm.tile_plan(rows, G, k, n, jnp.bfloat16)
        assert plan is not None, (k, n)
        tm, sub, tn = plan.fwd
        assert tm % sub == 0 and sub % 8 == 0 and n % tn == 0 == tn % 128
        assert k * tn * 2 <= gm.WEIGHT_TILE_BYTES
        assert plan.drows[:2] == (tm, sub)
        assert k % plan.drows[2] == 0 == plan.drows[2] % 128
        assert n * plan.drows[2] * 2 <= gm.WEIGHT_TILE_BYTES
        _, _, dk, dn = plan.dweights
        assert k % dk == 0 == dk % 128 and n % dn == 0 == dn % 128
        assert dk * dn * 4 <= gm.ACC_BYTES
        # mode 3 stays bound by the MXU: FLOPs a byte of its two operands
        assert dk * dn / (dk + dn) >= 256 or dk == k
    # a pass behind the first chunk, an eighth of it here, has a plan too
    assert gm.tile_plan(max(rows // 8, 128), G, H, W, jnp.bfloat16)
    # a width that is no whole number of lane tiles (the tests' models),
    # fewer rows than a piece, a dtype of one byte: ragged_dot's
    for k, n in ((96, 128), (128, 96), (H, 1856)):
        assert gm.tile_plan(rows, G, k, n, jnp.bfloat16) is None
    assert gm.tile_plan(64, G, H, W, jnp.bfloat16) is None
    assert gm.tile_plan(rows, G, H, W, jnp.int8) is None
    assert gm.grouped_matmul(
        "fwd", jnp.zeros((256, 96)), jnp.zeros((2, 96, 128)),
        jnp.array([100, 100], jnp.int32), jnp.float32) is None


def test_what_a_plan_hands_down_leaves_a_small_product_to_ragged_dot():
    """``make_grouped_matmul``: a product of fewer rows than ``PLAN_ROWS``
    (a counted pass of a thin share) answers None, one of as many runs the
    kernel."""
    grouped = gm.make_grouped_matmul(None, interpret=True)
    sizes = jnp.array([300, 0, 212], jnp.int32)
    w = jnp.ones((3, 128, 128), jnp.float32)
    rows = jnp.ones((gm.PLAN_ROWS, 128), jnp.float32)
    assert grouped("fwd", rows[:-8], w, sizes, jnp.float32) is None
    got = grouped("fwd", rows, w, sizes, jnp.float32)
    np.testing.assert_array_equal(got[:512], 128.0)
    np.testing.assert_array_equal(got[512:], 0.0)


def _plain(mode, a, b, sizes):
    """The products group by group in float32 numpy."""
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    ends = np.cumsum(sizes)
    spans = list(zip(ends - sizes, ends))
    if mode == "dweights":
        return np.stack([a[lo:hi].T @ b[lo:hi] for lo, hi in spans])
    out = np.zeros((a.shape[0], b.shape[2 if mode == "fwd" else 1]),
                   np.float32)
    for (lo, hi), w in zip(spans, b):
        out[lo:hi] = a[lo:hi] @ (w if mode == "fwd" else w.T)
    return out


@pytest.mark.parametrize("mode", gm.MODES)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_widths_and_groups_with_the_rows_scaled_down(cell, mode):
    """Each cell's widths and its count of groups (OLMoE's 64 cut to 8: 64
    matrices of 2048 x 2048 are half a GiB), the plan's own tiles, 128 rows
    of which three groups own 100: the first product forward and to the
    rows, the second to the weights, in the cell's bfloat16."""
    G, H, W, F, _ = CELLS[cell]
    G = min(G, 8)
    k, n = (F, H) if mode == "dweights" else (H, W)
    sizes = [0] * G
    sizes[1], sizes[G // 2], sizes[-1] = 40, 33, 27
    (a, b), (a0, b0), mine = _operands(mode, sizes, "bfloat16", rows=128,
                                       k=k, n=n)
    got = gm.grouped_matmul(mode, a, b, jnp.asarray(sizes, jnp.int32),
                            jnp.bfloat16, interpret=True)
    _close(got, _plain(mode, a0, b0, np.asarray(sizes)), "bfloat16")


@pytest.mark.parametrize("empty_visits", [False, True])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_the_schedule_visits_every_row_once_and_tiles_in_order(
        case, empty_visits):
    sizes, tm = GROUPS[case], 128
    group, tile, out_tile, lo, hi = (np.asarray(t) for t in gm.schedule(
        jnp.asarray(sizes, jnp.int32), M, tm, empty_visits))
    assert len(group) == M // tm + len(sizes) - 1
    ends = np.cumsum(sizes)
    owner = np.full(M, -1)
    for g, t, l, h in zip(group, tile, lo, hi):
        if h > l:
            assert (owner[t * tm + l:t * tm + h] == -1).all()
            owner[t * tm + l:t * tm + h] = g
    want = np.searchsorted(ends, np.arange(M), side="right")
    np.testing.assert_array_equal(
        owner, np.where(np.arange(M) < ends[-1], want, -1))
    # a group's steps and a tile's steps are consecutive: the weights'
    # block and the output block each change and never come back
    for ids in (group, out_tile):
        changes = ids[1:][ids[1:] != ids[:-1]]
        assert len(set(changes.tolist())) == len(changes)
    assert (np.diff(out_tile) >= 0).all() and (np.diff(group) >= 0).all()
    live = lo >= 0
    if empty_visits:
        # mode 3 writes every group's block, a group of no rows its zeros
        assert set(group.tolist()) >= set(range(len(sizes)))
    else:
        # modes 1 and 2 write every row tile; no step is a group's of no rows
        assert set(out_tile[live].tolist()) == set(range(M // tm))
        assert all(sizes[g] for g, l, h in zip(group, lo, hi) if h > l)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_expert_layer_with_the_kernels_is_the_layer_without(dtype):
    """``moe._grouped_matmul`` handed the kernels (interpret mode) against
    itself on ``lax.ragged_dot``: the product and both gradients through
    its ``custom_vjp``, with rows of no group behind the last group; and a
    width the kernels have no tile for stays ``ragged_dot``'s."""
    from hetu_galvatron_tpu.models import moe

    kernels = functools.partial(gm.grouped_matmul, interpret=True)
    sizes = jnp.asarray(GROUPS["rows_of_no_group"], jnp.int32)
    (x, w), _, mine = _operands("fwd", GROUPS["rows_of_no_group"], dtype,
                                garbage=False)
    cot = jax.random.normal(jax.random.key(3), (M, N), jnp.float32)

    def loss(x, w, grouped):
        y = moe._grouped_matmul(x, w, sizes, jnp.float32, grouped)
        return jnp.sum(jnp.where(mine, y, 0.0) * cot)

    want = jax.grad(loss, argnums=(0, 1))(x, w, None)
    got = jax.grad(loss, argnums=(0, 1))(x, w, kernels)
    for name, a, b in zip(("rows", "weights"), got, want):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        _close(jnp.where(mine, a, 0) if name == "rows" else a,
               jnp.where(mine, b, 0) if name == "rows" else b, dtype, name)
    # the kernels' names are in the program where they run, and only there
    program = lambda x, w: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda x, w: loss(x, w, kernels), argnums=(0, 1)))(x, w))
    text = program(x, w)
    assert text.count("pallas_call") == 3 and "ragged_dot" not in text
    assert all(f"name={name}" in text for name in gm.CALLS)
    narrow = program(x[:, :96], w[:, :96])
    assert (narrow.count("ragged_dot_general[") == 3
            and "pallas_call" not in narrow)
