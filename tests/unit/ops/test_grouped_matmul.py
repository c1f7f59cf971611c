"""``ops/pallas/grouped_matmul.py``: the two small-group kernels in interpret
mode against ``jax.lax.ragged_dot`` / ``ragged_dot_general`` in float32 --
group sizes that are data (empty groups, a group smaller than a block, groups
that are no multiple of the tile, fewer rows than the buffer with the rows of
no group reading exactly zero, every row in one group, the compact buffer's
and the fallback's lengths) at each cell's ``(A, B)`` scaled down --, the
visit table, the gradient of ``mixtral._sorted_experts`` through the kernels
against the ``ragged_dot`` path's, and the rule that chooses between them.
(The v5e compile at the cells' shapes is ``test_tpu_compile.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.models.mixtral as mx
from deepspeed_tpu.ops.pallas import grouped_matmul as gm

BF16 = jnp.bfloat16
G, ROWS, BLOCK = 4, 16, 8

#: each cell's expert ``(hidden, intermediate)`` over 16
CELLS = {"mellum2_8k": (144, 56), "olmoe_4k": (128, 64),
         "kimi_8k": (128, 88), "zaya_8k": (128, 128), "keye_16k": (128, 48)}

#: ``(rows of the buffer, group sizes)``
LOADS = {
    "level": (128, [32, 32, 32, 32]),
    "empty_groups": (128, [0, 70, 0, 58]),
    "smaller_than_a_block": (128, [3, 5, 1, 2]),
    "no_multiple_of_the_tile": (128, [17, 30, 9, 41]),
    "compact_buffer_half_full": (128, [20, 11, 25, 8]),
    "all_rows_in_one_group": (128, [0, 0, 128, 0]),
    "last_group_only": (128, [0, 0, 0, 37]),
    "no_row_at_all": (128, [0, 0, 0, 0]),
    "fallback_length": (512, [20, 11, 25, 8]),
}


def ragged_dot(lhs, rhs, sizes):
    out = jax.lax.ragged_dot(lhs.astype(jnp.float32),
                             rhs.astype(jnp.float32), sizes)
    return jnp.where((jnp.arange(lhs.shape[0]) < sizes.sum())[:, None],
                     out, 0)


def ragged_outer(lhs, rhs, sizes):
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes, dims)


def operands(M, A, B, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (M, A), BF16),
            jax.random.normal(k[1], (G, A, B), BF16) / A ** 0.5,
            jax.random.normal(k[2], (M, B), BF16))


def rel(got, want):
    got = got.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rows_times_a_groups_weight(cell, load):
    """``ds_moe_gmm`` equals ``ragged_dot`` to bf16's rounding, with the
    weight as it lies and transposed, whole columns and halves; the rows of
    no group are exactly zero."""
    (A, B), (M, sizes) = CELLS[cell], LOADS[load]
    lhs, w, _ = operands(M, A, B)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = ragged_dot(lhs, w, sizes)
    kw = dict(rows=ROWS, sub=BLOCK, interpret=True)
    outs = [gm.gmm(lhs, w, sizes, cols=B, **kw),
            gm.gmm(lhs, jnp.swapaxes(w, 1, 2), sizes, cols=B,
                   transpose_rhs=True, **kw),
            gm.gmm(lhs, w, sizes, cols=B // 2, **kw)]
    total = int(sizes.sum())
    for out in outs:
        assert out.dtype == BF16 and out.shape == (M, B)
        assert rel(out, want) < 4e-3
        assert not np.asarray(out[total:], np.float32).any()


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_stacked_weights_gradient(cell, load):
    """``ds_moe_gmm_t`` equals ``ragged_dot_general`` to bf16's rounding;
    an empty group's block is zero, whatever the rows of no group hold."""
    (A, B), (M, sizes) = CELLS[cell], LOADS[load]
    lhs, _, rhs = operands(M, A, B)
    total = sum(sizes)
    # a row of no group may hold anything: the kernel must not read it
    lhs = lhs.at[total:].set(jnp.nan)
    rhs = rhs.at[total:].set(jnp.inf)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = ragged_outer(lhs[:total], rhs[:total], sizes)
    kw = dict(rows=ROWS, sub=BLOCK, interpret=True)
    for out in (gm.tgmm(lhs, rhs, sizes, cols=B, **kw),
                gm.tgmm(lhs, rhs, sizes, cols=B // 2, **kw)):
        assert out.dtype == BF16 and out.shape == (G, A, B)
        assert rel(out, want) < 4e-3
        empty = np.asarray(sizes) == 0
        assert not np.asarray(out, np.float32)[empty].any()


#: Nemotron-3's expert ``(hidden, intermediate)`` over 16: 168 = 1.3 lanes and
#: 116 = 0.9, as 2688 x 1856 one width of whole lanes' worth of sublanes and
#: no whole lanes -- here both are none, which interpret mode does not mind
HID, INTER = 168, 116
PRODUCTS = {
    # name: (kernel, contraction or rows' width, output columns, transposed)
    "up_columns": ("gmm", HID, INTER, False),
    "down_contraction": ("gmm", INTER, HID, False),
    "dt_columns_transposed": ("gmm", HID, INTER, True),
    "dx_contraction_transposed": ("gmm", INTER, HID, True),
    "dw1_columns": ("tgmm", HID, INTER, None),
    "dw2_rows": ("tgmm", INTER, HID, None),
}


@pytest.mark.parametrize("load", ["empty_groups", "compact_buffer_half_full",
                                  "fallback_length"])
@pytest.mark.parametrize("product", sorted(PRODUCTS))
def test_a_width_of_no_whole_lanes_is_one_whole_block(product, load):
    """Nemotron 8k's six products at a small width of no whole lanes, as
    output columns and as contraction, the block being the whole dimension:
    ``ragged_dot`` / ``ragged_dot_general`` to bf16's rounding, rows of no
    group exactly zero, an empty group's block zero."""
    kernel, A, B, transposed = PRODUCTS[product]
    M, sizes = LOADS[load]
    lhs, w, rhs = operands(M, A, B, seed=1)
    total = sum(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    kw = dict(rows=ROWS, cols=B, sub=BLOCK, interpret=True)
    if kernel == "gmm":
        want = ragged_dot(lhs, w, sizes)
        out = gm.gmm(lhs, jnp.swapaxes(w, 1, 2) if transposed else w, sizes,
                     transpose_rhs=transposed, **kw)
        assert out.dtype == BF16 and out.shape == (M, B)
        assert not np.asarray(out[total:], np.float32).any()
    else:
        lhs = lhs.at[total:].set(jnp.nan)
        rhs = rhs.at[total:].set(jnp.inf)
        want = ragged_outer(lhs[:total], rhs[:total], sizes)
        out = gm.tgmm(lhs, rhs, sizes, **kw)
        assert out.dtype == BF16 and out.shape == (G, A, B)
        empty = np.asarray(sizes) == 0
        assert not np.asarray(out, np.float32)[empty].any()
    assert rel(out, want) < 4e-3


@pytest.mark.parametrize("every_group", [False, True])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_visits_follow_the_real_rows(load, every_group):
    """The table's live count is the tiles each group's rows touch (an empty
    group one with ``every_group``), plus -- for ``ds_moe_gmm`` -- the tiles
    past the last row, which are zeroed and fetch nothing."""
    M, sizes = LOADS[load]
    group_of, tile_of, read_of, flags, offsets, count = (
        np.asarray(a) for a in gm._visits(
            jnp.asarray(sizes, jnp.int32), M, ROWS, every_group))
    ends = np.cumsum(sizes)
    touched = [(g, t) for g, (size, end) in enumerate(zip(sizes, ends))
               for t in range((end - size) // ROWS, -(-end // ROWS)) if size]
    live = len(touched)
    if every_group:
        assert count == live + sum(s == 0 for s in sizes)
        visited = [(g, t) for g, t in zip(group_of[:count], tile_of[:count])
                   if sizes[g]]
        assert visited == touched
        firsts = flags[:count] & gm._FIRST != 0
        assert firsts.sum() == G    # each group's accumulator zeroed once
    else:
        done = -(-int(ends[-1]) // ROWS)
        assert count == live + M // ROWS - done
        assert list(zip(group_of[:live], tile_of[:live])) == touched
        assert list(tile_of[live:count]) == list(range(done, M // ROWS))
        assert (flags[live:count] & gm._DEAD != 0).all()
        assert (flags[:live] & gm._DEAD == 0).all()
        assert (read_of[live:count] == max(done - 1, 0)).all()
        # every tile of the buffer is written, and first-written once
        firsts = flags[:count] & gm._FIRST != 0
        assert sorted(tile_of[:count][firsts]) == list(range(M // ROWS))
    assert count <= M // ROWS + G == len(flags)
    assert list(offsets) == [0, *ends]


def _tiny_tiles(M, A, B, groups, dtype):
    return gm.Tiles(ROWS, B, B)


@pytest.mark.parametrize("held,experts", [(4, None), (2, 16)],
                         ids=["every_row_a_pair", "compact_buffer"])
def test_expert_layer_gradient_through_the_kernels(monkeypatch, held,
                                                   experts):
    """``_routed_experts`` (``_sorted_experts``, and ``_compact_experts``
    over a held share's compact buffer) with the products in the two
    kernels: output and all five gradients equal the ``ragged_dot`` path's
    within bf16."""
    N, K, H, I = 64 if experts is None else 512, 2, 32, 48
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(k[0], (N, H), BF16)
    w1, w3 = (jax.random.normal(k[i], (held, H, I)) / H ** 0.5
              for i in (1, 2))
    w2 = jax.random.normal(k[3], (held, I, H)) / I ** 0.5
    topk_w = jax.nn.softmax(jax.random.normal(k[4], (N, K)))
    topk_idx = jax.random.randint(k[5], (N, K), 0, experts or held)
    if experts is not None:
        assert mx._compact_rows(N * K, held, experts) is not None

    def loss(x, w1, w2, w3, topk_w):
        out, rows = mx._routed_experts(x, w1, w2, w3, topk_w, topk_idx, 0,
                                       experts)
        return jnp.sum(out.astype(jnp.float32) ** 2), rows

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    (want, want_rows), want_grads = grad(x, w1, w2, w3, topk_w)
    monkeypatch.setattr(mx, "_grouped_tiles", _tiny_tiles)
    jaxpr = str(jax.make_jaxpr(grad)(x, w1, w2, w3, topk_w))
    assert "ds_moe_gmm" in jaxpr and "ragged_dot" not in jaxpr
    (got, got_rows), got_grads = grad(x, w1, w2, w3, topk_w)
    assert np.array_equal(got_rows, want_rows)
    assert abs(float(got) - float(want)) <= 2e-2 * abs(float(want))
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert rel(a, b.astype(jnp.float32)) < 2e-2


# -- the rule -----------------------------------------------------------------

#: the seven cells' layers: ``(devices under the mesh, rows of the buffer(s),
#: hidden, intermediate, groups held)``
SHAPES = {
    "olmoe-1b-7b.train.4k": (1, (65536,), 2048, 1024, 64),
    "kimi-vl-a3b.train.8k": (1, (12288, 49152), 2048, 1408, 8),
    "zaya1-8b.train.8k": (1, (8192,), 2048, 2048, 8),
    "keye-vl2-30b-a3b.train.16k": (1, (32768, 131072), 2048, 768, 16),
    "mellum2-12b-a2.5b.train.8k": (1, (16384, 65536), 2304, 896, 8),
    "mixtral-8x7b.train.ep4": (4, (32768,), 4096, 3584, 8),
    "nemotron-3-nano-30b-a3b.train.8k": (1, (6144, 49152), 2688, 1856, 8),
}


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_the_rule_takes_the_one_chip_cells_and_leaves_ep4(cell):
    devices, buffers, H, I, groups = SHAPES[cell]
    for M in buffers:
        for A, B in ((H, I), (I, H)):
            tiles = gm.plan("tpu", devices, M, A, B, groups)
            if devices > 1:
                assert tiles is None
                # nor would its weight fit, on a mesh of one
                assert gm.plan("tpu", 1, M, A, B, groups) is None
                continue
            assert tiles == gm.Tiles(512, B, B)
            # off a TPU, in float32, on a chip that was never timed
            assert gm.plan("cpu", 1, M, A, B, groups) is None
            assert gm.plan("tpu", 1, M, A, B, groups, itemsize=4) is None
            assert gm.plan("tpu", 1, M, A, B, groups,
                           device_kind="TPU v4") is None


@pytest.mark.parametrize("M,A,B,groups,why", [
    (64, 64, 128, 4, "the tiny sizes: no whole tile, no whole lane"),
    (8, 2048, 1024, 64, "a decode step's rows"),
    (4096, 2048, 1024, 64, "fewer rows than a block a group"),
    (16384, 4096, 14336, 8, "Mixtral's whole expert: the weight never fits"),
    (16384, 2304, 900, 8, "columns that are no whole sublane tile either"),
    (16384, 2304, 1864, 8, "whole 8-row tiles, no whole 16-row ones"),
    (16384, 1856, 1856, 8, "two widths of no whole lanes"),
    (16384, 4096, 1856, 8, "no whole lanes, and too wide to hold whole"),
])
def test_the_rule_leaves_what_it_cannot_tile(M, A, B, groups, why):
    assert gm.plan("tpu", 1, M, A, B, groups) is None, why


def test_wide_weights_are_held_in_column_halves():
    """A block that does not fit 5/16 of VMEM whole is held in two halves of
    whole lanes; the float32 accumulator, eight bytes an element with its
    block, splits earlier; what fits in neither stays on ``ragged_dot``."""
    assert gm.plan("tpu", 1, 16384, 2048, 2048, 8) == \
        gm.Tiles(512, 2048, 2048)
    assert gm.plan("tpu", 1, 16384, 2048, 3072, 8) == \
        gm.Tiles(512, 3072, 1536)
    assert gm.plan("tpu", 1, 16384, 4096, 2048, 8) == \
        gm.Tiles(512, 2048, 1024)
    assert gm.plan("tpu", 1, 16384, 4096, 3072, 8) is None


@pytest.mark.parametrize("A,B", [(2688, 1856), (1856, 2688), (2688, 1808),
                                 (2688, 1872), (1024, 1856), (3008, 1024)])
def test_a_width_of_no_whole_lanes_is_never_split(A, B):
    """Half of 1856 is 928, no lanes either: such a width is one block, as
    columns and as contraction, or the shape stays on ``ragged_dot``."""
    assert gm.plan("tpu", 1, 6144, A, B, 8) == gm.Tiles(512, B, B)


def test_the_budget_counts_a_width_as_mosaic_holds_it():
    """At the next whole lane: 2688 x 1920 x 8 B fit the accumulator's 5/16
    of VMEM whole, 2688 x 2048 x 8 B of a shape 128 columns wider do not and
    split; 3712 columns are whole lanes whose halves are not, and do not fit
    whole: no tiles."""
    budget = (128 << 20) * 5 // 16
    assert gm._lanes(1856) == 1920 and gm._lanes(2048) == 2048
    assert 2688 * 1920 * 8 <= budget < 2688 * 2048 * 8
    assert gm.plan("tpu", 1, 6144, 2688, 2048, 8) == gm.Tiles(512, 2048, 1024)
    assert gm._cols(2688, 2 * 1856, 8, budget) is None


def test_the_layer_asks_the_rule_what_it_sees(monkeypatch):
    """``mixtral._grouped_tiles`` hands ``plan`` the backend, the devices
    under the active mesh, the shape, the operands' width and the device
    kind -- on this CPU: ``ragged_dot``."""
    seen = []
    monkeypatch.setattr(gm, "plan", lambda *a: seen.append(a))
    mx._grouped_tiles(16384, 2304, 896, 8, BF16)
    assert seen == [("cpu", 1, 16384, 2304, 896, 8, 2,
                     jax.devices()[0].device_kind)]
    lhs, w, _ = operands(128, 32, 48)
    assert "ragged_dot" in str(jax.make_jaxpr(mx._grouped_dot)(
        lhs, w, jnp.asarray([32] * 4, jnp.int32)))
