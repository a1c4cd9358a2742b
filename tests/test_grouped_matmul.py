"""The grouped-matmul kernel that reads expert matrices out of the held
stack in place (ops/pallas/grouped_matmul.py), in interpreter mode on the
CPU: the real visit walk, block indexing and masked stores, against
`jax.lax.ragged_dot` on the layer's slice and against one product a row;
and the kernel that adds the experts' live rows back into their tokens
(ops/pallas/row_accumulate.py) against the plain scatter-add."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul,
    reference_grouped_matmul,
    visits,
)
from dnn_tpu.ops.pallas import row_accumulate as ra
from dnn_tpu.parallel import moe

F32, BF16 = jnp.float32, jnp.bfloat16


def _operands(r, k, n, n_layer, e, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    rows = jax.random.normal(key, (r, k), F32).astype(dtype)
    stack = (jax.random.normal(jax.random.fold_in(key, 1),
                               (n_layer, e, k, n), F32) * k ** -0.5
             ).astype(dtype)
    return rows, stack


# (case, rows R, group sizes, layer of 3, dtype, (row tile, column tile));
# cases of one shape share one compiled kernel
CASES = [
    ("every_expert_active", 40, [5, 7, 6, 8, 4, 10], 1, F32, (16, 48)),
    ("empty_first_last_middle", 40, [0, 17, 0, 0, 23, 0], 1, F32, (16, 48)),
    ("held_tail_ignored", 40, [5, 0, 12, 3, 0, 4], 2, F32, (16, 48)),
    ("no_rows_at_all", 40, [0, 0, 0, 0, 0, 0], 0, F32, (16, 48)),
    ("layer_0", 40, [5, 7, 6, 8, 4, 10], 0, F32, (16, 48)),
    ("last_layer", 40, [5, 7, 6, 8, 4, 10], 2, F32, (16, 48)),
    ("rows_not_a_multiple_of_the_tile", 37, [3, 0, 20, 0, 0, 14], 1, F32,
     (16, 48)),
    ("bfloat16", 40, [1, 30, 0, 0, 0, 9], 1, BF16, (16, 48)),
    ("bfloat16_column_tiles", 37, [3, 0, 20, 4, 0], 2, BF16, (16, 128)),
    ("tiles_chosen_by_the_code", 300, [130, 0, 1, 160, 9], 1, BF16, None),
]

_kernel = jax.jit(grouped_matmul, static_argnames=("interpret", "tiles"))


@pytest.mark.parametrize("case,r,sizes,layer,dtype,tiles", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_equals_ragged_dot_and_the_per_row_product(case, r, sizes,
                                                          layer, dtype,
                                                          tiles):
    k, n = 32, 256 if tiles in (None, (16, 128)) else 48
    rows, stack = _operands(r, k, n, 3, len(sizes), dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    got = _kernel(rows, stack, jnp.int32(layer), gs, interpret=True,
                  tiles=tiles)
    assert got.shape == (r, n) and got.dtype == F32
    held = sum(sizes)  # rows behind the last group are unspecified
    ref = reference_grouped_matmul(rows, stack, layer, gs)
    expert_of_row = np.repeat(np.arange(len(sizes)), sizes)
    per_row = jnp.einsum("rk,rkn->rn", rows[:held].astype(F32),
                         stack[layer][expert_of_row].astype(F32),
                         precision="highest")
    tol = 1e-5 if dtype == F32 else 1e-4  # bfloat16 products are exact
    np.testing.assert_allclose(got[:held], ref[:held], atol=tol, rtol=tol)
    np.testing.assert_allclose(got[:held], per_row, atol=tol, rtol=tol)


def test_visits_walk_each_active_group_over_the_tiles_it_has_rows_in():
    sizes = jnp.asarray([0, 17, 0, 0, 23, 0], jnp.int32)
    offsets, group, tile, n = visits(sizes, 48, 16)
    assert offsets.tolist() == [0, 0, 17, 17, 17, 40, 40]
    # group 1 holds rows 0-16 (tiles 0, 1), group 4 rows 17-39 (tiles 1, 2);
    # tile 2's tail and the empty groups are never visited
    assert int(n) == 4 and group.shape == (3 + 6 - 1,)
    assert group[:4].tolist() == [1, 1, 4, 4]
    assert tile[:4].tolist() == [0, 1, 1, 2]
    # past the last visit the entries repeat it: no new block is named
    assert group[4:].tolist() == [4] * 4 and tile[4:].tolist() == [2] * 4


def test_off_the_tpu_the_dispatcher_is_the_plain_form():
    rows, stack = _operands(24, 16, 32, 2, 3, F32)
    gs = jnp.asarray([4, 0, 20], jnp.int32)
    np.testing.assert_array_equal(
        grouped_matmul(rows, stack, 1, gs),
        reference_grouped_matmul(rows, stack, 1, gs))
    with pytest.raises(ValueError, match="do not meet"):
        grouped_matmul(rows.astype(BF16), stack, 1, gs, interpret=True)


@pytest.mark.parametrize("gated,held,dtype,layer", [
    (True, None, F32, 0), (True, (2, 4), BF16, 2), (False, None, F32, 1)],
    ids=["gated", "gated_held_bf16", "plain"])
def test_moe_ffn_grouped_on_a_stack_equals_it_on_the_slice(gated, held,
                                                           dtype, layer):
    """`moe_ffn_grouped` handed `LayerOf(stack, layer)` matrices (a layer
    loop's whole stacks, through the kernel) against the same call handed
    the layer's matrices cut out (`ragged_dot`): outputs and the int32
    stats; and `forms` says which form each took."""
    n_layer, d, f, e, k = 3, 32, 48, 8, 2
    n_held = e if held is None else held[1]
    keys = jax.random.split(jax.random.PRNGKey(3), n_layer)
    init = (lambda key: moe.init_moe_gated(key, d, e, f, n_held=n_held)) \
        if gated else (lambda key: moe.init_moe(key, d, e, f))
    layers = [init(key) for key in keys]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, d), F32)
    cd = None if dtype == F32 else dtype
    if cd is not None:  # held in the compute dtype, as the daemon holds it
        layers = [{name: w.astype(cd) if name in moe.EXPERT_MATRICES else w
                   for name, w in p.items()} for p in layers]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    on_stack = {**layers[layer], **{
        name: moe.LayerOf(stacked[name], jnp.int32(layer))
        for name in moe.EXPERT_MATRICES if name in stacked}}
    forms_stack, forms_slice, forms_cpu = set(), set(), set()
    kw = dict(top_k=k, compute_dtype=cd, return_stats=True, held=held)
    y_stack, stats_stack = jax.jit(lambda p, x: moe.moe_ffn_grouped(
        p, x, interpret=True, forms=forms_stack, **kw))(on_stack, x)
    y_slice, stats_slice = jax.jit(lambda p, x: moe.moe_ffn_grouped(
        p, x, forms=forms_slice, **kw))(layers[layer], x)
    assert forms_stack == {"stack_kernel"} and forms_slice == {"ragged_dot"}
    tol = 1e-5 if cd is None else 2e-2
    np.testing.assert_allclose(y_stack, y_slice, atol=tol, rtol=tol)
    assert stats_stack.dtype == jnp.int32
    assert stats_stack.shape == (moe.N_STATS,)
    np.testing.assert_array_equal(stats_stack, stats_slice)
    # off the TPU a stack is cut and goes the plain way
    y_cpu = jax.jit(lambda p, x: moe.moe_ffn_grouped(
        p, x, forms=forms_cpu, **kw)[0])(on_stack, x)
    assert forms_cpu == {"ragged_dot"}
    np.testing.assert_array_equal(y_cpu, y_slice)


# (case, tokens S, width D, rows R, live rows, bytes a column tile of y may
# hold: None the module's)
ACCUMULATE_CASES = [
    ("no_row_live", 24, 256, 300, 0, None),
    ("part_of_a_tile", 24, 256, 300, 5, None),
    ("a_whole_tile", 24, 256, 300, 128, None),
    ("one_row_into_the_next_tile", 24, 256, 300, 129, None),
    ("every_row_live", 24, 256, 300, 300, None),
    ("fewer_rows_than_a_tile", 24, 256, 20, 13, None),
    ("live_past_the_rows", 24, 256, 20, 64, None),
    ("y_in_two_column_tiles", 24, 512, 300, 200, 24 * 256 * 4),
    ("y_in_four_column_tiles", 24, 512, 300, 200, 24 * 128 * 4),
    ("y_in_four_column_tiles_no_row_live", 24, 512, 300, 0, 24 * 128 * 4),
    ("y_in_four_column_tiles_every_row_live", 24, 512, 300, 300,
     24 * 128 * 4),
]


@pytest.mark.parametrize("case,s,d,r,live,y_bytes", ACCUMULATE_CASES,
                         ids=[c[0] for c in ACCUMULATE_CASES])
def test_row_accumulate_adds_the_live_rows_alone(case, s, d, r, live,
                                                 y_bytes, monkeypatch):
    """Every row under `live`, times its weight, added into its token's
    row of a y that already holds something; rows from `live` on hold NaN
    (the grouped matmul leaves them unspecified) and are never read into a
    sum; a token met by several rows gets them all, rows that follow each
    other (a token all of whose picks are held) and rows either side of a
    row tile's edge among them."""
    if y_bytes is not None:
        monkeypatch.setattr(ra, "_Y_BLOCK_BYTES", y_bytes)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    y = jax.random.normal(ks[0], (s, d))
    rows = jax.random.normal(ks[1], (r, d)).at[min(live, r):].set(jnp.nan)
    tokens = jax.random.randint(ks[2], (r,), 0, s)
    tokens = tokens.at[2:10].set(5)
    if r > 131:
        tokens = tokens.at[126:131].set(7)
    weights = jax.random.uniform(ks[3], (r,))
    got = jax.jit(lambda *a: ra.row_accumulate(*a, interpret=True))(
        y, rows, tokens, weights, jnp.int32(live))
    want = np.array(y)
    for i in range(min(live, r)):
        want[int(tokens[i])] += float(weights[i]) * np.asarray(rows[i])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # off the TPU the dispatcher is the plain form
    np.testing.assert_allclose(
        ra.row_accumulate(y, rows, tokens, weights, jnp.int32(live)), want,
        atol=1e-5, rtol=1e-5)
