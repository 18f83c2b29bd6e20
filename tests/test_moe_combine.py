"""The expert layer's way back as a kernel (``ops/moe_combine.py``): the
Pallas kernel through the interpreter on tiny shapes against the form by
the slots (``combine_slots``: one gathered row a slot, the sum in
float32), the plan by cell, and the layer with the kernel inside it.

A file of its own, so that ``--dist loadfile`` makes it a load of its
own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import moe_combine
from horovod_tpu.parallel import moe

# the expert cells' layers: tokens, choices, hidden, held, experts, the
# rows computed on, and the token tile the plan gives them
CELLS = {
    "sdar_train_s8192_bd4": (16384, 8, 2048, 16, 128, 32768, 1024),
    "lfm2_train_s32768": (32768, 4, 2048, 8, 64, 32768, 1024),
    "smallthinker_train_s16384": (16384, 6, 2560, 16, 64, 49152, 512),
    # the step over the row bound: the held experts hold more than twice
    # their share, so a tile's ranges are longer and the tile is shorter
    "smallthinker_whole_buffer": (16384, 6, 2560, 16, 64, 98304, 256),
    "kimilin_train_s16384": (16384, 8, 2304, 8, 256, 8192, 1024),
    "trinitym_train_s8192": (8192, 8, 2048, 16, 128, 16384, 1024),
    "glm47f_train_s8192": (8192, 4, 2048, 8, 64, 8192, 1024),
}


def sorted_slots(experts, first_held, held):
    """``route``'s sort of a given choice ``experts [n, k]``: the order,
    its inverse and the held experts' sizes."""
    local = np.asarray(experts).reshape(-1) - first_held
    key = np.where((local >= 0) & (local < held), local, held)
    order = np.argsort(key, kind="stable").astype(np.int32)
    inverse = np.zeros_like(order)
    inverse[order] = np.arange(order.size, dtype=np.int32)
    return order, jnp.asarray(inverse), jnp.asarray(
        np.bincount(key, minlength=held + 1)[:held].astype(np.int32))


def chosen(n, k, experts, seed):
    """``k`` distinct experts a token, as a top-k gives them."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(experts)[:k] for _ in range(n)])


def operands(n, k, d, experts, first_held, held, dtype, rows=None, seed=0,
             choice=None):
    choice = chosen(n, k, experts, seed) if choice is None else choice
    _, inverse, sizes = sorted_slots(choice, first_held, held)
    rows = n * k if rows is None else rows
    live = int(sizes.sum())
    assert live <= rows
    keys = jax.random.split(jax.random.key(seed), 2)
    # what the grouped matmul leaves: nothing past the held groups
    ys = jnp.where(jnp.arange(rows)[:, None] < live,
                   jax.random.normal(keys[0], (rows, d)), 0).astype(dtype)
    weights = jax.random.uniform(keys[1], (n, k), minval=0.1)
    return choice, ys, weights, inverse, sizes


def both(ys, weights, inverse, sizes, k, tiles, dtype=jnp.float32):
    return (moe_combine.combine_rows(ys, weights, inverse, sizes, k=k,
                                     tiles=tiles, dtype=dtype,
                                     interpret=True),
            moe_combine.combine_slots(ys, weights, inverse, k).astype(dtype))


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain_sum"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("first_held,held,rows", [
    (0, 2, 64),      # an eighth... a quarter held, under the row bound
    (0, 8, None),    # every expert held: the rows are the slots
    (5, 2, 64),      # this chip's experts are not the first
], ids=["quarter_held", "all_held", "first_held_5"])
def test_the_kernel_gives_the_slots_sum(weighted, dtype, first_held, held,
                                        rows):
    n, k, d, experts = 64, 2, 128, 8
    _, ys, weights, inverse, sizes = operands(
        n, k, d, experts, first_held, held, dtype, rows, seed=held)
    got, want = both(ys, weights if weighted else None, inverse, sizes, k,
                     (32, 16))
    assert got.shape == (n, d) and got.dtype == jnp.float32
    # float32 sums of the same products in another order (two choices:
    # the same sum where the plain sum adds exact bfloat16 values)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    if not weighted:
        np.testing.assert_array_equal(got, want)


def test_a_token_held_elsewhere_comes_out_zero_and_the_rest_do_not():
    n, k, d, experts, held = 64, 2, 128, 8, 2
    choice, ys, weights, inverse, sizes = operands(
        n, k, d, experts, 0, held, jnp.bfloat16, 64)
    got, want = both(ys, weights, inverse, sizes, k, (32, 16))
    elsewhere = (choice >= held).all(axis=1)
    assert 0 < elsewhere.sum() < n
    assert not np.asarray(got)[elsewhere].any()
    assert np.abs(np.asarray(got)[~elsewhere]).min(axis=0).max() > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_an_expert_without_rows_and_one_with_every_row():
    """Expert 0 is chosen by nobody, expert 1 by every token: its range
    in a tile of 32 tokens is 32 rows, two chunks of 16 (three where the
    range starts inside a row tile), and the last chunk of the last tile
    is moved back into the rows."""
    n, k, d, experts, held = 64, 2, 128, 8, 3
    rng = np.random.default_rng(3)
    choice = np.stack([[1, rng.integers(2, experts)] for _ in range(n)])
    _, ys, weights, inverse, sizes = operands(
        n, k, d, experts, 0, held, jnp.bfloat16, 96, choice=choice)
    assert sizes[0] == 0 and sizes[1] == n
    for dtype in (jnp.float32, jnp.bfloat16):
        got, want = both(ys, weights, inverse, sizes, k, (32, 16), dtype)
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32), rtol=0,
                                   atol=1e-6 if dtype == jnp.float32 else 4e-2)


def test_the_whole_buffer_gives_the_bounded_rows_result_bit_for_bit():
    """The side of the layer's branch that runs on every slot reads the
    same ranges, from the held experts' sizes: what lies past them adds
    nothing, whatever it is (a chunk may bring such rows; no token's 0/1
    row selects them)."""
    n, k, d, experts, held = 64, 2, 128, 8, 2
    _, ys, weights, inverse, sizes = operands(
        n, k, d, experts, 0, held, jnp.bfloat16)
    assert ys.shape[0] == n * k
    poisoned = ys.at[int(sizes.sum()):].set(1e4)
    for w in (weights, None):
        whole = moe_combine.combine_rows(
            poisoned, w, inverse, sizes, k=k, tiles=(32, 16),
            dtype=jnp.float32, interpret=True)
        bounded = moe_combine.combine_rows(
            ys[:64], w, inverse, sizes, k=k, tiles=(32, 16),
            dtype=jnp.float32, interpret=True)
        np.testing.assert_array_equal(whole, bounded)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_plan_gives_every_cell_tiles_that_fit(cell):
    n, k, d, held, experts, rows, planned = CELLS[cell]
    tile, chunk = moe_combine.plan(n, d, held, rows, 2)
    assert tile == planned and n % tile == 0
    assert chunk % 16 == 0 and chunk <= rows and rows % 16 == 0
    assert moe_combine.vmem_bytes(tile, chunk, d, held, 2) \
        <= moe_combine._VMEM_LIMIT
    # a range is one chunk nearly always: under an even routing the
    # expected rows and the alignment's slack leave three standard
    # deviations (a binomial's)
    expected = tile * k / experts
    assert expected + 3 * expected ** 0.5 + 16 <= chunk


@pytest.mark.parametrize("n,d,rows,held", [
    (64, 96, 64, 8),       # d is no whole number of 128-lane tiles
    (100, 128, 208, 8),    # no tile divides the tokens
    (128, 128, 64, 8),     # fewer rows than a chunk
    (128, 128, 256, 4),    # the held experts are no whole sublane tile
], ids=["lanes", "tokens", "rows", "held"])
def test_a_refused_shape_takes_the_slots_path(n, d, rows, held, monkeypatch):
    k, experts = 2, 64
    assert moe_combine.plan(n, d, held, rows, 2) is None
    monkeypatch.setattr(moe_combine, "combine_rows", None)   # not called
    _, ys, weights, inverse, sizes = operands(
        n, k, d, experts, 0, held, jnp.bfloat16, rows)
    got = moe_combine.combine(ys, weights, inverse, sizes, k=k,
                              dtype=jnp.float32, interpret=False)
    np.testing.assert_array_equal(
        got, moe_combine.combine_slots(ys, weights, inverse, k))


def test_off_the_chip_the_layer_takes_the_slots_path(monkeypatch):
    """``interpret`` (the package's one rule says so on the CPU) keeps
    the form XLA runs, whatever the plan would give."""
    n, k, d, experts, held = 128, 2, 128, 16, 8
    assert moe_combine.plan(n, d, held, 128, 2) is not None
    assert not moe_combine.engaged(n, d, held, 128, jnp.bfloat16)
    monkeypatch.setattr(moe_combine, "combine_rows", None)   # not called
    _, ys, weights, inverse, sizes = operands(
        n, k, d, experts, 0, held, jnp.bfloat16, 128)
    got = moe_combine.combine(ys, None, inverse, sizes, k=k,
                              dtype=jnp.bfloat16, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (n, d)


@pytest.mark.parametrize("first_held", [0, 4])
def test_the_layer_with_the_kernel_inside_gives_the_layers_gradients(
        first_held, monkeypatch):
    """``routed_experts`` forward and backward with the way back by the
    interpreted kernel, against the same layer by the slots: the output,
    and the gradients by the tokens, the router (through the weights'
    gradient, put at its slots by the rows) and both matrices."""
    n, k, d, ff, experts, held = 64, 2, 128, 32, 8, 2
    keys = jax.random.split(jax.random.key(7), 4)
    x2 = jax.random.normal(keys[0], (n, d))
    router = jax.random.normal(keys[1], (d, experts)) * 0.1
    gate_up = jax.random.normal(keys[2], (held, d, 2 * ff)) * 0.05
    down = jax.random.normal(keys[3], (held, ff, d)) * 0.05

    def run():
        def loss(x2, router, gate_up, down):
            y, _ = moe.routed_experts(
                x2, router, jnp.zeros((experts,)), gate_up, down, top_k=k,
                scaling=1.5, first_held=first_held, dtype=jnp.float32,
                interpret=True)
            return (y ** 2).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            x2, router, gate_up, down)

    want = run()
    jax.clear_caches()      # ``_forward`` and ``_backward`` are jitted

    def by_kernel(rows, weights, inverse, held_sizes, *, k, dtype, interpret):
        return moe_combine.combine_rows(rows, weights, inverse, held_sizes,
                                        k=k, tiles=(32, 16), dtype=dtype,
                                        interpret=True)

    monkeypatch.setattr(moe_combine, "combine", by_kernel)
    got = run()
    jax.clear_caches()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
