"""The router's three slot-free forms (``parallel/moe.py``: the counts by
comparison, the chosen scores by a select over the experts, the sort's
inverse by a second sort) against the forms they took the place of: the
two scatter-adds, ``take_along_axis`` and the scatter by ``order``.

``_old_route`` and ``_old_decision`` are line-for-line copies of
``route`` and of ``routing_decision``'s inverse as they stood before the
change.  Op by op (``jax.disable_jit()``) every field of the decision
and the gradients of a loss over the weights and the balance loss are
the old ones to the bit; under ``jit`` the integers are equal and the
floats lie within 4 float32 ulps of their largest entry, or as far
apart as the old form's own two compiles where a gradient's terms
cancel (the compiler fuses the sigmoid and the division into other
fusions around a select than around a gather, as any two compiles of the
layer differ); the
select alone is the gather to the bit under ``jit``, value and
cotangent.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import Routing

N, D = 48, 16


def _old_route(x2, router, bias, *, top_k, scaling, first_held, held,
               score_rule="sigmoid", balance=False):
    n = x2.shape[0]
    logits = jnp.dot(
        x2.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if score_rule == "sigmoid" else logits
    if score_rule == "sigmoid":
        _, experts = lax.top_k(scores + lax.stop_gradient(bias), top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        weights = (chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
                   * scaling)
    else:
        chosen, experts = lax.top_k(scores, top_k)
        weights = jax.nn.softmax(chosen, axis=-1) * scaling
    local = experts.reshape(n * top_k) - first_held
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    dropped = is_held.sum(dtype=jnp.int32) - group_sizes[:held].sum()
    load = jnp.zeros((router.shape[1],), jnp.int32).at[
        experts.reshape(n * top_k)].add(1)
    routing = Routing(weights, experts.astype(jnp.int32), order, group_sizes,
                      dropped, load)
    if balance:
        share = load.astype(jnp.float32) / (n * top_k)
        mean = jax.nn.softmax(logits, axis=-1).mean(axis=0)
        routing = routing._replace(
            balance=router.shape[1] * jnp.sum(share * mean))
    return routing


def _old_decision(x2, router, bias, *, top_k, **how):
    n = x2.shape[0]
    routing = _old_route(x2, router, bias, top_k=top_k, **how)
    inverse = jnp.zeros_like(routing.order).at[routing.order].set(
        jnp.arange(n * top_k, dtype=jnp.int32))
    return routing._replace(inverse=inverse)


# experts, choices a token, held experts, the first of them, and what the
# scores are like: ``random``; ``tied`` (every expert has a twin of the
# same score, so every choice breaks ties); ``one`` (every token chooses
# the same experts, each of which takes a slot of every token: with one
# choice a token, every slot goes to one expert)
SHAPES = [
    (8, 2, 2, 2, "random"),
    (8, 2, 8, 0, "random"),
    (8, 1, 2, 3, "one"),
    (8, 2, 2, 2, "tied"),
    (64, 4, 16, 8, "random"),
    (64, 4, 64, 0, "tied"),
    (64, 8, 16, 40, "one"),
    (256, 8, 8, 16, "random"),
    (256, 8, 256, 0, "one"),
    (256, 4, 32, 224, "tied"),
]
CASES = [(rule, *shape) for rule in moe.SCORE_RULES for shape in SHAPES]
IDS = [f"{rule}-E{e}-k{k}-held{held}at{first}-{kind}"
       for rule, e, k, held, first, kind in CASES]


def _operands(rule, experts, top_k, held, first_held, kind):
    keys = jax.random.split(jax.random.key(experts + top_k), 4)
    x2 = jax.random.normal(keys[0], (N, D))
    router = jax.random.normal(keys[1], (D, experts)) / 4
    if kind == "tied":
        router = jnp.repeat(router[:, ::2], 2, axis=1)
    if kind == "one":
        # a direction every token shares, which the favoured experts read
        x2 = x2.at[:, 0].set(6.0)
        favoured = (first_held + jnp.arange(top_k) * 3 - 1) % experts
        router = (router / 8).at[0, favoured].set(2.0)
    bias = None
    if rule == "sigmoid":
        bias = jax.random.uniform(keys[2], (experts,), minval=-0.05,
                                  maxval=0.05)
        if kind == "tied":
            bias = jnp.repeat(bias[::2], 2)
    how = dict(top_k=top_k, scaling=1.7, first_held=first_held, held=held,
               score_rule=rule, balance=True)
    return (x2, router, bias), how, jax.random.normal(keys[3], (N, top_k))


def _loss(decide, how, mix):
    def loss(x2, router, bias):
        routing = decide(x2, router, bias, **how)
        return (routing.weights * mix).sum() + 3.0 * routing.balance
    return loss


def _decision_and_gradients(decide, operands, how, mix):
    routing = decide(*operands, **how)
    return routing, jax.grad(_loss(decide, how, mix), argnums=(0, 1))(
        *operands)


@functools.lru_cache(maxsize=None)
def _old_op_by_op(case):
    """The old form's decision and gradients, each operation a program of
    its own: what two of the tests below compare with."""
    operands, how, mix = _operands(*case)
    with jax.disable_jit():
        return _decision_and_gradients(_old_decision, operands, how, mix)


INTEGERS = ("experts", "order", "group_sizes", "dropped", "load", "inverse")


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_op_by_op_the_decision_and_its_gradients_are_the_old_ones_to_the_bit(
        case):
    operands, how, mix = _operands(*case)
    with jax.disable_jit():
        new, new_grads = _decision_and_gradients(
            moe.routing_decision, operands, how, mix)
    old, old_grads = _old_op_by_op(case)
    for field in (*INTEGERS, "weights", "balance"):
        np.testing.assert_array_equal(
            _bits(getattr(new, field)), _bits(getattr(old, field)), field)
    for got, want in zip(new_grads, old_grads):
        np.testing.assert_array_equal(_bits(got), _bits(want))
    # what the case is there for
    e, k, held, first, kind = case[1:]
    assert int(new.dropped) == 0 and int(new.load.sum()) == N * k
    assert int(new.group_sizes.sum()) == N * k
    np.testing.assert_array_equal(new.order[new.inverse], np.arange(N * k))
    if kind == "one":
        assert sorted(np.asarray(new.load))[-k:] == [N] * k
    if held == e:
        assert int(new.group_sizes[held]) == 0


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_under_jit_the_integers_are_equal_and_the_floats_within_four_ulps(
        case):
    operands, how, mix = _operands(*case)
    new, new_grads = jax.jit(lambda *o: _decision_and_gradients(
        moe.routing_decision, o, how, mix))(*operands)
    old, old_grads = jax.jit(lambda *o: _decision_and_gradients(
        _old_decision, o, how, mix))(*operands)
    for field in INTEGERS:
        np.testing.assert_array_equal(getattr(new, field),
                                      getattr(old, field), field)
    apart, apart_grads = _old_op_by_op(case)
    for got, want, other in (
            (new.weights, old.weights, apart.weights),
            (new.balance, old.balance, apart.balance),
            *zip(new_grads, old_grads, apart_grads)):
        want = np.asarray(want)
        # 4 ulps of the largest entry; a gradient whose terms cancel (a
        # token that chose two twins has weights of a half whatever the
        # scores) is rounding alone, and there the room is what the old
        # form's own two compiles, this one and op by op, lie apart
        room = max(4 * np.spacing(np.abs(want).max()),
                   np.abs(np.asarray(other) - want).max())
        np.testing.assert_allclose(got, want, rtol=0, atol=room)


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_select_alone_is_the_gather_to_the_bit_under_jit(case):
    (x2, router, bias), how, mix = _operands(*case)
    scores = x2 @ router
    if case[0] == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    _, experts = lax.top_k(scores if bias is None else scores + bias,
                           how["top_k"])
    gather = lambda scores, experts: jnp.take_along_axis(scores, experts,
                                                         axis=-1)

    def value_and_cotangent(form):
        value, pull = jax.jit(lambda s, e: jax.vjp(
            lambda s: form(s, e), s))(scores, experts)
        return value, *jax.jit(pull)(mix)

    for got, want in zip(value_and_cotangent(moe._chosen),
                         value_and_cotangent(gather)):
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("bins", [1, 3, 9, 64, 257])
def test_the_counts_are_the_scatter_adds(bins):
    values = jax.random.randint(jax.random.key(bins), (4096,), 0, bins)
    want = jnp.zeros((bins,), jnp.int32).at[values].add(1)
    got = jax.jit(moe._counts, static_argnums=1)(values, bins)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)
    # every slot in the last bin: what a layer that holds nothing counts
    np.testing.assert_array_equal(
        moe._counts(jnp.full((512,), bins - 1), bins),
        jnp.zeros((bins,), jnp.int32).at[bins - 1].set(512))
