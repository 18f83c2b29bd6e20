"""GLM-4.7-Flash's mechanisms on the training path (``glm4_moe_lite``):
latent attention, routed experts that drop nothing with a chip's share of
them, a shared expert, a multi-token-prediction module.  The program
(``models/transformer.py``, ``parallel/moe.py``) against the plain
reference ``tests/glm_reference.py`` on seeded weights; the shares of the
experts adding up to the uncut layer; the counters; the published values
of the named size and the count of its cut; the paths that refuse it.
All on the CPU at small sizes: hidden 64, 4 heads of 16 + 8 against
values of 24, latent ranks 24 and 16, 16 experts of width 32 of which 4
are held from expert 4 on, 3 a token, one dense layer before two expert
layers.
"""

import functools
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import glm_reference as ref  # noqa: E402

from horovod_tpu.models.transformer import (GPT_CONFIGS, Block,  # noqa: E402
                                            gpt)
from horovod_tpu.parallel import moe  # noqa: E402

SMALL = dict(
    num_layers=3, layer_types=("mla",) * 3, vocab_size=256, emb_dim=64,
    num_heads=4, num_kv_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, mlp_ratio=3,
    routed_experts=16, routed_held=4, routed_first_held=4, routed_top_k=3,
    routed_width=32, max_len=64, attention_impl="reference",
    dtype=jnp.float32)
CONFIG = dict(
    num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, rope_theta=1e6, rms_norm_eps=1e-5,
    n_routed_experts=4, first_held_expert=4, num_experts_per_tok=3,
    routed_scaling_factor=1.8, mtp_loss_weight=0.3)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 2), 0, 256)


def small_model(**overrides):
    return gpt("glm-4.7-flash", **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread over (0, 1) at this width, and the norms' weights
    away from 1 (at 1 the final norm before the prediction module's own
    norm changes nothing, and a reference that takes the stream after it
    could not be told from one that takes it before)."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if "scale" in name:
            return leaf + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf

    return {**variables, "params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program_losses(model, variables, tokens):
    logits, mtp_logits = model.apply(
        {k: variables[k] for k in ("params", "moe_state")},
        tokens[:, :-2], next_tokens=tokens[:, 1:-1])
    return (ref._cross_entropy(logits, tokens[:, 1:-1]).mean(),
            ref._cross_entropy(mtp_logits, tokens[:, 2:]).mean())


def program_loss(model, variables, tokens):
    main, mtp = program_losses(model, variables, tokens)
    return main + CONFIG["mtp_loss_weight"] * mtp


def _traced(forward, losses, loss, variables):
    """Both heads' logits, both losses and the gradient of the weighted
    loss in ``params``, from one trace."""
    def run(v):
        grads = jax.grad(lambda p: loss({**v, "params": p}))(v["params"])
        return forward(v), losses(v), loss(v), grads

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(variables)


@functools.cache
def sound():
    """The seeded variables and what the plain reference gives for them,
    computed once a module (the attention's form changes no variable)."""
    variables = init(small_model())
    return variables, _traced(
        lambda v: ref.forward(CONFIG, v, TOKENS[:, :-2], TOKENS[:, 1:-1]),
        lambda v: ref.losses(CONFIG, v, TOKENS),
        lambda v: ref.loss(CONFIG, v, TOKENS), variables)


@functools.cache
def program(attention):
    model = small_model(attention_impl=attention)
    return _traced(
        lambda v: model.apply(
            {k: v[k] for k in ("params", "moe_state")},
            TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1]),
        lambda v: program_losses(model, v, TOKENS),
        lambda v: program_loss(model, v, TOKENS), sound()[0])


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_model_matches_plain_reference(attention):
    """Logits of both heads, both losses and every leaf of the gradient,
    with the reference attention and through the flash kernels (the
    Pallas interpreter): query and key channels 16 + 8 = the values' 24."""
    got, got_losses, _, got_grads = program(attention)
    _, (want, want_losses, _, want_grads) = sound()
    for name, a, b in zip(("logits", "mtp_logits"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, err_msg=name)
    np.testing.assert_allclose(got_losses, want_losses, atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("depart", [
    "bias_in_weights", "rope_per_head_key", "mtp_after_norm",
    "concat_swapped"])
def test_comparison_fails_on_a_seeded_departure(depart):
    variables, (_, _, sound_loss, _) = sound()
    got = program("reference")[2]
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.loss(CONFIG, v, TOKENS, depart))(
            variables)
    assert abs(got - sound_loss) < 1e-5
    assert abs(got - departed) > 1e-4


def _block(cfg, first_held, held):
    return Block(replace(cfg, routed_first_held=first_held,
                         routed_held=held), "mla", "routed")


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of sixteen.  Every share computes
    the same attention and the same shared expert, and its own experts'
    part of the routed sum: the routed parts of all four, with the rest
    counted ONCE, are the whole layer as the uncut reference gives it."""
    from horovod_tpu.ops.rope import rope_tables

    cfg = small_model().cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)
    tabs = rope_tables(positions, cfg.rope_dim, cfg.rope_theta)
    whole = _block(cfg, 0, 16)
    variables = jax.jit(whole.init)(jax.random.PRNGKey(4), x, positions,
                                    tabs)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    state = {"moe_state": variables["moe_state"]}

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 4],
                "experts_fc2": p["experts_fc2"][first:first + 4] * fc2_scale}
        return _block(cfg, first, 4).apply({"params": mine, **state}, x,
                                           positions, tabs)

    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)   # the stream, attention, shared
        total = alike + sum(share(first) - alike for first in (0, 4, 8, 12))
        uncut = ref.block({**CONFIG, "n_routed_experts": 16,
                           "first_held_expert": 0}, p,
                          variables["moe_state"]["bias"], x)
        one = share(4)
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # and one share alone is NOT the layer: it leaves out 12 experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


def _routed(x, router, bias, fc1, fc2, first_held=0):
    return moe.routed_experts(x, router, bias, fc1, fc2, top_k=3,
                              scaling=1.8, first_held=first_held,
                              dtype=jnp.float32)


def _layer(seed=5, n=96, d=32, experts=16, held=4, ff=24):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (n, d)),
            jax.random.normal(k[1], (d, experts)) * 0.3,
            jax.random.uniform(k[2], (experts,), minval=-0.05, maxval=0.05),
            jax.random.normal(k[3], (held, d, 2 * ff)) * 0.2,
            jax.random.normal(k[4], (held, ff, d)) * 0.2)


def test_nothing_is_dropped_when_every_token_chooses_the_same_expert():
    """A bias of +10 on expert 5 makes all 96 tokens choose it (a GShard
    capacity of 2 x 96 x 3 / 16 = 36 would drop 60 of them): its group
    holds 96 rows, the counter of dropped rows reads 0, and every token's
    output has the expert's term, as the reference computes it."""
    x, router, bias, fc1, fc2 = _layer()
    bias = bias.at[5].set(10.0)
    y, routing = _routed(x, router, bias, fc1, fc2, first_held=4)
    assert int(routing.group_sizes[1]) == 96
    assert int(routing.dropped) == 0
    assert int(routing.group_sizes.sum()) == 96 * 3
    config = {**CONFIG, "num_experts_per_tok": 3}
    blk = {"router": router, "experts_fc1": fc1, "experts_fc2": fc2}
    weights = ref.routing_weights(config, blk, bias, x)
    want = sum(weights[:, 4 + e, None] * ref._gated(x, fc1[e], fc2[e])
               for e in range(4))
    assert float(weights[:, 5].min()) > 0
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weights():
    x, router, bias, fc1, fc2 = _layer()
    scores = jax.nn.sigmoid(x @ router)
    plain = moe.route(x, router, jnp.zeros_like(bias), top_k=3, scaling=1.8,
                      first_held=4, held=4)
    biased = moe.route(x, router, bias.at[7].set(0.5), top_k=3, scaling=1.8,
                       first_held=4, held=4)
    assert int((biased.experts == 7).sum()) > int((plain.experts == 7).sum())
    for routing in (plain, biased):
        chosen = jnp.take_along_axis(scores, routing.experts, axis=-1)
        np.testing.assert_allclose(
            routing.weights, chosen / chosen.sum(-1, keepdims=True) * 1.8,
            rtol=1e-6)
    # a program that let the bias into the weights would give it a gradient
    grad = jax.grad(lambda b: _routed(x, router, b, fc1, fc2, 4)[0].sum())(
        bias)
    assert float(jnp.abs(grad).max()) == 0.0


def test_the_balancing_update_evens_the_load_and_moves_no_weight():
    """``rebalanced`` is the aux-free update the configuration names
    (``noaux_tc``): every expert's bias moves by ``rate`` against the sign
    of its load's distance from the mean.  The load counts the slots of
    ALL experts, the held ones' part of it being the rows the grouped
    matmul sees; a router that overloads four experts is brought back to
    an even share, step by step, by the bias alone."""
    x, router, _, _, _ = _layer(n=512)
    x = x.at[:, 0].set(1.0)             # one channel every token shares
    router = router.at[0, 4:8].add(0.6)  # pulls every token to four experts
    state = {"layer": {"bias": jnp.zeros((16,))}}

    def routed(state):
        return moe.route(x, router, state["layer"]["bias"], top_k=3,
                         scaling=1.8, first_held=4, held=4)

    first = routed(state)
    assert first.load.sum() == 512 * 3
    assert first.load[4:8].tolist() == first.group_sizes[:4].tolist()
    even = 512 * 3 * 4 / 16
    assert int(first.group_sizes[:4].sum()) > 1.25 * even
    once = moe.rebalanced(state, {"layer": {"load": first.load}}, 0.01)
    np.testing.assert_allclose(
        once["layer"]["bias"],
        0.01 * jnp.sign(first.load.mean() - first.load), rtol=1e-6)
    for _ in range(60):
        state = moe.rebalanced(
            state, {"layer": {"load": routed(state).load}}, 0.01)
    last = routed(state)
    assert abs(int(last.group_sizes[:4].sum()) / even - 1.0) < 0.1
    # the weights are the scores' alone, whatever the bias has become
    chosen = jnp.take_along_axis(jax.nn.sigmoid(x @ router), last.experts,
                                 axis=-1)
    np.testing.assert_allclose(
        last.weights, chosen / chosen.sum(-1, keepdims=True) * 1.8,
        rtol=1e-5)


def test_the_rows_of_the_last_step_are_state_and_not_parameters():
    """``moe_stats`` holds each expert layer's rows per held expert, its
    dropped rows and the load of every routed expert; ``moe_state`` the
    selection bias, seeded non-zero;
    neither is under ``params``.  ``publish_stats`` turns the first into
    the registry's gauges."""
    from horovod_tpu.obs.registry import MetricsRegistry

    model = small_model()
    variables = init(model)
    assert set(variables) == {"params", "moe_state", "moe_stats"}
    assert set(variables["moe_state"]) == {"block1", "block2", "mtp"}
    assert float(jnp.abs(variables["moe_state"]["block1"]["bias"]).min()) > 0
    _, new = jax.jit(lambda v: model.apply(
        v, TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1],
        mutable=["moe_stats"]))(variables)
    registry = MetricsRegistry()
    stats = moe.publish_stats(new["moe_stats"], registry)
    assert set(stats) == {"block1", "block2", "mtp/block"}
    routing = moe.route(jnp.zeros((4, 8)), jnp.zeros((8, 16)),
                        jnp.arange(16.0), top_k=3, scaling=1.0,
                        first_held=12, held=4)
    assert routing.group_sizes.tolist() == [0, 4, 4, 4, 0]
    for layer, entry in stats.items():
        rows = new["moe_stats"]
        for part in layer.split("/"):
            rows = rows[part]
        assert entry["rows_held"] == int(rows["rows"].sum()) > 0
        assert entry["rows_dropped"] == 0
        assert entry["max_over_mean"] >= 1.0
        assert registry.gauge("moe.rows_held", layer=layer).value == \
            entry["rows_held"]
    # an apply that does not ask for the counters leaves them alone
    assert jax.eval_shape(model.apply, variables,
                          TOKENS[:, :SEQ]).shape == (2, SEQ, 256)


# ------------------------------------------------------------ the row bound
# The sort puts the held experts' rows first, so the layer computes on the
# first ``row_bound`` rows of it (two even shares, in whole row tiles of
# the grouped matmul) and on all ``n * top_k`` only in a step whose held
# rows pass that.  512 tokens, 3 a token, experts 4 and 5 held of 16: two
# shares are 384 rows, one tile of 512, a third of the 1536 slots.

@pytest.mark.parametrize("shape,rows", [
    ((8192, 4, 8, 64), 8192),      # glm47f_train_s8192: a quarter of 32768
    ((8192, 8, 16, 128), 16384),   # trinitym_train_s8192: of 65536
    ((8192, 4, 64, 64), 32768),    # every expert held: all the slots
    ((1000, 4, 2, 16), 1024),      # 1000 rows of share, in tiles of 512
    ((512, 3, 2, 16), 512),        # the layers below
    ((96, 3, 4, 16), 288),         # a tile holds more than the slots: all
])
def test_the_row_bound_follows_from_the_shapes(shape, rows):
    assert moe.row_bound(*shape) == rows


def _branches(fn, *args):
    def count(jaxpr):
        return sum((eqn.primitive.name == "cond") + sum(
            count(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("held,branches", [(16, 0), (2, 1)])
def test_a_layer_that_holds_every_expert_has_no_branch(held, branches):
    """``held == E``: the bound is every slot and the program has no
    ``cond`` in it; an eighth held: one."""
    layer = _layer(n=512, held=held)
    assert _branches(lambda *a: _routed(*a)[0], *layer) == branches
    assert bool(_routed(*layer)[1].overflowed) is False


def _outcome(layer, dtype, first_held=4):
    """``y``, the routing, and the gradients of a weighted sum of ``y``
    by the tokens, the router and both expert matrices."""
    x, router, bias, fc1, fc2 = layer
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run(x, router, fc1, fc2):
        y, routing = moe.routed_experts(
            x, router, bias, fc1, fc2, top_k=3, scaling=1.8,
            first_held=first_held, dtype=dtype)
        return (y.astype(jnp.float32) * probe).sum(), (y, routing)

    (_, (y, routing)), grads = jax.value_and_grad(
        run, argnums=(0, 1, 2, 3), has_aux=True)(x.astype(dtype), router,
                                                 fc1, fc2)
    return y, routing, grads


def _whole_buffer(monkeypatch):
    """So many shares that the bound is every slot: the layer as it is
    where every expert is held, one computation on ``n * top_k`` rows."""
    monkeypatch.setattr(moe, "ROW_BOUND_SHARES", 10 ** 6)


def _assert_bitwise(ours, whole):
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), ours, whole)


def _assert_last_bit(ours, whole):
    """Within 1e-6 of the largest element, a float32 sum's last bit, and
    for a bfloat16 array one rounding of the element besides (the last
    bit of the float32 sum it was rounded from decides a tie now and
    then: one element of 8192).  Two XLA programs need not round
    a float32 sum alike, and off the chip they do not: the CPU's compiler
    contracts a multiply and an add of the weighted sum into one rounding
    in one program and not in the other, and the gradient of the stand-in
    (``lax.ragged_dot``) is a dense contraction over ALL the rows it was
    handed, a held expert's among zeros, which the CPU's matmul blocks
    otherwise over 512 rows than over 1536 (the chip's ``tgmm`` walks a
    group's own row tiles, the same on both sides)."""
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=2.0 ** -7 if b.dtype == jnp.bfloat16 else 0,
        atol=1e-6 * float(jnp.abs(b).max())), ours, whole)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_bounded_rows_give_the_whole_buffers_result_bit_for_bit(
        dtype, monkeypatch):
    """The fast side against the computation on all 1536 rows (a row is
    computed from its own group's matrix alone, and a row past the bound
    adds an exact zero either way): ``y`` and the gradients of the
    tokens, the router and both expert matrices every bit in bfloat16,
    the cells' dtype, and to float32's last bit in float32
    (``_assert_last_bit`` says why not closer)."""
    layer = _layer(n=512, held=2)
    y, routing, grads = _outcome(layer, dtype)
    assert not bool(routing.overflowed) and int(routing.dropped) == 0
    assert 0 < int(routing.group_sizes[:2].sum()) <= 512
    _whole_buffer(monkeypatch)
    whole_y, whole_routing, whole_grads = _outcome(layer, dtype)
    assert _branches(lambda *a: _routed(*a)[0], *layer) == 0
    _assert_bitwise(routing.weights, whole_routing.weights)
    (_assert_bitwise if dtype == jnp.bfloat16 else _assert_last_bit)(
        (y, grads), (whole_y, whole_grads))
    assert float(jnp.abs(grads[1]).max()) > 0      # the router's is there


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_more_rows_than_the_bound_take_the_whole_buffer(dtype, monkeypatch):
    """A bias of +10 on expert 5 sends all 512 tokens to it: with expert
    4's rows the held experts get more than the 512 rows of the bound, the
    layer says so, runs on all 1536 rows and drops nothing: every bit of
    the expert matrices' gradients is the whole-buffer computation's
    (this side contracts over 1536 rows too); ``y`` and the tokens' and
    the router's gradients to the last bit (the branch is a program of
    its own: ``_assert_last_bit``)."""
    x, router, bias, fc1, fc2 = _layer(n=512, held=2)
    layer = (x, router, bias.at[5].set(10.0), fc1, fc2)
    y, routing, grads = _outcome(layer, dtype)
    assert bool(routing.overflowed) and int(routing.dropped) == 0
    assert int(routing.group_sizes[1]) == 512
    assert int(routing.group_sizes[:2].sum()) > moe.row_bound(512, 3, 2, 16)
    _whole_buffer(monkeypatch)
    whole_y, whole_routing, whole_grads = _outcome(layer, dtype)
    assert not bool(whole_routing.overflowed)      # no bound, none passed
    _assert_bitwise(grads[2:], whole_grads[2:])
    _assert_last_bit((y, grads[:2]), (whole_y, whole_grads[:2]))
    assert float(jnp.abs(y).min(axis=-1).max()) > 0


def _small_tiles(monkeypatch, shares):
    """The small model routes 64 tokens x 3: a row tile of 512 holds all
    192 slots and its layers have no branch.  With tiles of 16 rows and
    ``shares`` even shares of 48, the bound is under the slots."""
    monkeypatch.setattr(moe, "GMM_ROW_TILE", 16)
    monkeypatch.setattr(moe, "ROW_BOUND_SHARES", shares)


def test_the_overflow_counter_counts_steps_and_is_published(monkeypatch):
    """``moe_stats`` carries ``overflow_steps`` a layer from step to
    step: a selection bias that sends every token of block 1 to two held
    experts puts it over its 96 rows in each of two steps, the other
    layers stay under theirs, nothing is dropped, and ``publish_stats``
    sets the gauge ``moe.overflow_steps{layer}``."""
    from horovod_tpu.obs.registry import MetricsRegistry

    _small_tiles(monkeypatch, shares=2)
    model = small_model()
    variables = init(model)
    assert int(variables["moe_stats"]["block1"]["overflow_steps"]) == 0
    skewed = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf.at[5:7].set(10.0)
        if "block1" in jax.tree_util.keystr(path) else leaf,
        variables["moe_state"])
    variables = {**variables, "moe_state": skewed}
    apply = jax.jit(lambda v: model.apply(
        v, TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1],
        mutable=["moe_stats"]))
    for step in (1, 2):
        _, new = apply(variables)
        variables = {**variables, "moe_stats": new["moe_stats"]}
        registry = MetricsRegistry()
        stats = moe.publish_stats(new["moe_stats"], registry)
        assert {layer: entry["overflow_steps"]
                for layer, entry in stats.items()} == {
            "block1": step, "block2": 0, "mtp/block": 0}
        assert stats["block1"]["rows_held"] > 96 >= max(
            stats["block2"]["rows_held"], stats["mtp/block"]["rows_held"])
        assert all(entry["rows_dropped"] == 0 for entry in stats.values())
        assert registry.gauge("moe.overflow_steps",
                              layer="block1").value == step


@pytest.mark.parametrize("policy", ["dots_with_no_batch_dims_saveable",
                                    "nothing_saveable"])
def test_a_rematerialised_block_keeps_both_sides_of_the_bound_exact(
        policy, monkeypatch):
    """Under ``nn.remat`` with ``block_remat_policy`` (the case of
    tests/remat_cases.py: latent attention, routed experts, a prediction
    module) the bounded layers give the loss and the gradients of the
    model whose layers have no bound.  One even share of 48 rows for a
    bound, so that the seeded routing puts some layers over it and leaves
    some under: both sides of the branch run, forward, recomputed and
    backward."""
    model = small_model(remat=True, remat_policy=policy)
    variables = init(model)

    def outcome():  # a function of its own a call: traced anew each time
        return jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, {**variables, "params": p},
                                   TOKENS)))(variables["params"])

    unbounded = outcome()
    _small_tiles(monkeypatch, shares=1)
    _, new = jax.jit(lambda v: model.apply(
        v, TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1],
        mutable=["moe_stats"]))(variables)
    over = [entry["overflow_steps"]
            for entry in moe.publish_stats(new["moe_stats"]).values()]
    assert sorted(set(over)) == [0, 1]
    loss, grads = outcome()
    np.testing.assert_allclose(float(loss), float(unbounded[0]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        grads, unbounded[1])


def test_the_tile_fill_gauge_is_set_while_the_step_is_traced():
    """``moe.gmm_tile_fill{layer}``, under the layers' names that
    ``publish_stats`` uses: every grouped matmul's tiles divide it."""
    from horovod_tpu.obs.registry import get_registry, reset_registry

    reset_registry()
    model = small_model()
    jax.eval_shape(lambda v: model.apply(
        v, TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1],
        mutable=["moe_stats"]), init(model))
    fills = {m["tags"]["layer"]: m["value"]
             for m in get_registry().snapshot()
             if m["name"] == "moe.gmm_tile_fill"}
    assert fills == {"block1": 1.0, "block2": 1.0, "mtp/block": 1.0}


PUBLISHED = dict(
    vocab_size=154880, num_layers=47, emb_dim=2048, num_heads=20, kv_heads=20,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6, norm_eps=1e-5,
    routed_experts=64, held_experts=64, routed_top_k=4, routed_width=1536,
    routed_scaling=1.8, shared_experts=1, dense_layers_first=1,
    mtp_modules=1, max_len=202752, tie_embeddings=False, use_bias=False,
    norm="rmsnorm", mlp="silu_gated", pos_embedding="rope")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS["glm-4.7-flash"]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.mlp_ratio * cfg.emb_dim == 10240
    assert set(cfg.layer_types) == {"mla"} and len(cfg.layer_types) == 47
    assert [cfg.ffn_type(i) for i in (0, 1, 46)] == [
        "dense", "routed", "routed"]


def test_the_cut_counts_706518528_parameters():
    """The benchmark's cut from the named size: depth 47 -> 5 (the dense
    layer and four expert layers), 8 of 64 experts held, an eighth of the
    vocabulary; every width as published (ISSUE 32 has the sum)."""
    model = gpt("glm-4.7-flash", num_layers=5, layer_types=("mla",) * 5,
                routed_held=8, vocab_size=19360)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    p = shapes["params"]
    assert count(p["block0"]) == 84_677_888
    assert count(p["block1"]) == 106_829_056
    assert count(p["mtp"]) == 115_223_808
    assert count(p["wte"]) == count(p["head"]) == 39_649_280
    assert count(p) == 706_518_528
    # the selection bias and the counters are state: no gradient, no moment
    assert count(shapes["moe_state"]) == 5 * 64
    # per expert layer: rows of 8 held experts, rows dropped, the load
    # of all 64, and the steps in which the layer passed its row bound
    assert count(shapes["moe_stats"]) == 5 * (8 + 1 + 64 + 1)


def _refusals():
    from horovod_tpu.models import decode
    from horovod_tpu.models.transformer import raw_block_forward
    from horovod_tpu.parallel import pipeline, tensor_parallel
    from horovod_tpu.serve.engine import SlotEngine

    x = jnp.zeros((1, 8, 64))
    return {
        "generate": lambda c, t: decode.generate(c, {}, t, 4),
        "prefill": lambda c, t: decode.prefill(c, {}, t),
        "decode_step": lambda c, t: decode.decode_step(c, {}, None, t[:, 0]),
        "init_cache": lambda c, t: decode.init_cache(c, 1),
        "init_paged_pool": lambda c, t: decode.init_paged_pool(c, 4, 8, 2),
        "slot_engine": lambda c, t: SlotEngine(c, {}, 2),
        "stack_tp_params": lambda c, t: tensor_parallel.stack_tp_params(
            {}, c, 2),
        "tp_gpt_apply": lambda c, t: tensor_parallel.tp_gpt_apply(
            {}, {}, c, t, "tp"),
        "stack_pp_params": lambda c, t: pipeline.stack_pp_params({}, c, 2),
        "pp_gpt_apply": lambda c, t: pipeline.pp_gpt_apply(
            {}, {}, c, t, "pp", microbatches=1),
        "raw_block_forward": lambda c, t: raw_block_forward(
            c, {}, x, jnp.arange(8), None),
    }


# Decode, serve, tensor and pipeline parallelism build GPT-2's block from
# raw weights: they refuse latent attention and window / full attention
# layers (by ``layer_types``), routed experts, a prediction module, and
# each setting of the attention layer that Trinity-Mini (``afmoe``)
# brought (a head size of its own, rotary in some layer types only, head
# norms, the output gate, four norms a block) by name, before anything is
# traced.
@pytest.mark.parametrize("setting", [
    "layer_types", "routed_experts", "mtp_modules", "sliding_attention",
    "head_size", "rope_layer_types", "qk_norm", "attention_gate",
    "post_norms"])
@pytest.mark.parametrize("path", sorted(_refusals()))
def test_paths_refuse_what_they_cannot_run(path, setting):
    gpt2 = gpt("nano").cfg
    cfg = {"layer_types": small_model().cfg,
           "routed_experts": replace(gpt2, mlp="silu_gated",
                                     routed_experts=8, routed_top_k=2,
                                     routed_width=32),
           "mtp_modules": replace(gpt2, mtp_modules=1),
           "sliding_attention": replace(
               gpt2, attention_window=8, layer_types=(
                   "sliding_attention", "sliding_attention",
                   "full_attention")),
           "head_size": replace(gpt2, head_size=64),
           "rope_layer_types": replace(gpt2, pos_embedding="rope",
                                       rope_layer_types=("attention",)),
           "qk_norm": replace(gpt2, qk_norm=True),
           "attention_gate": replace(gpt2, attention_gate=True),
           "post_norms": replace(gpt2, post_norms=True)}[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("override,message", [
    ({"moe_experts": 4}, "two expert layers"),
    ({"mlp": "gelu"}, "routed experts are silu-gated"),
    ({"routed_top_k": 17}, "routed_top_k"),
    ({"routed_first_held": 14}, "held experts"),
    ({"mtp_modules": 2}, "one prediction module"),
    ({"pos_embedding": "learned"}, "pos_embedding must be 'rope'"),
    ({"v_head_dim": 0}, "needs positive"),
    # what Trinity-Mini's settings cannot mean
    ({"layer_types": ("mla", "mla", "sliding_attention")},
     "attention_window must be set"),
    ({"layer_types": ("mla", "mla", "windowed")}, "layer_types must name"),
    ({"head_size": 0}, "head_size=0 must be positive"),
    # (a latent layer that no rotation names sees no positions since
    # PR 51: tests/test_kimi_linear.py)
    ({"q_lora_rank": -1}, "q_lora_rank may be 0"),
    ({"rope_layer_types": ("mla", "mamba")}, "rope_layer_types names"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_flash_takes_unequal_head_sizes():
    """Values narrower than the keys: the flash kernels have taken them
    since PR 42 and ``mla_mixer``'s refusal, older than that, went in
    PR 51; the flash path and the reference path agree."""
    variables = jax.jit(small_model(v_head_dim=16).init)(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ])
    with jax.default_matmul_precision("highest"):
        flash, plain = (
            jax.jit(small_model(attention_impl=impl, v_head_dim=16).apply)(
                variables, TOKENS[:, :SEQ])[0]
            for impl in ("flash", "reference"))
    np.testing.assert_allclose(flash, plain, atol=2e-4)
