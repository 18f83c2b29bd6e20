"""SmallThinker-21BA3B's mechanisms on the training path (``model_name:
smallthinker_21b_instruct``): a router that reads the layer's input ahead
of attention (a decision made from one tensor, applied to another), its
weights a softmax over the chosen logits with no selection bias,
ReLU-gated experts, a load-balance loss, full layers without positions
beside window layers that rotate, seven query heads to a key/value head.
The program (``models/transformer.py``, ``parallel/moe.py``) against the
benchmark's own plain reference
(``benchmark/configs/smallthinker-21ba3b-instruct.reference.py``) on
seeded weights; the four shares of the experts adding up to the uncut
layer; ``routed_experts`` on one tensor every bit what the commit before
computed; the published values of the named size and the count of its
cut; the paths that refuse the new settings; the trees of the other named
sizes unchanged.
All on the CPU at small sizes: hidden 64, 7 query heads over 1 key/value
head of 16, 8 experts of width 32, 3 a token, a window of 8 in 32 tokens,
one full layer and three window layers.
"""

import functools
import hashlib
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (GPT_CONFIGS, Block,
                                            TransformerConfig, gpt)
from horovod_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "smallthinker-21ba3b-instruct"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("smallthinker_reference",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

KINDS = ("full_attention",) + ("sliding_attention",) * 3
COEF = 0.05
SMALL = dict(
    num_layers=4, layer_types=KINDS, vocab_size=256, emb_dim=64,
    num_heads=7, num_kv_heads=1, head_size=16, attention_window=8,
    routed_experts=8, routed_held=2, routed_first_held=4, routed_top_k=3,
    routed_width=32, routed_balance_coef=COEF, max_len=64,
    attention_impl="reference",
    # several tiles a row, and a window that is a multiple of neither
    flash_block_q=16, flash_block_k=4, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=7, num_key_value_heads=1,
    head_dim=16, sliding_window_size=8, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], rope_theta=1.5e6, rms_norm_eps=1e-6,
    moe_num_primary_experts=2, first_held_expert=4,
    moe_num_active_primary_experts=3, balance_loss_coef=COEF)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded parameters; the router ten times its initial size so that
    the logits spread at this width, and the norms' weights away from
    1."""
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

    return {"params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program_logprob(model, variables, tokens):
    logits = model.apply(variables, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def program_loss(model, variables, tokens):
    """As the step writes it: cross-entropy plus the coefficient times
    the balance losses the expert layers sowed."""
    logits, sown = model.apply(variables, tokens[:, :-1],
                               mutable=["losses"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.mean() + model.cfg.routed_balance_coef * sum(
        jax.tree.leaves(sown.get("losses", {})))


def _outcome(logprob, loss, variables):
    """The labels' log-probabilities, the loss and the gradient of the
    loss in ``params``, from one trace."""
    def run(v):
        grads = jax.grad(lambda p: loss({"params": p}))(v["params"])
        return logprob(v), loss(v), grads

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(variables)


@functools.cache
def seeded():
    """The seeded variables: the same whatever the attention's form, the
    balance loss's coefficient and the router's placement."""
    return init(small_model())


@functools.cache
def sound(coef=COEF):
    """What the plain reference gives for the seeded variables, computed
    once a coefficient."""
    config = {**CONFIG, "balance_loss_coef": coef}
    batch = {"tokens": TOKENS}
    return _outcome(lambda v: ref.logprob(config, v, batch),
                    lambda v: ref.loss(config, v, batch), seeded())


@functools.cache
def program(attention="reference", coef=COEF):
    model = small_model(attention_impl=attention, routed_balance_coef=coef)
    return _outcome(lambda v: program_logprob(model, v, TOKENS),
                    lambda v: program_loss(model, v, TOKENS), seeded())


@pytest.mark.parametrize("coef", [0.0, COEF], ids=["plain", "balanced"])
@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_model_matches_plain_reference(attention, coef):
    """The loss, every label's log-probability and every leaf of the
    gradient, with the reference attention and through the flash kernels
    (the Pallas interpreter, seven query heads on one key/value head),
    without the balance loss and with it."""
    got_logp, got_loss, got_grads = program(attention, coef)
    want_logp, want_loss, want_grads = sound(coef)
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_loss, want_loss, atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def _departed(depart):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda v: ref.loss(
            CONFIG, v, {"tokens": TOKENS}, depart))(seeded())


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart):
    got = program()[1]
    assert abs(got - sound()[1]) < 1e-5
    assert abs(got - _departed(depart)) > 1e-4


def test_the_late_router_chooses_other_experts_and_is_the_other_setting():
    """The router's placement changes only which experts are chosen and
    with which weights: from the layer's input and from the normed stream
    after attention the choices differ, and the program with the usual
    placement (``routed_router_input="ffn_input"``) is the reference's
    ``router_after_attention`` departure, not the sound reference."""
    late = small_model(routed_router_input="ffn_input")
    variables = seeded()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v: program_loss(late, v, TOKENS))(variables)
        assert abs(got - _departed("router_after_attention")) < 1e-5
        assert abs(got - sound()[1]) > 1e-4
        blk = variables["params"]["block1"]
        x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
        after = x + ref._attention(CONFIG, blk, ref._rms_norm(
            x, blk["ln1"]["scale"], 1e-6), True, True)
        u = ref._rms_norm(after, blk["ln2"]["scale"], 1e-6)
    chosen = lambda t: moe.route(
        t.reshape(-1, 64), blk["router"], None, top_k=3, scaling=1.0,
        first_held=0, held=8, score_rule="softmax_chosen").experts
    moved = np.asarray(jnp.sort(chosen(x)) != jnp.sort(chosen(u))).any(-1)
    assert 0.2 < moved.mean() <= 1.0


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of eight, three a token.  Every
    share computes the same router decision and the same attention, and
    its own experts' part of the routed sum: the routed parts of all
    four, with the rest counted ONCE, are the whole layer as the uncut
    reference gives it."""
    from horovod_tpu.ops.rope import rope_tables

    cfg = small_model(routed_held=8, routed_first_held=0).cfg
    kind = "sliding_attention"
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)
    tabs = rope_tables(positions, cfg.rope_dim, cfg.rope_theta)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), kind, "routed")

    variables = jax.jit(block(0, 8).init)(jax.random.PRNGKey(4), x,
                                          positions, tabs)
    assert "moe_state" not in variables          # no selection bias
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply({"params": mine}, x, positions, tabs)

    config = {**CONFIG, "moe_num_primary_experts": 8,
              "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)       # the stream and attention
        total = alike + sum(share(first) - alike
                            for first in range(0, 8, 2))
        uncut, _ = ref._block(config, p, x, True, True)
        one = share(2)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    # and one share alone is NOT the layer: it leaves out six experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


def test_softmax_over_the_chosen_is_the_renormalised_full_softmax():
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(k[0], (64, 16))
    router = jax.random.normal(k[1], (16, 8))
    routing = moe.route(x, router, None, top_k=3, scaling=1.0, first_held=2,
                        held=4, score_rule="softmax_chosen")
    np.testing.assert_allclose(routing.weights.sum(-1), 1.0, atol=1e-6)
    full = jax.nn.softmax(jnp.dot(x, router, precision="highest"), axis=-1)
    picked = jnp.take_along_axis(full, routing.experts, axis=-1)
    np.testing.assert_allclose(
        routing.weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    # the largest logits, no bias: the choice is the full softmax's top 3
    np.testing.assert_array_equal(
        jnp.sort(routing.experts), jnp.sort(jax.lax.top_k(full, 3)[1]))
    assert int(routing.load.sum()) == 64 * 3 and int(routing.dropped) == 0
    with pytest.raises(ValueError, match="takes no selection bias"):
        moe.route(x, router, jnp.zeros((8,)), top_k=3, scaling=1.0,
                  first_held=0, held=8, score_rule="softmax_chosen")
    with pytest.raises(ValueError, match="score_rule must be one of"):
        moe.route(x, router, None, top_k=3, scaling=1.0, first_held=0,
                  held=8, score_rule="softmax")


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_gates_handwritten_backward_is_jax_grads(activation):
    """``grouped_ffn``'s rule (``_ffn_bwd``: the gate through
    ``jax.vjp(_gate)``, the matmuls by hand) against ``jax.grad`` of the
    same feed-forward written a row at a time, zeros included: two rows
    are zero, so their gates are exactly 0, where ReLU's slope is 0 on
    both sides of the comparison."""
    k = jax.random.split(jax.random.PRNGKey(6), 4)
    rows, d, ff, held = 12, 8, 6, 3
    sizes = jnp.asarray([4, 5, 2, 1], jnp.int32)   # the last: no expert
    group = np.repeat(np.arange(4), np.asarray(sizes))
    xs = jax.random.normal(k[0], (rows, d)).at[jnp.asarray([1, 6])].set(0.0)
    gate_up = jax.random.normal(k[1], (held, d, 2 * ff))
    down = jax.random.normal(k[2], (held, ff, d))
    probe = jax.random.normal(k[3], (rows, d))
    act = {"silu": jax.nn.silu, "relu": lambda g: jnp.where(g > 0, g, 0.0)}

    def plain(xs, gate_up, down):
        out = []
        for r in range(rows):
            if group[r] == held:
                out.append(jnp.zeros((d,)))
                continue
            h = xs[r] @ gate_up[group[r]]
            out.append((act[activation](h[:ff]) * h[ff:]) @ down[group[r]])
        return (jnp.stack(out) * probe).sum()

    def ours(xs, gate_up, down):
        ys = moe.grouped_ffn(xs, gate_up, down, sizes, dtype=jnp.float32,
                             interpret=True, activation=activation)
        return (ys * probe).sum()

    with jax.default_matmul_precision("highest"):
        want, got = (
            jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(xs, gate_up, down)
            for f in (plain, ours))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7), got, want)
    assert float(jnp.abs(got[1][0][jnp.asarray([1, 6])]).max()) == 0.0


# sha256 (16 digits) over y, the routing's weights, experts, order, group
# sizes and load, and the gradients by the tokens, the router and both
# expert matrices, as the commit before this file (PR 42's tree) computes
# them on the CPU in float32: GLM-4.7-Flash's routing (4 a token of 64, 8
# held from expert 8 on, scaling 1.8) and Trinity-Mini's (8 of 128, 16
# held from expert 16 on, scaling 2.826), under the row bound and, with a
# skewed bias, over it.  The bfloat16 cases have no digest (``None``): a
# bfloat16 matmul's last bit is the host CPU's (two of the four digests
# that stood here passed on one checking machine and failed on the next,
# PR 48), so they are held to what no machine moves: the choice, the sort
# and the counts exactly those of the float32 case on the same rounded
# tokens, the float outputs within ``BF16_OF_FLOAT32`` of it.
PARENTS = {
    ("glm", "bfloat16", 0.0): (None, False),
    ("trinity", "bfloat16", 0.0): (None, False),
    ("glm", "bfloat16", 10.0): (None, True),
    ("trinity", "bfloat16", 10.0): (None, True),
    ("glm", "float32", 0.0): ("c318dfe0b707aa3f", False),
    ("trinity", "float32", 0.0): ("89be5dbba7e11726", False),
    ("glm", "float32", 10.0): ("873b351ba66b9a7b", True),
    ("trinity", "float32", 10.0): ("ff388538d38a99c7", True),
}
ROUTINGS = {"glm": dict(n=512, experts=64, held=8, first=8, top_k=4,
                        scaling=1.8),
            "trinity": dict(n=256, experts=128, held=16, first=16, top_k=8,
                            scaling=2.826)}
# The largest difference of a bfloat16 result from the float32 one, over
# the float32 one's largest entry: bfloat16 keeps eight bits (2 ** -8 =
# 0.0039 a rounding) and a result here is three matmuls deep with sums
# over 24 to 32 products; the readings of the four cases on this machine
# lie between 0.0037 and 0.0114 (the weights are float32 in both and
# read 0), so the limit stands two and a half times over the largest.
BF16_OF_FLOAT32 = 0.03


@pytest.mark.parametrize("which,dtype,skew", sorted(PARENTS))
def test_routed_experts_on_one_tensor_is_what_the_parent_computed(
        which, dtype, skew):
    """``routed_experts`` is now a decision and its application in turn;
    on one tensor, with sigmoid scores, a bias and a silu gate, it is the
    one function it was: forward, the routing and all four gradients, on
    both sides of the row bound.  In float32 bit for bit what the parent
    computed; in bfloat16 bit for bit its own two halves called in turn,
    its discrete outputs those of the float32 case and its float outputs
    within a stated limit of it."""
    shape, dtype = ROUTINGS[which], jnp.dtype(dtype)
    n, experts, held, first = (shape[k] for k in (
        "n", "experts", "held", "first"))
    d, ff = 32, 24
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(k[0], (n, d)).astype(dtype)
    router = jax.random.normal(k[1], (d, experts)) * 0.3
    bias = jax.random.uniform(k[2], (experts,), minval=-0.05, maxval=0.05)
    bias = bias.at[first:first + 2].add(skew)
    fc1 = jax.random.normal(k[3], (held, d, 2 * ff)) * 0.2
    fc2 = jax.random.normal(k[4], (held, ff, d)) * 0.2
    probe = jax.random.normal(k[5], x.shape)

    def whole(x, router, fc1, fc2, dtype):
        return moe.routed_experts(
            x, router, bias, fc1, fc2, top_k=shape["top_k"],
            scaling=shape["scaling"], first_held=first, dtype=dtype)

    def halves(x, router, fc1, fc2, dtype):
        routing = moe.routing_decision(
            x, router, bias, top_k=shape["top_k"], scaling=shape["scaling"],
            first_held=first, held=held)
        return moe.apply_routing(routing, x, fc1, fc2, dtype=dtype)

    def results(layer, x, dtype):
        def run(x, router, fc1, fc2):
            y, routing = layer(x, router, fc1, fc2, dtype)
            return (y.astype(jnp.float32) * probe).sum(), (y, routing)

        (_, (y, routing)), grads = jax.value_and_grad(
            run, argnums=(0, 1, 2, 3), has_aux=True)(x, router, fc1, fc2)
        assert routing.balance is None      # nothing computed that was not
        discrete = (routing.experts, routing.order, routing.group_sizes,
                    routing.load, routing.overflowed)
        return (y, routing.weights, *grads), discrete

    floats, discrete = results(whole, x, dtype)
    digest, overflowed = PARENTS[which, dtype.name, skew]
    assert bool(discrete[-1]) == overflowed
    if digest is not None:
        sha = hashlib.sha256()
        y, weights, *grads = floats
        for a in (y, weights, *discrete[:-1], *grads):
            sha.update(np.asarray(a).tobytes())
        assert sha.hexdigest()[:16] == digest
        return
    # the two halves in turn, in this dtype on this machine: every bit
    for got, want in zip(results(halves, x, dtype), (floats, discrete)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(b.astype(jnp.float32)))
    # the float32 case on the same (rounded) tokens: the choice is made in
    # float32 whatever the stream's dtype, so it is the same choice
    exact, same_choice = results(whole, x.astype(jnp.float32), jnp.float32)
    for a, b in zip(discrete, same_choice):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name, a, b in zip(("y", "weights", "dx", "drouter", "dfc1", "dfc2"),
                          floats, exact):
        apart = float(jnp.abs(a.astype(jnp.float32) - b).max())
        assert apart <= BF16_OF_FLOAT32 * float(jnp.abs(b).max()), (
            name, apart, float(jnp.abs(b).max()))


def test_a_decision_from_one_tensor_is_applied_to_another():
    """The two halves on two tensors: the decision from ``x``, the rows
    dispatched from ``u``, against every expert applied to every row of
    ``u`` and weighted by ``x``'s choice."""
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    n, d, ff, experts, held, first = 64, 16, 12, 8, 4, 2
    x, u = (jax.random.normal(k[i], (n, d)) for i in (0, 1))
    router = jax.random.normal(k[2], (d, experts))
    fc1 = jax.random.normal(k[3], (held, d, 2 * ff)) * 0.3
    fc2 = jax.random.normal(k[4], (held, ff, d)) * 0.3
    with jax.default_matmul_precision("highest"):
        routing = moe.routing_decision(
            x, router, None, top_k=3, scaling=1.0, first_held=first,
            held=held, score_rule="softmax_chosen")
        y, applied = moe.apply_routing(routing, u, fc1, fc2,
                                       dtype=jnp.float32, activation="relu")
        dense = jnp.zeros((n, experts)).at[
            jnp.arange(n)[:, None], routing.experts].set(routing.weights)
        gate, up = jnp.split(jnp.einsum("nd,edf->enf", u, fc1), 2, axis=-1)
        outs = jnp.einsum("enf,efd->end", jnp.maximum(gate, 0) * up, fc2)
        want = jnp.einsum("ne,end->nd", dense[:, first:first + held], outs)
    np.testing.assert_allclose(y, want, atol=1e-4)
    assert routing.inverse is not None and int(applied.dropped) == 0
    np.testing.assert_array_equal(routing.order[routing.inverse],
                                  jnp.arange(n * 3))


def test_the_balance_loss_is_one_at_an_even_load():
    """Eight tokens, each with three large logits, arranged so that every
    expert is chosen three times: ``f_e`` is an eighth for each, ``P``
    sums to one, so the loss is exactly 1; with every token on the same
    three experts it is ``8 x 3 x (1/3) x P_e`` = 8/3 as the softmax
    sharpens."""
    eye = jnp.eye(8)
    even = 20.0 * (eye + jnp.roll(eye, 1, axis=1) + jnp.roll(eye, 2, axis=1))
    route = lambda logits: moe.route(
        logits, eye, None, top_k=3, scaling=1.0, first_held=0, held=2,
        score_rule="softmax_chosen", balance=True)
    np.testing.assert_allclose(route(even).balance, 1.0, atol=1e-6)
    np.testing.assert_array_equal(route(even).load, 3)
    same = jnp.tile(20.0 * (eye[0] + eye[1] + eye[2]), (8, 1))
    np.testing.assert_allclose(route(same).balance, 8.0 / 3.0, atol=1e-5)


def test_the_balance_loss_takes_its_gradient_through_the_mean_softmax():
    """``f`` is a count: the gradient by the router is that of
    ``E sum_e f_e P_e`` with ``f`` held fixed."""
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    x = jax.random.normal(k[0], (32, 16))
    router = jax.random.normal(k[1], (16, 8))
    route = lambda r: moe.route(
        x, r, None, top_k=3, scaling=1.0, first_held=0, held=8,
        score_rule="softmax_chosen", balance=True)
    share = route(router).load.astype(jnp.float32) / (32 * 3)

    def through_mean(r):
        full = jax.nn.softmax(jnp.dot(x, r, precision="highest"), axis=-1)
        return 8 * jnp.sum(share * full.mean(0))

    got = jax.grad(lambda r: route(r).balance)(router)
    np.testing.assert_allclose(got, jax.grad(through_mean)(router),
                               atol=1e-6)
    assert float(jnp.abs(got).max()) > 0


def test_the_router_traces_at_the_blocks_top_with_the_balance_inside():
    """Scope ``moe_route`` opens at the block's top where the router
    reads the layer's input (inside ``mlp`` where it reads the experts'),
    ``moe_balance`` inside it; the counters hold the balance loss and
    there is no selection bias to keep."""
    model = small_model()
    variables = seeded()
    text = jax.jit(lambda v, t: program_loss(model, v, t)).lower(
        variables, TOKENS).as_text(debug_info=True)
    assert "block0/moe_route/" in text
    assert "block0/moe_route/moe_balance/" in text
    assert "block0/mlp/moe_route" not in text
    assert "block0/mlp/" in text and "moe_dispatch/" in text
    late = small_model(routed_router_input="ffn_input")
    text = jax.jit(lambda v, t: program_loss(late, v, t)).lower(
        variables, TOKENS).as_text(debug_info=True)
    assert "block0/mlp/moe_route/" in text
    made = jax.jit(model.init)(jax.random.PRNGKey(0), TOKENS[:, :SEQ])
    assert set(made) == {"params", "moe_stats", "losses"}
    _, new = jax.jit(lambda v: model.apply(
        v, TOKENS[:, :SEQ], mutable=["moe_stats", "losses"]))(
            {"params": made["params"], "moe_stats": made["moe_stats"]})
    assert set(new["moe_stats"]["block0"]) == {
        "rows", "dropped", "load", "overflow_steps", "balance_loss"}
    stats = moe.publish_stats(new["moe_stats"])
    assert set(stats) == {f"block{i}" for i in range(4)}
    for i, entry in enumerate(stats.values()):
        np.testing.assert_allclose(
            entry["balance_loss"],
            new["losses"][f"block{i}"]["moe_balance"][0], rtol=1e-6)
        assert 0.5 < entry["balance_loss"] < 8.0


PUBLISHED = dict(
    vocab_size=151936, num_layers=52, emb_dim=2560, num_heads=28,
    kv_heads=4, head_dim=128, attention_window=4096, rope_theta=1.5e6,
    norm_eps=1e-6, routed_experts=64, held_experts=64, routed_top_k=6,
    routed_width=768, routed_scaling=1.0, shared_experts=0,
    dense_layers_first=0, mtp_modules=0, max_len=16384,
    tie_embeddings=False, use_bias=False, norm="rmsnorm",
    pos_embedding="rope", rope_layer_types=("sliding_attention",),
    qk_norm=False, attention_gate=False, post_norms=False,
    routed_router_input="layer_input", routed_scores="softmax_chosen",
    routed_activation="relu", remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.routed_balance_coef > 0
    assert cfg.layer_types == tuple(
        "full_attention" if i % 4 == 0 else "sliding_attention"
        for i in range(52))
    assert {cfg.ffn_type(i) for i in range(52)} == {"routed"}
    assert cfg.window_of("sliding_attention") == 4096
    assert cfg.window_of("full_attention") is None
    assert cfg.rotates("sliding_attention")
    assert not cfg.rotates("full_attention")


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def test_the_named_size_counts_21506562560_parameters():
    shapes = jax.eval_shape(lambda: gpt(NAME).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    outside = sum(_count(p["block0"][k]) for k in (
        "qkv", "proj", "ln1", "ln2", "router"))
    assert outside == 21_140_480
    assert _count(p["block0"]) == 398_627_840
    assert _count(p) == 21_506_562_560
    assert "moe_state" not in shapes


def test_the_cut_counts_656529920_parameters():
    """The benchmark's cut from the named size: depth 52 -> 4 (published
    layers 0-3, one whole period), 16 of 64 experts held, a quarter of
    the vocabulary; every width as published (ISSUE 43 has the sum)."""
    model = gpt(NAME, num_layers=4, layer_types=KINDS, routed_held=16,
                vocab_size=37984)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    for i in range(4):
        assert _count(p[f"block{i}"]) == 115_512_320
    assert _count(p["wte"]) == _count(p["head"]) == 97_239_040
    assert _count(p) == 656_529_920
    # per expert layer: rows of 16 held experts, rows dropped, the load of
    # all 64, the overflow counter and the balance loss
    assert _count(shapes["moe_stats"]) == 4 * (16 + 1 + 64 + 1 + 1)


def test_the_backward_at_16384_keys_is_one_kernel():
    """The cell's call, ``[28 on 4, 16384, 128]`` in bfloat16: a kv row's
    dk and dv resident count 36.25 MiB and its dq 60.5, over the 32 MiB
    either form has to fit to be taken in its turn, so the smaller count
    decides and the call states it, a whole MiB (PR 44; the two passes
    before it).  Half the length fits the 32 MiB and states them."""
    from flash_oracle import plan_of
    from horovod_tpu.ops.flash_attention import (
        _dq_resident_bwd_vmem_bytes, _fused_bwd_vmem_bytes,
    )

    assert _fused_bwd_vmem_bytes(16384, 128, 512, 256, 2) == 36.25 * 2 ** 20
    assert _dq_resident_bwd_vmem_bytes(16384, 128, 512, 256, 2,
                                       7) == 60.5 * 2 ** 20
    backward = lambda plan: (plan.bwd_form, plan.bwd_vmem_bytes)
    assert plan_of(16384, 128, 7, 2).bwd_form == "dkdv_resident"
    assert backward(plan_of(16384, 128, 7, 2)) == (
        "dkdv_resident", 37 * 2 ** 20)
    assert plan_of(8192, 128, 7, 2).bwd_form == "dkdv_resident"
    assert backward(plan_of(8192, 128, 7, 2)) == (
        "dkdv_resident", 32 * 2 ** 20)
    # of a head's 32 x 64 tiles the full layer's causal half and the
    # band of a 4096-key window, and the grid walks those alone (PR 49)
    tiles = lambda plan: (plan.tiles_live, plan.tiles_mask)
    assert tiles(plan_of(16384, 128)) == (1056, 2048)
    assert tiles(plan_of(16384, 128, window=4096)) == (504, 2048)
    for plan in (plan_of(16384, 128, 7), plan_of(16384, 128, 7,
                                                 window=4096)):
        assert plan.tiles_grid == plan.tiles_live == 7 * len(plan.live_tiles)


@pytest.mark.parametrize("window", [None, 20], ids=["full", "banded"])
def test_two_passes_agree_with_the_one_kernel_at_seven_to_a_kv_head(
        monkeypatch, window):
    """Seven query heads on one key/value head (the first group that is
    no power of two), a full and a banded call: the one kernel with the
    Q tile outermost, which the 16 384-key cell runs since PR 44, against
    the two passes it ran before, every bit, and both against the
    blockwise scan."""
    from flash_oracle import (folded_plan, grouped_blockwise, pallas_calls,
                              vmem_limits)

    from horovod_tpu.ops import flash_attention as fa

    b, h, hkv, s, d, bq, bk = 2, 7, 1, 64, 16, 16, 8
    rng = np.random.RandomState(13)
    mk = lambda heads: jnp.asarray(rng.randn(b * heads, s, d) * 0.7,
                                   jnp.float32)
    q, do, k, v = mk(h), mk(h), mk(hkv), mk(hkv)
    scale = d ** -0.5
    plan = lambda: folded_plan(q, k, v, True, bq, bk, h, hkv, window)
    o, lse = fa._flash_fwd_kernel(q, k, v, plan(), scale, True)

    def backward(plan):
        run = lambda: fa._flash_bwd_pallas(q, k, v, o, lse, do, plan, scale,
                                           True)
        return run(), list(pallas_calls(jax.make_jaxpr(run)().jaxpr))

    one, kernels = backward(plan())
    assert kernels == ["flash_bwd_dkdv"]
    vmem_limits(monkeypatch, 0)
    two, kernels = backward(plan())
    assert kernels == ["flash_bwd_dkdv", "flash_bwd_dq"]
    oracle = grouped_blockwise(q, k, v, o, lse, do, True, scale, bk, window,
                                h, hkv)
    for name, a, t, r in zip(("dq", "dk", "dv"), one, two, oracle):
        np.testing.assert_array_equal(a, t, err_msg=name)
        err = np.abs(np.asarray(t) - np.asarray(r)).max() / np.abs(r).max()
        assert err <= 2e-6, f"{name}: {err:.3g} of the largest entry"


NEW_SETTINGS = {"routed_router_input": "layer_input",
                "routed_scores": "softmax_chosen",
                "routed_activation": "relu", "routed_balance_coef": 0.01}


PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]


@pytest.mark.parametrize("setting", sorted(NEW_SETTINGS))
@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_each_new_setting_by_name(path, setting):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's block
    from raw weights: each refuses the router's placement, the score
    rule, the experts' activation and the balance loss by name, before
    anything is traced, whatever else the configuration says."""
    from test_glm_moe_mla import _refusals

    cfg = replace(gpt("nano").cfg, **{setting: NEW_SETTINGS[setting]})
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


@pytest.mark.parametrize("override,message", [
    ({"routed_router_input": "attention_output"},
     "routed_router_input must be one of"),
    ({"routed_scores": "softmax"}, "routed_scores must be one of"),
    ({"routed_activation": "gelu"}, "routed_activation must be one of"),
    ({"routed_balance_coef": -0.1}, "must not be negative"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_the_defaults_are_the_parents():
    """Every new setting defaults to what the expert layer was: the
    router on the experts' input, sigmoid scores with the bias, a silu
    gate, no balance loss; GLM's and Trinity's named sizes say nothing
    else."""
    cfg = TransformerConfig()
    assert (cfg.routed_router_input, cfg.routed_scores,
            cfg.routed_activation, cfg.routed_balance_coef) == (
                "ffn_input", "sigmoid", "silu", 0.0)
    for size in ("glm-4.7-flash", "trinity-mini"):
        named = GPT_CONFIGS[size]
        assert (named.routed_router_input, named.routed_scores,
                named.routed_activation, named.routed_balance_coef) == (
                    "ffn_input", "sigmoid", "silu", 0.0), size


# leaves and a digest of the sorted ``path:shape:dtype`` lines of the
# whole variable tree, taken on the commit before this file existed
TREES = {"small": (149, "6cdd1d23a276d671"),
         "granite-4.0-h-micro": (458, "3fbd116a94e7256c"),
         "glm-4.7-flash": (911, "02dcf7089015504d"),
         "trinity-mini": (595, "881c866566da5b95"),
         "phi-4-mini-flash-reasoning": (434, "4b9cd88ebc76dc72")}


@pytest.mark.parametrize("size", sorted(TREES))
def test_the_other_named_sizes_build_the_trees_they_built(size):
    model = gpt(size, attention_impl="reference")
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)))
    lines = sorted(
        f"{jax.tree_util.keystr(path)}:{tuple(leaf.shape)}:{leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), digest) == TREES[size]
