"""``ops/kda.py``, the chunked gated delta rule with a decay per channel
of the key, against the recurrence itself token by token: ``o`` and every
gradient in float32 at several lengths and chunk sizes, under decays so
strong that ``exp(-G)`` over a chunk overflows, with ``beta`` at 0 and at
1; a head whose channels share one decay is a gated delta rule; the
state crosses chunks and groups; what the backward keeps; what the call
refuses.  All on the CPU at small sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import scopes
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops.kda import group_chunks, kda, kept_mib


def recurrence(q, k, v, g, beta):
    """``S_t = (I - b k k^T) Diag(exp(g)) S + b k v^T``, ``o = S^T q``,
    a token at a time, a head at a time."""
    dk, dv = q.shape[-1], v.shape[-1]

    def head(q, k, v, g, beta):
        def token(S, at):
            q_t, k_t, v_t, g_t, b_t = at
            S = jnp.exp(g_t)[:, None] * S
            S = S + b_t * jnp.outer(k_t, v_t - S.T @ k_t)
            return S, S.T @ q_t

        return jax.lax.scan(token, jnp.zeros((dk, dv)),
                            (q, k, v, g, beta))[1]

    per_head = jax.vmap(head, in_axes=1, out_axes=1)
    return jax.vmap(per_head)(q, k, v, g, beta)


def inputs(seq, strength, seed=0, batch=2, heads=3, dk=16, dv=8):
    """Unit keys, scaled unit queries, log-decays ``-strength *
    softplus(normal)`` a channel, ``beta`` in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, seq, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, heads, dk)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dv))
    g = -strength * jax.nn.softplus(
        jax.random.normal(ks[3], (batch, seq, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return q, k, v, g, beta


def both(args, chunk, every):
    """``o`` and the five gradients of a weighted sum of it, by the
    chunk rule and by the recurrence."""
    weights = jax.random.normal(jax.random.PRNGKey(9),
                                args[2].shape)

    def run(rule):
        return jax.value_and_grad(
            lambda *a: jnp.sum(rule(*a) * weights), argnums=range(5),
            has_aux=False)(*args)

    with jax.default_matmul_precision("highest"):
        o = kda(*args, chunk=chunk, states_every=every)
        want = recurrence(*args)
        _, got_grads = run(lambda *a: kda(*a, chunk=chunk,
                                          states_every=every))
        _, want_grads = run(recurrence)
    return o, want, got_grads, want_grads


# gentle decays; decays of order one; and decays of sixty times a
# softplus a token and channel: G reaches -3800 over a chunk of 64 and
# exp(-G) is far past float32 (and float64)
STRENGTHS = [0.05, 1.0, 60.0]
# (seq, chunk, states_every): one group, several groups, a group that
# does not divide (3 chunks, a state every 2: groups of 1), chunks under
# the 16-token sub-block, one chunk
SHAPES = [(128, 64, 4), (256, 32, 2), (96, 32, 2), (64, 8, 4), (32, 32, 4)]


@pytest.mark.parametrize("strength", STRENGTHS)
@pytest.mark.parametrize("seq,chunk,every", SHAPES)
def test_chunk_rule_is_the_recurrence(seq, chunk, every, strength):
    args = inputs(seq, strength)
    o, want, got_grads, want_grads = both(args, chunk, every)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want, atol=2e-6)
    for name, got, ref in zip("q k v g beta".split(), got_grads,
                              want_grads):
        assert bool(jnp.isfinite(got).all()), name
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(got, ref, atol=2e-4 * scale + 1e-9,
                                   err_msg=name)


def test_the_strong_case_would_overflow_a_factorised_chunk():
    """What the sub-blocks are for: under the strong decays the
    cumulated log-decay of a chunk is far past what ``exp`` of its
    negative holds, and of a 16-token sub-block too, so no reference
    point inside a chunk makes ``exp(R - G_j)`` alone safe."""
    g = inputs(128, 60.0)[3]
    G = jnp.cumsum(g.reshape(2, 2, 64, 3, 16), axis=2)
    assert float(-G.min()) > 3000 > 88.7        # log(float32 max)
    assert bool(jnp.isinf(jnp.exp(-G)).any())
    sub = jnp.cumsum(g.reshape(2, 8, 16, 3, 16), axis=2)
    assert bool(jnp.isinf(jnp.exp(-sub)).any())


@pytest.mark.parametrize("value", [0.0, 1.0])
def test_beta_at_its_ends(value):
    """``beta = 0`` writes nothing: the state stays zero and so does
    ``o``, whose gradient still reaches ``beta``; ``beta = 1`` replaces
    what the key read."""
    q, k, v, g, _ = inputs(64, 1.0)
    beta = jnp.full(q.shape[:3], value)
    o, want, got_grads, want_grads = both((q, k, v, g, beta), 16, 2)
    np.testing.assert_allclose(o, want, atol=2e-6)
    if value == 0.0:
        assert float(jnp.abs(o).max()) == 0.0
    for got, ref in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * float(jnp.abs(ref).max()) + 1e-9)
    assert float(jnp.abs(got_grads[4]).max()) > 0


def test_one_decay_a_head_is_a_gated_delta_rule():
    """With a head's channels sharing one log-decay the rule is the
    gated delta rule ``S_t = alpha_t (I - b k k^T) S + b k v^T`` with a
    scalar ``alpha`` a head: the sibling this model is told from."""
    q, k, v, g, beta = inputs(96, 1.0)
    g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    def gated_delta(q, k, v, a, beta):
        def head(q, k, v, a, beta):
            def token(S, at):
                q_t, k_t, v_t, a_t, b_t = at
                S = jnp.exp(a_t) * S
                S = S + b_t * jnp.outer(k_t, v_t - S.T @ k_t)
                return S, S.T @ q_t

            return jax.lax.scan(token, jnp.zeros((16, 8)),
                                (q, k, v, a, beta))[1]

        return jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))(
            q, k, v, a, beta)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            kda(q, k, v, g, beta, chunk=32),
            gated_delta(q, k, v, g[..., 0], beta), atol=2e-6)


def test_the_state_crosses_chunks_and_groups():
    """A change at token 0 reaches the last token's output, four chunks
    and two groups later, and no output before the change's token."""
    q, k, v, g, beta = inputs(128, 0.05)
    with jax.default_matmul_precision("highest"):
        base = kda(q, k, v, g, beta, chunk=32, states_every=2)
        moved = kda(q, k, v.at[:, 0].add(1.0), g, beta, chunk=32,
                    states_every=2)
        later = kda(q, k, v.at[:, 70].add(1.0), g, beta, chunk=32,
                    states_every=2)
    # (a state of 16 x 8 overwritten for 127 tokens keeps little: the
    # rule itself reads differences of 1e-7)
    assert float(jnp.abs(moved - base)[:, -1].max()) > 5e-6
    assert float(jnp.abs(later - base)[:, :70].max()) == 0.0
    assert float(jnp.abs(later - base)[:, 70:].max()) > 1e-4


def test_bfloat16_inputs_keep_float32_decays_and_states():
    """``q``, ``k``, ``v`` in bfloat16: ``o`` comes back in bfloat16,
    close to the float32 rule on the same rounded inputs, and the
    gradients of ``g`` and ``beta`` stay float32."""
    q, k, v, g, beta = inputs(64, 1.0)
    low = tuple(t.astype(jnp.bfloat16) for t in (q, k, v))
    o = kda(*low, g, beta, chunk=16)
    assert o.dtype == jnp.bfloat16
    want = recurrence(*(t.astype(jnp.float32) for t in low), g, beta)
    assert float(jnp.abs(o.astype(jnp.float32) - want).max()) < 0.03
    grads = jax.grad(lambda *a: jnp.sum(kda(*a, chunk=16).astype(
        jnp.float32)), argnums=(0, 3, 4))(*low, g, beta)
    assert [t.dtype for t in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.float32]


def test_the_backward_keeps_a_state_a_group_and_no_state_a_token():
    """The forward rule's residuals: the five inputs, and the states at
    the groups' starts, ``[batch, groups, heads, d_k, d_v]`` float32,
    named with ``o`` for a rematerialised block to keep."""
    args = inputs(256, 1.0)
    o, res = kda_ops._kda_fwd(*args, 32, 4)
    assert res[5].shape == (2, 2, 3, 16, 8) and res[5].dtype == jnp.float32
    assert [r.shape for r in res[:5]] == [a.shape for a in args]
    # the first group starts from nothing, the second from what the
    # first left
    assert float(jnp.abs(res[5][:, 0]).max()) == 0.0
    assert float(jnp.abs(res[5][:, 1]).max()) > 0.0
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: kda(*b, chunk=32), *a)[0])(*args))
    assert f"name={scopes.KDA_OUT}" in text
    assert f"name={scopes.KDA_STATES}" in text
    assert {scopes.KDA_OUT, scopes.KDA_STATES} <= set(scopes.KERNEL_OUTPUTS)
    # what one layer of the cell keeps: 128 MiB of states and 128 of o
    assert kept_mib(1, 16384, 32, 128, 128, 64, 4, 2) == 256.0
    assert kept_mib(1, 16384, 32, 128, 128, 64, 8, 2) == 192.0


def test_group_chunks_divides_the_sequences_chunks():
    assert [group_chunks(n, 4) for n in (256, 6, 3, 1, 7)] == [4, 3, 3, 1, 1]
    assert group_chunks(8, 1) == 1 and group_chunks(8, 100) == 8


def test_the_rule_traces_under_its_scope():
    args = inputs(64, 1.0)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(kda(*a, chunk=16)))).lower(
        *args).as_text(debug_info=True)
    # the scope is the outermost here; inside a model it follows the
    # block's ``kda``
    assert "jvp(kda_scan)/jit(_forward)" in text
    assert "transpose(jvp(kda_scan))/jit(_backward)" in text


@pytest.mark.parametrize("edit,message", [
    (dict(seq=72, chunk=16), "seq=72 is not a multiple of chunk=16"),
    (dict(seq=96, chunk=24), "chunk=24 is no power of two"),
])
def test_a_length_the_chunk_does_not_divide_is_refused_by_name(edit, message):
    args = inputs(edit["seq"], 1.0)
    with pytest.raises(ValueError, match=message):
        kda(*args, chunk=edit["chunk"])


def test_shapes_that_do_not_agree_are_refused():
    q, k, v, g, beta = inputs(32, 1.0)
    with pytest.raises(ValueError, match="do not agree"):
        kda(q, k, v, g[..., :8], beta, chunk=16)
    with pytest.raises(ValueError, match="do not agree"):
        kda(q, k, v, g, beta[:, :16], chunk=16)
