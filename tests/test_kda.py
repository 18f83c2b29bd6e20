"""``ops/kda.py``, the chunked gated delta rule with a decay per channel
of the key, against the recurrence itself token by token: ``o`` and every
gradient in float32 at several lengths and chunk sizes, under decays so
strong that ``exp(-G)`` over a chunk overflows, with ``beta`` at 0 and at
1; a head whose channels share one decay is a gated delta rule; the
state crosses chunks and groups; what the backward keeps; what the call
refuses.  Each of these for both forms of the rule: the chunk algebra as
XLA compiles it (``form`` ``xla``: ``plan`` answering ``None``) and the
kernel pair ``kda_fwd`` / ``kda_bwd`` through the Pallas interpreter
(``kernels``: what ``plan`` answers off the TPU); then which form a shape
takes, and that the kernels' call holds no loop.  All on the CPU at small
sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import scopes
from horovod_tpu.ops import flash_attention
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops.kda import group_chunks, kda, kept_mib


@pytest.fixture(params=["xla", "kernels"])
def form(request, monkeypatch):
    """Which form of the rule ``kda`` runs: ``plan`` decides, from the
    shape alone; off the TPU it gives the kernels every shape."""
    if request.param == "xla":
        monkeypatch.setattr(kda_ops, "plan", lambda *shape: None)
    return request.param


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


_BY_RECURRENCE = {}


def both(args, chunk, every):
    """``o`` and the five gradients of a weighted sum of it, by the
    chunk rule and by the recurrence (whose answers are kept: each form
    of the rule asks for them)."""
    weights = jax.random.normal(jax.random.PRNGKey(9),
                                args[2].shape)

    def run(rule):
        o, pullback = jax.vjp(rule, *args)
        return o, pullback(weights)

    with jax.default_matmul_precision("highest"):
        o, got_grads = run(lambda *a: kda(*a, chunk=chunk,
                                          states_every=every))
        key = tuple(np.asarray(a).tobytes() for a in args)
        if key not in _BY_RECURRENCE:
            _BY_RECURRENCE[key] = run(jax.jit(recurrence))
        want, want_grads = _BY_RECURRENCE[key]
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
def test_chunk_rule_is_the_recurrence(form, seq, chunk, every, strength):
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
def test_beta_at_its_ends(form, value):
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


def test_one_decay_a_head_is_a_gated_delta_rule(form):
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


def test_the_state_crosses_chunks_and_groups(form):
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


def test_bfloat16_inputs_keep_float32_decays_and_states(form):
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


def test_the_backward_keeps_a_state_a_group_and_no_state_a_token(form):
    """The forward rule's residuals: the five inputs, and the states at
    the groups' starts, ``[batch, groups, heads, d_k, d_v]`` float32,
    named with ``o`` for a rematerialised block to keep."""
    args = inputs(256, 1.0)
    o, res = kda_ops._kda_fwd(*args, 32, 4, kda_ops.plan(
        256, 3, 16, 8, 32, 4, 4))
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


def test_the_rule_traces_under_its_scope(form):
    args = inputs(64, 1.0)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(kda(*a, chunk=16)))).lower(
        *args).as_text(debug_info=True)
    # the scope is the outermost here; inside a model it follows the
    # block's ``kda``
    inner = "_kernel" if form == "kernels" else ""
    assert f"jvp(kda_scan)/jit({inner}_forward)" in text
    assert f"transpose(jvp(kda_scan))/jit({inner}_backward)" in text


def _equations(jaxpr, stack=""):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, a
    kernel's body apart, each with the scopes around it."""
    for eqn in jaxpr.eqns:
        inside = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, inside
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [
                    value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, inside)


def test_the_kernels_call_holds_two_kernels_and_no_loop(monkeypatch):
    """At a shape ``plan`` takes the traced rule is one ``pallas_call``
    named ``kda_fwd`` forward and, under ``jax.grad``, that and one named
    ``kda_bwd``, both under the scope ``kda_scan``; nothing of it is a
    ``scan`` or a ``while`` outside a kernel, and the kernels' outputs
    carry the names a rematerialised block keeps.  The XLA form at the
    same shape is two scans."""
    args = inputs(128, 1.0, batch=1, heads=2)
    rule = lambda *a: kda(*a, chunk=32, states_every=2)
    # (a function anew each time: a traced one is remembered)
    grad = lambda: jax.grad(lambda *a: jnp.sum(rule(*a)), argnums=range(5))

    def traced(fn):
        eqns = list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))
        kernels = [(e.params["name"], stack) for e, stack in eqns
                   if e.primitive.name == "pallas_call"]
        return kernels, {e.primitive.name for e, _ in eqns}, [
            e.params["name"] for e, _ in eqns if e.primitive.name == "name"]

    kernels, primitives, names = traced(rule)
    assert [name for name, _ in kernels] == ["kda_fwd"]
    assert not primitives & {"scan", "while"}
    kernels, primitives, names = traced(grad())
    assert sorted(name for name, _ in kernels) == ["kda_bwd", "kda_fwd"]
    assert all(scopes.KDA_SCAN in stack for _, stack in kernels)
    assert not primitives & {"scan", "while"}
    assert sorted(names) == [scopes.KDA_OUT, scopes.KDA_STATES]
    monkeypatch.setattr(kda_ops, "plan", lambda *shape: None)
    kernels, primitives, _ = traced(grad())
    assert not kernels and "scan" in primitives


def test_a_head_count_the_head_block_does_not_divide(monkeypatch):
    """Six heads at four a program run three a program (the largest
    divisor), five run one: the rule is the recurrence either way."""
    assert [kda_ops._head_block(h) for h in (32, 6, 5, 3, 1)] == [
        4, 3, 1, 3, 1]
    monkeypatch.setattr(kda_ops, "HEAD_BLOCK", 2)
    args = inputs(64, 1.0, batch=1, heads=3, dk=8, dv=8)
    assert kda_ops.plan(64, 3, 8, 8, 16, 2, 4) == (1, True)
    o, want, got_grads, want_grads = both(args, 16, 2)
    np.testing.assert_allclose(o, want, atol=2e-6)
    for got, ref in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            got, ref, atol=2e-4 * float(jnp.abs(ref).max()) + 1e-9)


# the cell's layer: the kernels, four heads a program; a head size off the
# lanes, in keys or in values, and a chunk under a bfloat16 tile: XLA's
# form; a state every 16th chunk at four heads is past the 16 MiB the
# calls state, at two it fits; float32 inputs are twice the streams
@pytest.mark.parametrize("compiled,shape,want", [
    (True, (16384, 32, 128, 128, 64, 4, 2), (4, False)),
    (True, (16384, 32, 128, 128, 64, 8, 2), (2, False)),
    (True, (16384, 32, 128, 128, 64, 4, 4), (2, False)),
    (True, (16384, 32, 128, 128, 64, 64, 2), None),
    (True, (16384, 32, 64, 128, 64, 4, 2), None),
    (True, (16384, 32, 128, 192, 64, 4, 2), None),
    (True, (16384, 32, 128, 128, 8, 4, 2), None),
    (True, (16384, 3, 128, 128, 64, 4, 2), (3, False)),
    (False, (16384, 32, 64, 128, 64, 4, 2), (4, True)),
    (False, (64, 3, 16, 8, 8, 4, 4), (3, True)),
])
def test_the_path_is_read_from_the_shape(monkeypatch, compiled, shape, want):
    """``plan(seq, heads, d_k, d_v, chunk, states_every, itemsize)``:
    compiled, a head is whole lane tiles in keys and in values, a chunk
    whole 16-row tiles, and a program's group fits the VMEM the calls
    state with as many heads as that leaves (none: ``None``, XLA's
    form); through the interpreter every shape is taken."""
    monkeypatch.setattr(flash_attention, "_interpret_for_backend",
                        lambda backend: not compiled)
    assert kda_ops.plan(*shape) == want
    if want is not None and compiled:
        seq, _, d_k, d_v, chunk, every, itemsize = shape
        assert kda_ops._vmem_bytes(
            group_chunks(seq // chunk, every), chunk, want[0], d_k, d_v,
            itemsize) <= kda_ops._VMEM_LIMIT


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
