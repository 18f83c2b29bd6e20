"""What stands between the fused q/k/v matmul and the flash kernels as
one Pallas kernel pair (``ops/attn_prep.py``) against the chain as XLA
compiles it (``models/transformer.py:attn_prep_chain``), through the
Pallas interpreter on the CPU at lane-whole shapes: ``q``, ``k`` and
``v`` and every gradient with norms and rotation, norms alone and
rotation alone; each part of ``d fused`` from its own stream; the rule
that reads the path from what the caller sees; a model whose heads are
128 wide on the kernels inside ``block_math``; what a rematerialised
block runs twice and keeps; the scope and the gauges."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import scopes
from horovod_tpu.models.transformer import attn_prep_chain, gpt
from horovod_tpu.obs.registry import get_registry, reset_registry
from horovod_tpu.ops import attn_prep
from horovod_tpu.ops.rope import rope_tables
from test_ssm_chain import _names

HD = 128
EPS = 1e-6
SEQ = 64


@pytest.fixture
def tiles(monkeypatch):
    """A token tile the sequences below cross: 32 tokens a program."""
    monkeypatch.setattr(attn_prep, "TOKEN_TILE", 32)


def _inputs(batch, heads, kv_heads, dtype, hd=HD):
    ks = jax.random.split(jax.random.PRNGKey(heads + kv_heads), 6)
    args = (jax.random.normal(
        ks[0], (batch, SEQ, (heads + 2 * kv_heads) * hd)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(ks[1], (hd,)),
            1.0 + 0.1 * jax.random.normal(ks[2], (hd,)))
    weights = tuple(jax.random.normal(k, (batch * n, SEQ, hd)).astype(dtype)
                    for k, n in zip(ks[3:], (heads, kv_heads, kv_heads)))
    return args, weights


def _norm(scale):
    return lambda t: nn.RMSNorm(epsilon=EPS, dtype=jnp.float32).apply(
        {"params": {"scale": scale}}, t)


def _path(kernels, heads, kv_heads, norms, rotates, hd=HD):
    """``fn(fused, q_scale, k_scale) -> (q, k, v)`` head-major, through
    the kernel pair or through the kept chain."""
    tables = rope_tables(jnp.arange(SEQ), hd, 10000.0) if rotates else None

    def fn(fused, q_scale, k_scale):
        if kernels:
            plan = attn_prep.plan(
                SEQ, heads, kv_heads, hd, norm="rmsnorm" if norms else None,
                rotates=rotates, flash=True, plain=True)
            return attn_prep.attn_prep(
                fused, (q_scale, k_scale) if norms else None, tables,
                heads=heads, kv_heads=kv_heads, eps=EPS, tiles=plan)
        return tuple(
            t.transpose(0, 2, 1, 3).reshape(-1, SEQ, hd)
            for t in attn_prep_chain(
                fused, _norm(q_scale) if norms else None,
                _norm(k_scale) if norms else None, tables, heads=heads,
                kv_heads=kv_heads, head_dim=hd))

    return fn


@functools.partial(jax.jit, static_argnums=0)
def _both(fn, args, weights):
    """``q``, ``k``, ``v`` and the gradients of their weighted sum in
    ``fused`` and the two scales."""
    out, pull = jax.vjp(fn, *args)
    return out, pull(weights)


def _close(got, want, rel):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (t.astype(jnp.float32) for t in (got, want))
    assert float(jnp.abs(got - want).max()) <= rel * float(
        jnp.abs(want).max()), (float(jnp.abs(got - want).max()),
                               float(jnp.abs(want).max()))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("norms,rotates", [(True, True), (True, False),
                                           (False, True)],
                         ids=["norms_rotation", "norms", "rotation"])
@pytest.mark.parametrize("batch,heads,kv_heads", [(2, 4, 1), (1, 8, 2)])
def test_kernels_agree_with_the_chain_in_values_and_every_gradient(
        tiles, batch, heads, kv_heads, norms, rotates, dtype):
    """In bfloat16 (the cells' dtype) ``v`` is a copy and ``q`` and
    ``k`` are the chain's bit for bit but for the order of a head's 128
    squares and what XLA's CPU backend contracts: at most one element
    in a thousand, by one rounding step.  The gradients carry float32
    sums in another order and, in bfloat16, ONE rounding of ``d fused``
    where the chain rounds its two terms apart: the tolerances of
    ``tests/test_ssm_chain.py``."""
    args, weights = _inputs(batch, heads, kv_heads, dtype)
    case = (heads, kv_heads, norms, rotates)
    out, grads = _both(_path(True, *case), args, weights)
    want_out, want_grads = _both(_path(False, *case), args, weights)
    assert float(jnp.abs(out[2] - want_out[2]).max()) == 0.0
    for got, want in zip(out[:2], want_out[:2]):
        _close(got, want, 1e-6 if dtype == jnp.float32 else 2 ** -8)
        if dtype == jnp.bfloat16:
            assert int(jnp.sum(got != want)) <= got.size // 1000
    for got, want, arg in zip(grads, want_grads, args):
        assert got.dtype == arg.dtype
        _close(got, want, 1e-5 if dtype == jnp.float32 else 2 ** -7)
    if not norms:
        assert float(jnp.abs(jnp.stack(grads[1:])).max()) == 0.0


def test_a_head_of_two_lane_tiles(tiles):
    """Heads of 256: the halves the rotation swaps are whole lane tiles,
    the mean square runs over both."""
    args, weights = _inputs(1, 2, 1, jnp.float32, hd=256)
    out, grads = _both(_path(True, 2, 1, True, True, hd=256), args, weights)
    want_out, want_grads = _both(_path(False, 2, 1, True, True, hd=256),
                                 args, weights)
    for got, want in zip(out, want_out):
        _close(got, want, 1e-6)
    for got, want in zip(grads, want_grads):
        _close(got, want, 1e-5)


def test_each_part_of_dfused_comes_from_its_own_stream(tiles):
    """``q``'s lanes from the cotangent of ``q``, ``k``'s and ``v``'s
    from theirs, ``v``'s the cotangent itself: none is dropped, none
    leaks into another's lanes, and every lane of ``d fused`` is
    written."""
    heads, kv_heads = 4, 2
    args, weights = _inputs(2, heads, kv_heads, jnp.float32)
    fn = _path(True, heads, kv_heads, True, True)
    lanes = {0: slice(0, heads * HD),
             1: slice(heads * HD, (heads + kv_heads) * HD),
             2: slice((heads + kv_heads) * HD, None)}

    def dfused(parts):
        picked = tuple(w if i in parts else jnp.zeros_like(w)
                       for i, w in enumerate(weights))
        return _both(fn, args, picked)[1][0]

    whole = dfused(lanes)
    for part, own in lanes.items():
        alone = dfused((part,))
        assert float(jnp.abs(alone[..., own]).min()) > 0.0
        assert float(jnp.abs(alone.at[..., own].set(0.0)).max()) == 0.0
        _close(alone[..., own], whole[..., own], 1e-6)
    dv = weights[2].reshape(2, kv_heads, SEQ, HD).transpose(
        0, 2, 1, 3).reshape(2, SEQ, kv_heads * HD)
    assert float(jnp.abs(whole[..., lanes[2]] - dv).max()) == 0.0


FLASH = dict(norm="rmsnorm", rotates=True, flash=True, plain=True)


@pytest.mark.parametrize("shape,call,want", [
    ((16384, 32, 4, 128), {}, (512, 4)),            # SDAR's cell
    ((8192, 32, 4, 128), dict(rotates=False), (512, 4)),    # Trinity's
                                                    # full_attention layer
    ((16384, 28, 4, 128), dict(norm=None), (512, 4)),   # SmallThinker's
                                                    # sliding layers
    ((8192, 16, 2, 256), {}, (512, 2)),             # heads of two tiles
    ((8192, 6, 3, 128), {}, (512, 3)),              # heads a program
    ((8192, 7, 1, 128), {}, (512, 1)),              # that divide both
    ((8704, 32, 4, 128), {}, (512, 4)),
    ((1088, 32, 4, 128), {}, (272, 4)),             # a tile that divides
    ((48, 4, 2, 128), {}, (48, 2)),                 # one tile of 16 rows
    ((8192, 32, 4, 128), dict(flash=False), None),  # another schedule, or
                                                    # ``attend`` handed in
    ((8192, 32, 4, 128), dict(plain=False), None),  # shared_kv, hand_on,
                                                    # differential
    ((8192, 32, 4, 128), dict(norm="layernorm"), None),  # not an RMS norm
    ((8192, 32, 4, 128), dict(norm=None, rotates=False), None),  # neither
    ((32768, 32, 8, 64), {}, None),                 # LFM2: half a lane tile
    ((32, 4, 2, 16), {}, None),                     # the tiny test models
    ((8192, 32, 4, 192), {}, None),                 # a tile and a half
    ((1000, 32, 4, 128), {}, None),                 # no tile of 16 rows
])
def test_the_path_is_read_from_what_the_caller_sees(shape, call, want):
    """The flash path, a layer that makes its own keys and values and
    hands none on, an RMS norm where there is one, a norm or a rotation,
    a head of whole 128-lane tiles (compiled or interpreted alike), a
    token tile of whole 16 rows that divides the sequence.  ``None`` is
    the caller's chain."""
    assert attn_prep.plan(*shape, **{**FLASH, **call}) == want


def _model(**settings):
    """Trinity-Mini's block at the narrowest widths the kernels take:
    two layers, one with norms and a rotation and one with norms alone,
    two heads over one of 128, an output gate, two of four experts
    held."""
    sizes = dict(
        num_layers=2, layer_types=("sliding_attention", "full_attention"),
        vocab_size=256, emb_dim=64, num_heads=2, num_kv_heads=1,
        head_size=128, attention_window=16, max_len=64, mlp_ratio=2,
        dense_layers_first=1, routed_experts=4, routed_held=2,
        routed_first_held=2, routed_top_k=2, routed_width=32,
        flash_block_q=16, flash_block_k=16)
    return gpt("trinity-mini", **{**sizes, **settings})


def _loss(model, tokens):
    """The mean next-token loss as a function of the parameters and of
    the variables' other collections (the router's bias)."""
    def loss(params, state):
        logits = model.apply({**state, "params": params}, tokens[:, :-1])
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32)),
            tokens[:, 1:, None], axis=-1)
        return -picked.mean()

    return loss


def _shapes(model, tokens):
    """The parameters' and the other collections' shapes."""
    state = dict(jax.eval_shape(model.init, jax.random.PRNGKey(1),
                                tokens[:, :-1]))
    return state.pop("params"), state


def _gauges():
    gauge = lambda name: get_registry().gauge(f"attn_prep.{name}").value
    return gauge("layers"), gauge("kernel_layers")


def test_a_model_at_lane_whole_heads_runs_the_kernels(monkeypatch):
    """Loss and every parameter's gradient of a two-layer model through
    the kernel pair inside ``block_math`` (the flash kernels read what
    it wrote), against the same model on the chain (``plan`` saying
    ``None``); the parameter tree is the chain's; the gauges say which
    ran."""
    model = _model(dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 256)
    state = dict(jax.jit(model.init)(jax.random.PRNGKey(1), tokens[:, :-1]))
    params = state.pop("params")
    reset_registry()
    got = jax.jit(jax.value_and_grad(_loss(model, tokens)))(params, state)
    assert _gauges() == (2, 2)
    monkeypatch.setattr(attn_prep, "plan", lambda *shape, **call: None)
    reset_registry()
    want = jax.jit(jax.value_and_grad(_loss(model, tokens)))(params, state)
    assert _gauges() == (2, 0)
    on_the_chain = _shapes(model, tokens)[0]
    assert jax.tree.map(jnp.shape, on_the_chain) == jax.tree.map(
        jnp.shape, params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        _close(g, w, 2e-5)


def test_a_narrow_model_counts_no_kernel_layer():
    """The tiny test models (heads of 16) keep the chain: the gauges say
    two layers and none on the kernels; a model with neither norms nor
    rotation sets no gauge."""
    model = _model(head_size=16, num_heads=4, num_kv_heads=2,
                   dtype=jnp.float32)
    tokens = jnp.zeros((1, 16), jnp.int32)
    reset_registry()
    jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    assert _gauges() == (2, 0)
    reset_registry()
    jax.eval_shape(gpt("nano").init, jax.random.PRNGKey(0), tokens)
    assert not [m for m in get_registry().snapshot()
                if m["name"].startswith("attn_prep.")]


@pytest.mark.parametrize("policy", ["nothing_saveable",
                                    "dots_with_no_batch_dims_saveable"])
def test_a_rematerialised_block_runs_the_forward_twice_and_keeps_nothing(
        policy):
    """Under ``jax.checkpoint`` with ``block_remat_policy`` each block
    runs ``attn_prep_fwd`` twice (the outputs carry no name a block
    keeps), ``attn_prep_bwd`` and ``flash_fwd`` once, and nothing as
    large as ``q`` beside ``fused`` and the kernels' named outputs goes
    from the forward to the backward in float32."""
    model = _model(dtype=jnp.bfloat16, remat=True, remat_policy=policy)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 256)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, tokens)))(
        *_shapes(model, tokens)).jaxpr
    names = _names(jaxpr)
    assert names.count("attn_prep_fwd") == 4
    assert names.count("attn_prep_bwd") == 2
    assert names.count("flash_fwd") == 2
    blocks = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    assert blocks
    for eqn in blocks:
        for var in eqn.invars:
            aval = var.aval
            assert not (aval.dtype == jnp.float32 and aval.ndim >= 3
                        and aval.size >= 2 * 32 * 2 * HD), aval


def test_the_scope_holds_the_kernels_and_the_chain():
    """The pair lowers under ``attn/attn_prep``, forward and backward;
    a narrow model's chain (its norms and its rotation) under the same
    scope."""
    tokens = jnp.zeros((1, 17), jnp.int32)

    def text(model):
        return jax.jit(jax.grad(_loss(model, tokens))).lower(
            *_shapes(model, tokens)).as_text(debug_info=True)

    kernels = text(_model(dtype=jnp.float32))
    # the calls sit behind an inner jit: its call site carries the scope
    for call, kernel in (("_forward", "attn_prep_fwd"),
                         ("_backward", "attn_prep_bwd")):
        assert f"block0/attn/attn_prep/jit({call})" in kernels, call
        assert f"{kernel}/pallas_call" in kernels, kernel
    chain = text(_model(head_size=16, dtype=jnp.float32))
    assert "attn_prep_fwd" not in chain
    assert "block0/attn/attn_prep/q_norm" in chain
    assert "block1/attn/attn_prep/k_norm" in chain
    assert scopes.ATTN_PREP in scopes.SCOPES
