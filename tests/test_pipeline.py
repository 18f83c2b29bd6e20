"""GPipe pipeline parallelism (parallel/pipeline.py) — the pp axis of
the optional-stretch parallelism set (reference is DP-only,
SURVEY.md §2.9).

Contract: pp_gpt_apply over a pp-axis mesh reproduces the unsharded
GPT.apply (fp32, up to associativity), forward and gradients, with each
stage holding only its layers' weights and activations streaming via
ppermute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models.transformer import gpt
from horovod_tpu.parallel.pipeline import (
    pp_gpt_apply, pp_gpt_loss, pp_gpt_loss_circular, stack_pp_params,
    stack_pp_params_circular,
)

PP = 4
AXIS = "pp"


def _mesh():
    return Mesh(np.asarray(jax.devices()[:PP]), (AXIS,))


def _model(**overrides):
    common = dict(num_layers=4, num_heads=4, emb_dim=64, max_len=64,
                  vocab_size=512, dtype=jnp.float32,
                  attention_impl="reference")
    common.update(overrides)
    return gpt("nano", **common)


def _tokens(seed=0, b=4, s=16):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 512, (b, s)), jnp.int32
    )


def _pp_fwd(model, params, tokens, microbatches):
    staged, replicated = stack_pp_params(params, model.cfg, PP)

    def local(staged, replicated, tok):
        return pp_gpt_apply(staged, replicated, model.cfg, tok, AXIS,
                            microbatches=microbatches)

    fwd = jax.jit(
        shard_map(
            local, mesh=_mesh(),
            in_specs=(P(AXIS), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )
    return fwd(staged, replicated, tokens)


@pytest.mark.parametrize("microbatches", [1, 2, 4])
@pytest.mark.parametrize("pos_embedding", ["learned", "rope"])
def test_pp_matches_single_device(microbatches, pos_embedding):
    model = _model(pos_embedding=pos_embedding)
    tokens = _tokens()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    ref = jax.jit(model.apply)(params, tokens)
    out = _pp_fwd(model, params, tokens, microbatches)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_pp_gradients_match():
    """Stage grads equal the matching layers' grads of the unsharded
    model (check_vma=True for the collective transposes, as with TP)."""
    model = _model()
    tokens = _tokens(1)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)
    targets = jnp.roll(tokens, -1, axis=1)

    def loss_ref(p):
        logits = model.apply(p, tokens)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1
        ).mean()

    g_ref = jax.jit(jax.grad(loss_ref))(params)["params"]
    staged, replicated = stack_pp_params(params, model.cfg, PP)

    def local_loss(staged, replicated, tok, tgt):
        logits = pp_gpt_apply(staged, replicated, model.cfg, tok, AXIS,
                              microbatches=2)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgt[..., None], -1
        ).mean()

    grad_fn = jax.jit(
        shard_map(
            jax.grad(local_loss), mesh=_mesh(),
            in_specs=(P(AXIS), P(), P(), P()), out_specs=P(AXIS),
            check_vma=True,
        )
    )
    g_pp = grad_fn(staged, replicated, tokens, targets)
    # stage 0 holds block0 (1 layer/stage with 4 layers, pp=4)
    np.testing.assert_allclose(
        np.asarray(g_pp["qkv"]["kernel"][0, 0]),
        np.asarray(g_ref["block0"]["qkv"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )
    # stage 3 holds block3
    np.testing.assert_allclose(
        np.asarray(g_pp["fc2"]["kernel"][3, 0]),
        np.asarray(g_ref["block3"]["fc2"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )


def _ref_token_loss(model, params, tokens, targets):
    logits = jax.jit(model.apply)(params, tokens)
    return -jnp.take_along_axis(
        jax.nn.log_softmax(logits), targets[..., None], -1
    ).mean()


@pytest.mark.parametrize("remat", [False, True])
def test_pp_loss_matches_single_device(remat):
    """pp_gpt_loss (stage-local head, scalar rejoin) equals the
    unsharded token loss — with and without per-tick remat."""
    model = _model()
    tokens = _tokens(2)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), tokens)
    ref = _ref_token_loss(model, params, tokens, targets)
    staged, replicated = stack_pp_params(params, model.cfg, PP)

    def local(staged, replicated, tok, tgt):
        return pp_gpt_loss(staged, replicated, model.cfg, tok, tgt, AXIS,
                           microbatches=2, remat=remat)

    loss = jax.jit(
        shard_map(
            local, mesh=_mesh(),
            in_specs=(P(AXIS), P(), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )(staged, replicated, tokens, targets)
    np.testing.assert_allclose(
        float(loss), float(ref), atol=2e-5, rtol=2e-5
    )


def test_pp_loss_gradients_match():
    """Training-path gradients through pp_gpt_loss: staged-block grads
    AND the replicated embed/head grads equal the unsharded model's
    (the scalar-psum rejoin must transpose to the same pullbacks as the
    full-logit broadcast)."""
    model = _model()
    tokens = _tokens(3)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)
    g_ref = jax.jit(jax.grad(
        lambda p: _ref_token_loss(model, p, tokens, targets)
    ))(params)["params"]
    staged, replicated = stack_pp_params(params, model.cfg, PP)

    def local_loss(staged, replicated, tok, tgt):
        return pp_gpt_loss(staged, replicated, model.cfg, tok, tgt, AXIS,
                           microbatches=2, remat=True)

    grad_fn = jax.jit(
        shard_map(
            jax.grad(local_loss, argnums=(0, 1)), mesh=_mesh(),
            in_specs=(P(AXIS), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=True,
        )
    )
    g_staged, g_rep = grad_fn(staged, replicated, tokens, targets)
    np.testing.assert_allclose(
        np.asarray(g_staged["qkv"]["kernel"][0, 0]),
        np.asarray(g_ref["block0"]["qkv"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(g_staged["fc2"]["kernel"][3, 0]),
        np.asarray(g_ref["block3"]["fc2"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(g_rep["wte"]["embedding"]),
        np.asarray(g_ref["wte"]["embedding"]),
        atol=2e-4, rtol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(g_rep["head"]["kernel"]),
        np.asarray(g_ref["head"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )


def test_pp_apply_remat_matches():
    """remat=True is numerically a no-op for the logits path."""
    model = _model()
    tokens = _tokens(4)
    params = jax.jit(model.init)(jax.random.PRNGKey(4), tokens)
    staged, replicated = stack_pp_params(params, model.cfg, PP)

    def run(remat):
        def local(staged, replicated, tok):
            return pp_gpt_apply(staged, replicated, model.cfg, tok, AXIS,
                                microbatches=2, remat=remat)
        return jax.jit(
            shard_map(local, mesh=_mesh(),
                      in_specs=(P(AXIS), P(), P()), out_specs=P(),
                      check_vma=False)
        )(staged, replicated, tokens)

    np.testing.assert_allclose(
        np.asarray(run(True)), np.asarray(run(False)), atol=1e-6
    )


@pytest.mark.parametrize("pp,circles,layers,mbs", [
    (2, 2, 4, 4),   # 1 layer/group, stream wraps twice
    (4, 2, 8, 4),   # M == pp: write-and-read same ring slot in one tick
    (2, 3, 6, 2),   # three circles
])
def test_pp_circular_loss_matches_single_device(pp, circles, layers, mbs):
    """Circular-schedule loss equals the unsharded token loss for
    several (P, V, M) geometries, including the M == P ring-buffer
    edge."""
    model = _model(num_layers=layers)
    tokens = _tokens(5)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(jax.random.PRNGKey(5), tokens)
    ref = _ref_token_loss(model, params, tokens, targets)
    staged, replicated = stack_pp_params_circular(
        params, model.cfg, pp, circles
    )
    mesh = Mesh(np.asarray(jax.devices()[:pp]), (AXIS,))

    def local(staged, replicated, tok, tgt):
        return pp_gpt_loss_circular(
            staged, replicated, model.cfg, tok, tgt, AXIS,
            microbatches=mbs, circles=circles,
        )

    loss = jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(P(AXIS), P(), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )(staged, replicated, tokens, targets)
    np.testing.assert_allclose(
        float(loss), float(ref), atol=2e-5, rtol=2e-5
    )


def test_pp_circular_gradients_match():
    """Gradients through the circular schedule: group grads land on the
    right (stage, circle) slots and match the unsharded model's layer
    grads; replicated embed/head grads match too."""
    pp, circles = 2, 2
    model = _model(num_layers=4)
    tokens = _tokens(6)
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(jax.random.PRNGKey(6), tokens)
    g_ref = jax.jit(jax.grad(
        lambda p: _ref_token_loss(model, p, tokens, targets)
    ))(params)["params"]
    staged, replicated = stack_pp_params_circular(
        params, model.cfg, pp, circles
    )
    mesh = Mesh(np.asarray(jax.devices()[:pp]), (AXIS,))

    def local_loss(staged, replicated, tok, tgt):
        return pp_gpt_loss_circular(
            staged, replicated, model.cfg, tok, tgt, AXIS,
            microbatches=4, circles=circles,
        )

    grad_fn = jax.jit(
        shard_map(
            jax.grad(local_loss, argnums=(0, 1)), mesh=mesh,
            in_specs=(P(AXIS), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=True,
        )
    )
    g_staged, g_rep = grad_fn(staged, replicated, tokens, targets)
    # layer (v*pp + s)*per_group + j sits at staged[s, v, j]:
    # block0 -> [0,0,0], block1 -> [1,0,0], block2 -> [0,1,0],
    # block3 -> [1,1,0]
    for blk, (st, v) in [(0, (0, 0)), (1, (1, 0)),
                         (2, (0, 1)), (3, (1, 1))]:
        np.testing.assert_allclose(
            np.asarray(g_staged["qkv"]["kernel"][st, v, 0]),
            np.asarray(g_ref[f"block{blk}"]["qkv"]["kernel"]),
            atol=2e-4, rtol=2e-4,
        )
    np.testing.assert_allclose(
        np.asarray(g_rep["wte"]["embedding"]),
        np.asarray(g_ref["wte"]["embedding"]),
        atol=2e-4, rtol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(g_rep["head"]["kernel"]),
        np.asarray(g_ref["head"]["kernel"]),
        atol=2e-4, rtol=2e-4,
    )


def test_pp_circular_validation_errors():
    model = _model()  # 4 layers
    params = jax.jit(model.init)(jax.random.PRNGKey(0), _tokens())
    with pytest.raises(ValueError, match="must divide"):
        stack_pp_params_circular(params, model.cfg, 4, 2)  # 8 !| 4
    staged, replicated = stack_pp_params_circular(params, model.cfg, 2, 2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), (AXIS,))

    def local(staged, replicated, tok, tgt):
        return pp_gpt_loss_circular(
            staged, replicated, model.cfg, tok, tgt, AXIS,
            microbatches=1, circles=2,  # M < pp
        )

    with pytest.raises(Exception, match="microbatches >= pp"):
        jax.jit(
            shard_map(local, mesh=mesh,
                      in_specs=(P(AXIS), P(), P(), P()), out_specs=P(),
                      check_vma=False)
        )(staged, replicated, _tokens(b=1), _tokens(b=1))

    # circular-stacked params into a CONTIGUOUS entry point must raise,
    # not silently broadcast the [circles] dim through the matmuls
    def wrong(staged, replicated, tok, tgt):
        return pp_gpt_loss(staged, replicated, model.cfg, tok, tgt, AXIS,
                           microbatches=2)

    with pytest.raises(Exception, match="pp_gpt_loss_circular"):
        jax.jit(
            shard_map(wrong, mesh=mesh,
                      in_specs=(P(AXIS), P(), P(), P()), out_specs=P(),
                      check_vma=False)
        )(staged, replicated, _tokens(), _tokens())


def test_pp_validation_errors():
    model = _model(num_layers=3)  # 3 % 4 != 0
    params = jax.jit(model.init)(jax.random.PRNGKey(0), _tokens())
    with pytest.raises(ValueError, match="must divide num_layers"):
        stack_pp_params(params, model.cfg, PP)

    model = _model()
    params = jax.jit(model.init)(jax.random.PRNGKey(0), _tokens())
    with pytest.raises(Exception, match="microbatches"):
        _pp_fwd(model, params, _tokens(b=3), microbatches=2)


def test_unstack_round_trips():
    """stack -> unstack is the identity for all three param layouts —
    the docs/inference.md reconstruction path as code — and unstacking
    with the WRONG factors raises instead of silently corrupting (JAX
    index clamping would otherwise produce a correct-shaped garbage
    checkpoint)."""
    from conftest import assert_trees_equal
    from horovod_tpu.parallel.pipeline import (
        stack_tp_pp_params, unstack_pp_params,
        unstack_pp_params_circular, unstack_tp_pp_params,
    )

    model = _model()  # 4 layers
    params = jax.jit(model.init)(jax.random.PRNGKey(7), _tokens())["params"]

    staged, rep = stack_pp_params({"params": params}, model.cfg, PP)
    assert_trees_equal(
        unstack_pp_params(staged, rep, model.cfg, PP), params
    )
    with pytest.raises(ValueError, match="leading dims"):
        unstack_pp_params(staged, rep, model.cfg, 2)

    staged, rep = stack_pp_params_circular(
        {"params": params}, model.cfg, 2, 2
    )
    assert_trees_equal(
        unstack_pp_params_circular(staged, rep, model.cfg, 2, 2), params
    )
    with pytest.raises(ValueError, match="leading dims"):
        unstack_pp_params_circular(staged, rep, model.cfg, 2, 1)

    st_sh, st_rep, rep = stack_tp_pp_params(
        {"params": params}, model.cfg, 2, 2
    )
    assert_trees_equal(
        unstack_tp_pp_params(st_sh, st_rep, rep, model.cfg, 2, 2), params
    )
    with pytest.raises(ValueError, match="leading dims"):
        unstack_tp_pp_params(st_sh, st_rep, rep, model.cfg, 4, 2)
