"""The two float32 chains of a Mamba-2 mixer as Pallas kernel pairs
(``ops/ssm_chain.py``) against the chains as XLA compiles them
(``models/transformer.py:ssm_prep_chain`` and ``ssm_norm_chain``),
through the Pallas interpreter on the CPU at small shapes: values and
every gradient at one group and at eight, with and without the filter's
bias; the zeros before the sequence; the filter's reach across a tile
boundary in both directions; the three producers of ``d fused``; the
rule that reads the path from the shape; what a rematerialised block
runs twice and keeps; the gauges."""

import functools

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import scopes
from horovod_tpu.models.transformer import (gpt, mamba_mixer, ssm_norm_chain,
                                            ssm_prep_chain)
from horovod_tpu.obs.registry import get_registry, reset_registry
from horovod_tpu.ops import ssm_chain

TAPS = 4
EPS = 1e-5


@pytest.fixture
def tiles(monkeypatch):
    """Tiles small enough that the shapes below cross them: 32 tokens a
    program of the front pair, 32 (one group of 128 lanes) or 16 tokens
    a program of the norm's."""
    monkeypatch.setattr(ssm_chain, "TOKEN_TILE", 32)
    monkeypatch.setattr(ssm_chain, "NORM_BLOCK", 32 * 128)


# (batch, seq, heads, head_dim, groups, state): one group 256 lanes wide
# beside B and C of one lane tile each, and eight groups of one lane
# tile beside B and C of eight lane tiles each (two lane blocks of x, of
# B and of C a token tile, four groups a block)
ONE = (2, 64, 4, 64, 1, 128)
EIGHT = (1, 64, 8, 128, 8, 128)


def _inputs(batch, seq, heads, hd, groups, state, dtype=jnp.float32,
            bias=True):
    inner, bc = heads * hd, groups * state
    width = inner + 2 * bc
    ks = jax.random.split(jax.random.PRNGKey(seq + inner + groups), 9)
    args = dict(
        fused=jax.random.normal(
            ks[0], (batch, seq, inner + width + heads)).astype(dtype),
        conv_kernel=0.5 * jax.random.normal(ks[1], (TAPS, width)),
        conv_bias=jax.random.normal(ks[2], (width,)) if bias else None,
        y=jax.random.normal(ks[3], (batch, seq, heads, hd)).astype(dtype),
        norm_scale=1.0 + 0.1 * jax.random.normal(ks[4], (inner,)))
    weights = tuple(jax.random.normal(k, shape) for k, shape in zip(
        ks[5:], [(batch, seq, heads, hd), (batch, seq, groups, state),
                 (batch, seq, groups, state), (batch, seq, inner)]))
    return args, weights


def _both_chains(kernels, heads, hd, groups, state):
    """``fn(fused, conv_kernel, conv_bias, y, norm_scale) -> (x, B, C,
    normed)`` through the kernel pairs or through the kept chains."""
    inner = heads * hd

    def fn(fused, conv_kernel, conv_bias, y, norm_scale):
        tiles = ssm_chain.plan(fused.shape[1], inner, groups, state, TAPS)
        prep, norm = (ssm_prep_chain, ssm_norm_chain) if not kernels else (
            functools.partial(ssm_chain.ssm_prep, tiles=tiles),
            functools.partial(ssm_chain.ssm_norm, tiles=tiles))
        return (*prep(fused, conv_kernel, conv_bias, inner=inner,
                      heads=heads, groups=groups),
                norm(y, fused, norm_scale, groups=groups, eps=EPS))

    return fn


@functools.partial(jax.jit, static_argnums=0)
def _both(fn, args, weights):
    """The four outputs and the gradients of their weighted sum in every
    input (a filter without a bias has no gradient for it)."""
    args = {k: v for k, v in args.items() if v is not None}
    call = lambda a: fn(a["fused"], a["conv_kernel"], a.get("conv_bias"),
                        a["y"], a["norm_scale"])
    loss = lambda a: sum(jnp.sum(o.astype(jnp.float32) * w)
                         for o, w in zip(call(a), weights))
    return call(args), jax.grad(loss)(args)


def _close(got, want, rel):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (t.astype(jnp.float32) for t in (got, want))
    assert float(jnp.abs(got - want).max()) <= rel * float(
        jnp.abs(want).max()), (float(jnp.abs(got - want).max()),
                               float(jnp.abs(want).max()))


@pytest.mark.parametrize("shape,dtype,bias", [
    (ONE, jnp.float32, True),
    (ONE, jnp.float32, False),
    (EIGHT, jnp.float32, True),
    (EIGHT, jnp.float32, False),
    (EIGHT, jnp.bfloat16, True),    # the cells' dtype
])
def test_kernels_agree_with_the_chains_in_values_and_every_gradient(
        tiles, shape, dtype, bias):
    """``x``, ``B``, ``C``, the normed product and the gradients in
    ``fused``, the filter, its bias, ``y`` and ``norm_scale``: float32
    sums in another order, and in bfloat16 the one rounding where the
    chains have it."""
    args, weights = _inputs(*shape, dtype=dtype, bias=bias)
    assert ssm_chain.plan(shape[1], shape[2] * shape[3], shape[4], shape[5],
                          TAPS) is not None
    out, grads = _both(_both_chains(True, *shape[2:]), args, weights)
    want_out, want_grads = _both(_both_chains(False, *shape[2:]), args,
                                 weights)
    assert set(grads) == set(want_grads) == {
        k for k, v in args.items() if v is not None}
    for got, want in zip(out, want_out):
        _close(got, want, 1e-6 if dtype == jnp.float32 else 2 ** -8)
    for name in grads:
        assert grads[name].dtype == args[name].dtype
        _close(grads[name], want_grads[name],
               1e-5 if dtype == jnp.float32 else 2 ** -7)


def _prep(args, shape):
    _, _, heads, hd, groups, state = shape
    tiles = ssm_chain.plan(shape[1], heads * hd, groups, state, TAPS)
    return ssm_chain.ssm_prep(
        args["fused"], args["conv_kernel"], args["conv_bias"],
        inner=heads * hd, heads=heads, groups=groups, tiles=tiles)


_run = jax.jit(_prep, static_argnums=1)


def test_the_first_tile_sees_zeros_before_the_sequence(tiles):
    """Token 0's filter output is its own input times the last tap plus
    the bias, and the clamped block in front of the first tile (the tile
    itself) is not read."""
    args, _ = _inputs(*ONE)
    inner, width = 256, 512
    fused, w, bias = args["fused"], args["conv_kernel"], args["conv_bias"]
    x, B, C = _run(args, ONE)
    got = jnp.concatenate([t.reshape(2, 64, -1) for t in (x, B, C)], -1)
    want = jax.nn.silu(fused[:, 0, inner:inner + width] * w[-1] + bias)
    assert float(jnp.abs(got[:, 0] - want).max()) < 1e-6
    later = dict(args, fused=fused.at[:, 1:].add(1.0))
    moved = jnp.concatenate(
        [t.reshape(2, 64, -1) for t in _run(later, ONE)], -1)
    assert float(jnp.abs(moved[:, 0] - got[:, 0]).max()) == 0.0


def test_the_filter_reaches_across_a_tile_boundary_both_ways(tiles):
    """Forward, token 30 (in tile 0) moves outputs 30-33 (33 is in tile
    1) of ``x``, ``B`` and ``C`` and nothing earlier or later; backward,
    ``d fused`` of token 30 sees the cotangent of token 33 and of no
    token past it."""
    args, weights = _inputs(*ONE)
    base = _run(args, ONE)
    moved = _run(dict(args, fused=args["fused"].at[:, 30].add(0.5)), ONE)
    for got, was in zip(moved, base):
        apart = jnp.abs(got - was).max(axis=(0, 2, 3))
        assert float(apart[:30].max()) == 0.0
        assert float(apart[30:34].min()) > 1e-4
        assert float(apart[34:].max()) == 0.0

    @jax.jit
    def dfused(weight):
        return jax.grad(lambda f: jnp.sum(
            _prep(dict(args, fused=f), ONE)[2] * weight))(args["fused"])

    only = lambda at: jnp.zeros_like(weights[2]).at[:, at].set(1.0)
    reach = lambda at: jnp.abs(dfused(only(at))).max(axis=(0, 2))
    assert float(reach(33)[30]) > 1e-6 and float(reach(33)[29]) == 0.0
    assert float(reach(34)[30]) == 0.0 and float(reach(34)[31]) > 1e-6


def test_each_part_of_dfused_comes_from_its_own_producer(tiles):
    """``z``'s lanes from the norm's backward, ``xBC``'s from the front
    pair's, ``dt``'s from the slice XLA keeps: none is dropped, none
    leaks into another's lanes."""
    batch, seq, heads, hd, groups, state = ONE
    inner, width = heads * hd, heads * hd + 2 * groups * state
    args, weights = _inputs(*ONE)
    dt_weight = jax.random.normal(jax.random.PRNGKey(7),
                                  (batch, seq, heads))

    def dfused(kernels, parts):
        fn = _both_chains(kernels, *ONE[2:])

        def loss(fused):
            *front, normed = fn(fused, args["conv_kernel"],
                                args["conv_bias"], args["y"],
                                args["norm_scale"])
            terms = {
                "xbc": sum(jnp.sum(o * w) for o, w in zip(front, weights)),
                "z": jnp.sum(normed * weights[3]),
                "dt": jnp.sum(fused[..., inner + width:] * dt_weight)}
            return sum(terms[p] for p in parts)

        return jax.jit(jax.grad(loss))(args["fused"])

    lanes = {"z": slice(0, inner), "xbc": slice(inner, inner + width),
             "dt": slice(inner + width, None)}
    whole = dfused(True, lanes)
    _close(whole, dfused(False, lanes), 1e-5)
    for part, own in lanes.items():
        alone = dfused(True, (part,))
        assert float(jnp.abs(alone[..., own]).min()) > 0.0
        assert float(jnp.abs(alone.at[..., own].set(0.0)).max()) == 0.0
        _close(alone[..., own], whole[..., own], 1e-6)
    assert float(jnp.abs(whole[..., lanes["dt"]] - dt_weight).max()) == 0.0


@pytest.mark.parametrize("seq,inner,groups,state,taps,want", [
    (8192, 4096, 1, 128, 4, (1024, 128, 128)),     # granite's cell
    (16384, 4096, 8, 128, 4, (1024, 512, 1024)),   # Nemotron's
    (8704, 768, 2, 128, 4, (544, 256, 1088)),      # tiles that divide
    (4096, 2048, 2, 1024, 4, (1024, 1024, 512)),   # a state past the block
    (48, 256, 1, 128, 4, (48, 128, 48)),           # one tile of 16 rows
    (64, 128, 1, 128, 9, (64, 128, 64)),           # a reach of eight rows
    (8192, 4096, 1, 64, 4, None),       # a state of half a lane tile
    (8192, 1024, 8, 16, 4, None),       # B and C lane-whole, a state not
    (8192, 1280, 2, 384, 4, None),      # no whole groups divide inner
    (8192, 4032, 1, 128, 4, None),      # inner not whole lane tiles
    (8192, 4096, 64, 128, 4, None),     # a group half a lane tile
    (32, 64, 1, 16, 4, None),           # the tiny test models
    (1000, 4096, 1, 128, 4, None),      # no tile of 16 rows divides
    (8192, 4096, 1, 128, 10, None),     # a reach past eight rows
])
def test_the_path_is_read_from_the_shape(seq, inner, groups, state, taps,
                                         want):
    """``inner``, the state and a group's lanes whole 128-lane tiles,
    compiled or interpreted alike; a lane block of whole groups of ``B``
    that divides ``inner`` and ``groups x state``; token tiles of whole 16 rows
    that divide the sequence; the filter at most eight rows back.
    ``None`` is the caller's chains."""
    assert ssm_chain.plan(seq, inner, groups, state, taps) == want


def _names(jaxpr):
    """The names of a jaxpr's ``pallas_call``s, a jitted function's
    counted at each of its call sites."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.extend(_names(sub))
    return out


@pytest.mark.parametrize("state,kernels", [(128, True), (64, False)])
def test_mamba_mixer_lowers_what_the_plan_says(state, kernels):
    """Two heads of 64 beside a state of 128: the kernel pairs; beside a
    state of 64 (``B`` and ``C`` half a lane tile each): the chains, and
    no kernel but the scan's."""
    cfg = gpt("granite-4.0-h-micro", ssm_heads=2, ssm_head_dim=64,
              ssm_state=state, ssm_chunk=16, emb_dim=32).cfg
    width = cfg.ssm_inner + 2 * state
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w_in = jax.random.normal(ks[0], (32, cfg.ssm_inner + width + 2)) / 6
    w_out = jax.random.normal(ks[1], (cfg.ssm_inner, 32)) / 11

    def mixer(h):
        return mamba_mixer(
            cfg, h, in_proj=lambda t: t @ w_in,
            conv_kernel=0.5 * jax.random.normal(ks[2], (TAPS, width)),
            conv_bias=jnp.zeros((width,)), dt_bias=jnp.zeros((2,)),
            a_log=jnp.zeros((2,)), d_skip=jnp.ones((2,)),
            norm_scale=jnp.ones((cfg.ssm_inner,)),
            out_proj=lambda t: t @ w_out)

    h = jax.random.normal(ks[3], (1, 32, 32))
    names = _names(jax.make_jaxpr(jax.grad(
        lambda t: jnp.sum(mixer(t))))(h).jaxpr)
    ours = sorted(n for n in names if n.startswith("ssm_"))
    assert ours == (["ssm_norm_bwd", "ssm_norm_fwd", "ssm_prep_bwd",
                     "ssm_prep_fwd"] if kernels else [])
    assert names.count("ssd_fwd") == 1


def _model(**settings):
    """Two Mamba-2 layers, by default at the narrowest widths the
    kernels take: two heads of 64, one group, a state of 128 (``xBC``
    384 lanes)."""
    sizes = dict(layer_types=("mamba",) * 2, num_layers=2, vocab_size=256,
                 emb_dim=64, num_heads=4, num_kv_heads=2, ssm_heads=2,
                 ssm_head_dim=64, ssm_state=128, ssm_chunk=16, max_len=64)
    return gpt("granite-4.0-h-micro", **{**sizes, **settings})


def _loss(model, tokens):
    def loss(params):
        logits = model.apply({"params": params}, tokens[:, :-1])
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32)),
            tokens[:, 1:, None], axis=-1)
        return -picked.mean()

    return loss


def test_a_model_at_lane_whole_widths_runs_the_kernels(monkeypatch):
    """Loss and every parameter's gradient of a two-layer model through
    the kernel pairs inside ``block_math``, against the same model on
    the chains (``plan`` saying ``None``); the gauges say which ran."""
    model = _model(dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 256)
    params = jax.jit(model.init)(jax.random.PRNGKey(1),
                                 tokens[:, :-1])["params"]
    gauge = lambda name: get_registry().gauge(f"ssm_chain.{name}").value

    reset_registry()
    got = jax.jit(jax.value_and_grad(_loss(model, tokens)))(params)
    assert (gauge("layers"), gauge("kernel_layers")) == (2, 2)
    monkeypatch.setattr(ssm_chain, "plan", lambda *shape: None)
    reset_registry()
    want = jax.jit(jax.value_and_grad(_loss(model, tokens)))(params)
    assert (gauge("layers"), gauge("kernel_layers")) == (2, 0)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        _close(g, w, 2e-5)


def test_a_narrow_model_counts_no_kernel_layer():
    """The tiny test models (inner 64, ``xBC`` 96 lanes) keep the
    chains: the gauges say two layers and none on the kernels."""
    model = _model(ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                   dtype=jnp.float32)
    reset_registry()
    tokens = jnp.zeros((1, 16), jnp.int32)
    jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    gauge = lambda name: get_registry().gauge(f"ssm_chain.{name}").value
    assert (gauge("layers"), gauge("kernel_layers")) == (2, 0)


@pytest.mark.parametrize("policy", ["nothing_saveable",
                                    "dots_with_no_batch_dims_saveable"])
def test_a_rematerialised_block_runs_the_chains_twice_and_keeps_nothing(
        policy):
    """Under ``jax.checkpoint`` with ``block_remat_policy`` each block
    runs ``ssm_prep_fwd`` and ``ssm_norm_fwd`` twice (the outputs carry
    no name a block keeps) and ``ssd_fwd`` once, and nothing float32 as
    wide as ``xBC``, ``inner`` or ``fused`` a token goes from the
    forward to the backward."""
    model = _model(dtype=jnp.bfloat16, remat=True, remat_policy=policy)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 33), 0, 256)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            tokens[:, :-1])["params"]
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model, tokens)))(params).jaxpr
    names = _names(jaxpr)
    assert names.count("ssm_prep_fwd") == names.count("ssm_norm_fwd") == 4
    assert names.count("ssm_prep_bwd") == names.count("ssm_norm_bwd") == 2
    assert names.count("ssd_fwd") == 2
    cfg = model.cfg
    wide = {cfg.ssm_inner, cfg.ssm_inner + 2 * cfg.ssm_state,
            2 * cfg.ssm_inner + 2 * cfg.ssm_state + cfg.ssm_heads}
    blocks = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    assert blocks
    for eqn in blocks:
        for var in eqn.invars:
            aval = var.aval
            assert not (aval.dtype == jnp.float32 and aval.ndim == 3
                        and aval.shape[1] == 32 and aval.shape[2] in wide), aval


def test_the_scopes_hold_the_kernels():
    """The front pair lowers under ``ssm/ssm_prep``, the gate and norm
    under ``ssm/ssm_norm``, forward and backward."""
    model = _model(dtype=jnp.float32)
    tokens = jnp.zeros((1, 17), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            tokens[:, :-1])["params"]
    text = jax.jit(jax.grad(_loss(model, tokens))).lower(params).as_text(
        debug_info=True)
    # the calls sit behind an inner jit: its call site carries the scope
    for scope, call, kernel in (
            ("ssm_prep", "_prep_forward", "ssm_prep_fwd"),
            ("ssm_prep", "_prep_backward", "ssm_prep_bwd"),
            ("ssm_norm", "_norm_forward", "ssm_norm_fwd"),
            ("ssm_norm", "_norm_backward", "ssm_norm_bwd")):
        assert f"block0/ssm/{scope}/jit({call})" in text, call
        assert f"{kernel}/pallas_call" in text, kernel
    assert scopes.SSM_PREP in scopes.SCOPES
