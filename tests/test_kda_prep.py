"""The float32 chain in front of the delta rule as a Pallas kernel pair
(``ops/kda_prep.py``) against the chain as XLA compiles it
(``models/transformer.py:kda_prep_chain``), through the Pallas
interpreter on the CPU at small shapes: values and every gradient over
several token tiles and head blocks, the zeros before the sequence, the
filter's reach across a tile boundary in both directions, the model's
strongest decays, and the rule that reads the path from the shape."""

import functools

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models.transformer import kda_prep_chain
from horovod_tpu.ops import flash_attention, kda_prep

TAPS = 4


@pytest.fixture
def tiles(monkeypatch):
    """Tiles small enough that the shapes below cross them: 32 tokens (a
    program's steps then take 32 rows) and two heads a program."""
    monkeypatch.setattr(kda_prep, "TOKEN_TILE", 32)
    monkeypatch.setattr(kda_prep, "HEAD_BLOCK", 2)


def _inputs(batch, seq, heads, hd, dtype=jnp.float32, bias=0.0, a=(1.0, 16.0)):
    inner = heads * hd
    ks = jax.random.split(jax.random.PRNGKey(seq + hd), 9)
    args = (jax.random.normal(ks[0], (batch, seq, 3 * inner)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (TAPS, 3 * inner)),
            jax.random.normal(ks[2], (batch, seq, inner)).astype(dtype),
            bias + jax.random.normal(ks[3], (inner,)),
            jnp.log(jax.random.uniform(ks[4], (heads,), jnp.float32, *a)))
    weights = tuple(jax.random.normal(k, (batch, seq, heads, hd))
                    for k in ks[5:])
    return args, weights


# the small models' head size, two sequences of two token tiles: the
# shape every test but the first case shares, so that each of the three
# programs below is compiled once for the file
SMALL = (2, 64, 2, 16)


def _kernel(*args):
    b, s, inner = args[2].shape
    heads = args[4].shape[0]
    return kda_prep.kda_prep(
        *args, tiles=kda_prep.plan(s, heads, inner // heads, TAPS))


_run = jax.jit(_kernel)


@functools.partial(jax.jit, static_argnums=0)
def _both(fn, args, weights):
    """The four outputs and the gradients of their weighted sum in all
    five inputs."""
    loss = lambda *a: sum(jnp.sum(o.astype(jnp.float32) * w)
                          for o, w in zip(fn(*a), weights))
    return fn(*args), jax.grad(loss, argnums=range(5))(*args)


@jax.jit
def _dfused_of_q(args, weight):
    return jax.grad(lambda f: jnp.sum(_kernel(f, *args[1:])[0] * weight))(
        args[0])


def _close(got, want, rel):
    got, want = (t.astype(jnp.float32) for t in (got, want))
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) <= rel * float(
        jnp.abs(want).max()), (float(jnp.abs(got - want).max()),
                               float(jnp.abs(want).max()))


@pytest.mark.parametrize("batch,seq,heads,hd,dtype", [
    # two token tiles and two head blocks at the cell's head size and dtype
    (1, 64, 4, 128, jnp.bfloat16),
    (*SMALL, jnp.float32),
])
def test_kernels_agree_with_the_chain_in_values_and_every_gradient(
        tiles, batch, seq, heads, hd, dtype):
    """``q``, ``k``, ``v``, ``g`` and the gradients in ``fused``,
    ``conv_kernel``, ``decay``, ``dt_bias`` and ``a_log``: float32 sums
    in another order, and in bfloat16 the one rounding where the chain
    has it."""
    args, weights = _inputs(batch, seq, heads, hd, dtype)
    assert kda_prep.plan(seq, heads, hd, TAPS) == (32, 2)
    out, grads = _both(_kernel, args, weights)
    want_out, want_grads = _both(kda_prep_chain, args, weights)
    for got, want in zip(out, want_out):
        assert got.dtype == want.dtype
        _close(got, want, 1e-6 if dtype == jnp.float32 else 2 ** -8)
    for got, want, arg in zip(grads, want_grads, args):
        assert got.dtype == want.dtype == arg.dtype
        _close(got, want, 1e-5 if dtype == jnp.float32 else 2 ** -7)


def test_the_first_tile_sees_zeros_before_the_sequence(tiles):
    """Token 0's filter output is its own input times the last tap, and
    the clamped block in front of the first tile (the tile itself) is
    not read: ``v`` of token 0 is ``silu(w[-1] * x[0])``."""
    args, _ = _inputs(*SMALL)
    fused, w = args[0], args[1]
    v = _run(*args)[2].reshape(2, 64, -1)
    want = jax.nn.silu(fused[:, 0, 64:] * w[-1, 64:])
    assert float(jnp.abs(v[:, 0] - want).max()) < 1e-6
    moved = _run(fused.at[:, 1:].add(1.0), *args[1:])[2].reshape(2, 64, -1)
    assert float(jnp.abs(moved[:, 0] - v[:, 0]).max()) == 0.0


def test_the_filter_reaches_across_a_tile_boundary_both_ways(tiles):
    """Forward, token 30 (in tile 0) moves outputs 30–33 (33 is in tile
    1) and nothing earlier or later; backward, ``dfused`` of token 30
    sees ``dq`` of token 33 and of no token past it."""
    args, weights = _inputs(*SMALL)
    base = _run(*args)
    moved = _run(args[0].at[:, 30].add(0.5), *args[1:])
    for got, was in zip(moved[:3], base[:3]):
        apart = jnp.abs(got - was).max(axis=(0, 2, 3))
        assert float(apart[:30].max()) == 0.0
        assert float(apart[30:34].min()) > 1e-4
        assert float(apart[34:].max()) == 0.0
    only = lambda at: jnp.zeros_like(weights[0]).at[:, at].set(1.0)
    reach = lambda at: jnp.abs(_dfused_of_q(args, only(at))).max(axis=(0, 2))
    assert float(reach(33)[30]) > 1e-6 and float(reach(33)[29]) == 0.0
    assert float(reach(34)[30]) == 0.0 and float(reach(34)[31]) > 1e-6


def test_kernels_stay_finite_under_the_models_strongest_decays(tiles):
    """``A`` at 16 and a softplus of six and more: ``g`` about -100 a
    token; every output and gradient finite, ``g``'s as the chain has
    them."""
    args, weights = _inputs(*SMALL, bias=6.0, a=(15.9, 16.0))
    out, grads = _both(_kernel, args, weights)
    want_out, want_grads = _both(kda_prep_chain, args, weights)
    assert float(out[3].min()) < -90.0
    for got, want in zip((*out, *grads), (*want_out, *want_grads)):
        assert bool(jnp.isfinite(got).all())
        _close(got, want, 1e-5)


@pytest.mark.parametrize("compiled,seq,heads,hd,taps,want", [
    (True, 16384, 32, 128, 4, (1024, 4)),   # the cell
    (True, 16384, 32, 256, 4, (1024, 4)),
    (True, 8704, 6, 128, 4, (544, 3)),      # tile and heads that divide
    (True, 48, 32, 128, 4, (48, 4)),        # one tile of whole 16 rows
    (True, 16384, 32, 64, 4, None),         # half a lane tile a head
    (True, 16384, 32, 16, 4, None),
    (True, 1000, 32, 128, 4, None),         # no tile of 16 rows divides
    (True, 16384, 32, 128, 10, None),       # a reach past eight rows
    (False, 64, 4, 16, 4, (64, 4)),         # the interpreter: any head
    (False, 1000, 4, 16, 4, None),
])
def test_the_path_is_read_from_the_shape(monkeypatch, compiled, seq, heads,
                                         hd, taps, want):
    """Compiled, a head is whole lane tiles; everywhere the token tile is
    whole 16-row tiles that divide the sequence and the filter reaches at
    most eight rows back.  ``None`` is the caller's chain."""
    monkeypatch.setattr(flash_attention, "_interpret_for_backend",
                        lambda backend: not compiled)
    assert kda_prep.plan(seq, heads, hd, taps) == want
