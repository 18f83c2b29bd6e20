"""Sequence-parallel attention tests.

Strategy (SURVEY.md §4 lesson): run the real SPMD schedule on the 8-device
virtual CPU mesh and compare bit-level behavior against the single-device
reference (`local_attention`) — no mocks.  Gradients are compared too,
since both schedules are advertised as training-ready.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import (
    local_attention,
    ring_attention,
    ulysses_attention,
)

B, S, H, D = 2, 32, 8, 16  # global seq 32 over 8 devices = 4 per shard
AXIS = "sp"


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), dtype) * 0.3
    return mk(), mk(), mk()


def _mesh():
    return Mesh(np.asarray(jax.devices()[:8]), (AXIS,))


def _sharded(fn, **kw):
    spec = P(None, AXIS)  # shard dim 1 (sequence)
    return jax.jit(
        shard_map(
            lambda q, k, v: fn(q, k, v, AXIS, **kw),
            mesh=_mesh(),
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_local_attention(self, causal):
        q, k, v = _qkv()
        ref = local_attention(q, k, v, causal=causal)
        out = _sharded(ring_attention, causal=causal)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_grads_match(self):
        q, k, v = _qkv(seed=1)
        sharded = _sharded(ring_attention, causal=True)

        def loss_ref(q, k, v):
            return (local_attention(q, k, v, causal=True) ** 2).sum()

        def loss_ring(q, k, v):
            return (sharded(q, k, v) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_bfloat16_io(self):
        q, k, v = _qkv(seed=2, dtype=jnp.bfloat16)
        out = _sharded(ring_attention, causal=True)(q, k, v)
        assert out.dtype == jnp.bfloat16
        ref = local_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True,
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=0.05, rtol=0.05
        )

    def test_long_context_memory_shape(self):
        """Each shard only ever materializes S/P-sized score blocks — the
        schedule compiles with per-device attention matrices of
        (s_local, s_local), not (S, S)."""
        q, k, v = _qkv(seed=3)
        fn = _sharded(ring_attention, causal=False)
        compiled = fn.lower(q, k, v).compile()
        # sanity: it runs; the (S,S) matrix never exists on one device by
        # construction of the scan (block is (B,H,4,4) here)
        out = compiled(q, k, v)
        assert out.shape == (B, S, H, D)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_local_attention(self, causal):
        q, k, v = _qkv(seed=4)
        ref = local_attention(q, k, v, causal=causal)
        out = _sharded(ulysses_attention, causal=causal)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_grads_match(self):
        q, k, v = _qkv(seed=5)
        sharded = _sharded(ulysses_attention, causal=True)
        g_ref = jax.grad(
            lambda *a: (local_attention(*a, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_uly = jax.grad(
            lambda *a: (sharded(*a) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g_uly, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_head_divisibility_error(self):
        q = jnp.zeros((1, 8, 6, 4))  # 6 heads over 8 devices
        with pytest.raises(ValueError, match="divisible"):
            _sharded(ulysses_attention)(q, q, q)


class TestLocalAttentionOffsets:
    def test_global_causal_offsets(self):
        """q_offset/kv_offset place the causal triangle in global coords."""
        q, k, v = _qkv(seed=6)
        full = local_attention(q, k, v, causal=True)
        # second half of queries attending the full key set
        half = local_attention(
            q[:, S // 2:], k, v, causal=True, q_offset=S // 2, kv_offset=0
        )
        np.testing.assert_allclose(
            np.asarray(half), np.asarray(full[:, S // 2:]), atol=2e-5,
            rtol=2e-5,
        )


class TestZigzagRing:
    """Load-balanced causal ring: zigzag layout round-trips and the
    distributed result matches single-device causal attention."""

    def test_shard_roundtrip(self):
        from horovod_tpu.parallel import zigzag_shard, zigzag_unshard

        x = jnp.arange(B * S * 3, dtype=jnp.float32).reshape(B, S, 3)
        z = zigzag_shard(x, 8, axis=1)
        back = zigzag_unshard(z, 8, axis=1)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        # rank 0's shard is chunks (0, 15): rows 0,1 and 30,31
        s_local = S // 8
        np.testing.assert_array_equal(
            np.asarray(z[:, :s_local]),
            np.asarray(jnp.concatenate([x[:, 0:2], x[:, 30:32]], axis=1)),
        )

    def test_matches_local_attention_causal(self):
        from horovod_tpu.parallel import (
            ring_attention_zigzag, zigzag_shard, zigzag_unshard,
        )

        q, k, v = _qkv(3)
        ref = local_attention(q, k, v, causal=True)
        zz = lambda t: zigzag_shard(t, 8, axis=1)
        out_z = _sharded(ring_attention_zigzag)(zz(q), zz(k), zz(v))
        out = zigzag_unshard(out_z, 8, axis=1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_grads_match(self):
        from horovod_tpu.parallel import (
            ring_attention_zigzag, zigzag_shard, zigzag_unshard,
        )

        q, k, v = _qkv(4)
        zz = lambda t: zigzag_shard(t, 8, axis=1)
        uz = lambda t: zigzag_unshard(t, 8, axis=1)
        w = jnp.asarray(
            np.random.RandomState(5).randn(B, S, H, D), jnp.float32
        )

        def loss_ref(q, k, v):
            return (local_attention(q, k, v, causal=True) * w).sum()

        def loss_zig(q, k, v):
            out = _sharded(ring_attention_zigzag)(zz(q), zz(k), zz(v))
            return (uz(out) * w).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_zig, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
            )

    def test_odd_local_length_rejected(self):
        from horovod_tpu.parallel import ring_attention_zigzag

        q = jnp.zeros((1, 8, 2, 4))  # 8 over 8 devices -> s_local 1 (odd)
        with pytest.raises(Exception, match="even local sequence"):
            _sharded(ring_attention_zigzag)(q, q, q)


def test_zigzag_positions_match_layout():
    """zigzag_positions(i) must be exactly the global positions of rank
    i's rows after zigzag_shard + contiguous split."""
    from horovod_tpu.parallel import zigzag_positions, zigzag_shard

    size, s = 4, 24
    x = jnp.arange(s)  # value == global position
    z = zigzag_shard(x, size)
    s_local = s // size
    for i in range(size):
        shard = np.asarray(z[i * s_local:(i + 1) * s_local])
        np.testing.assert_array_equal(
            shard, np.asarray(zigzag_positions(i, size, s_local))
        )


@pytest.mark.parametrize("schedule", ["ring", "zigzag", "ulysses"])
def test_values_wider_than_keys(schedule):
    """The value width is the values' own in every schedule, as in the
    flash kernels (differential attention sends values twice as wide as
    its keys, over half as many key/value heads): the output and the
    three gradients against ``local_attention``, causal."""
    from horovod_tpu.parallel import (
        ring_attention_zigzag, zigzag_shard, zigzag_unshard,
    )

    rng = np.random.RandomState(6)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(B, S, heads, width), jnp.float32) * 0.3
    q, k, v, w = mk(H, D), mk(H // 2, D), mk(H // 2, 2 * D), mk(H, 2 * D)
    rep = lambda t: jnp.repeat(t, 2, axis=2)

    def plain(q, k, v):
        return local_attention(q, rep(k), rep(v), causal=True)

    def sharded(q, k, v):
        if schedule == "ring":
            return _sharded(ring_attention, causal=True)(q, k, v)
        if schedule == "ulysses":   # attends at full heads
            return _sharded(ulysses_attention, causal=True)(
                q, rep(k), rep(v))
        zz = lambda t: zigzag_shard(t, 8, axis=1)
        return zigzag_unshard(
            _sharded(ring_attention_zigzag)(zz(q), zz(k), zz(v)), 8, axis=1)

    out = sharded(q, k, v)
    assert out.shape == (B, S, H, 2 * D)
    np.testing.assert_allclose(out, plain(q, k, v), atol=2e-5, rtol=2e-5)
    got, want = (jax.grad(lambda *a: (f(*a) * w).sum(),
                          argnums=(0, 1, 2))(q, k, v)
                 for f in (sharded, plain))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)
